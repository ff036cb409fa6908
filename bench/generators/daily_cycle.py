"""Poisson arrivals with a daily cycle: rigid HPC jobs of a machine.

A copy of the Seth-like generator the repository's CPU benchmarks use,
with its constants moved into the traffic file:

- inter-arrival: exponential with mean ``day_interarrival_s`` from
  ``day_hours[0]`` to ``day_hours[1]`` o'clock (inclusive), else
  ``night_interarrival_s``; times are whole seconds, at least 1 s apart;
- processors: drawn uniformly from ``procs_weights`` expanded into a
  list (a weight is a repeat count); nodes = max(1, procs // cores),
  cores per node = min(procs, cores);
- runtime: ``int(lognormvariate(mu, sigma)) + 1``, capped at
  ``max_runtime_s``;
- estimate: ``int(runtime * U(lo, hi)) + estimate_pad_s``, capped at
  ``max_estimate_s``;
- user: uniform in 1..``users``;
- memory per node: with ``mem_per_core_mb``, any whole number of MB
  from 1 to ``mem_per_core_mb`` x the cores the job takes on a node;
  else one of ``mem_mb``, as ``seth_jobs`` draws it.

Each record carries both request forms: ``requested_nodes`` /
``requested_resources`` and the SWF-style totals
(``requested_processors``, ``requested_memory``) that the simulator's
default job factory maps back onto the same per-node request.

Every seed draws its own jobs: with ``mem_mb`` in the parameters the
generator is ``seth_jobs`` draw for draw.

The class is a ``SyntheticWorkload`` so that ``Experiment`` reseeds it
for every repeat (``seed + rep``) with no change to the program.
"""
from __future__ import annotations

import math
import random
from typing import Dict, Iterator, Tuple

from repro.workloads.synthetic import SyntheticWorkload


class DailyCycleWorkload(SyntheticWorkload):
    def __init__(self, n_jobs: int, seed: int, cores_per_node: int,
                 params: Dict) -> None:
        super().__init__(n_jobs, seed=seed, cores_per_node=cores_per_node)
        self.params = params
        self.procs = [int(p) for p, w in params["procs_weights"].items()
                      for _ in range(int(w))]

    def _draw(self, rng: random.Random) -> Tuple:
        """One job's random draws, in the order ``seth_jobs`` makes them:
        the arrival's uniform, processors, raw runtime, user, estimate
        factor, memory per node."""
        p = self.params
        u, procs = rng.random(), rng.choice(self.procs)
        runtime = rng.lognormvariate(*p["runtime_lognormal"])
        user = rng.randint(1, p["users"])
        factor = rng.uniform(*p["estimate_factor"])
        if "mem_per_core_mb" in p:
            cores = min(procs, self.cores_per_node)
            mem = rng.randint(1, p["mem_per_core_mb"] * cores)
        else:
            mem = rng.choice(p["mem_mb"])
        return u, procs, runtime, user, factor, mem

    def __iter__(self) -> Iterator[Dict[str, object]]:
        p = self.params
        day_lo, day_hi = p["day_hours"]
        cores = self.cores_per_node
        t = 0
        rng = random.Random(self.seed)
        for i in range(self.n_jobs):
            u, procs, runtime, user, factor, mem = self._draw(rng)
            hour = (t // 3600) % 24
            rate = (p["day_interarrival_s"] if day_lo <= hour <= day_hi
                    else p["night_interarrival_s"])
            # random.expovariate(1 / rate), from its uniform
            t += int(-math.log(1.0 - u) / (1.0 / rate)) + 1
            nodes = max(1, procs // cores)
            dur = min(int(runtime) + 1, p["max_runtime_s"])
            est = min(int(dur * factor) + p["estimate_pad_s"],
                      p["max_estimate_s"])
            per_node = {"core": min(procs, cores), "mem": mem}
            yield {
                "id": str(i),
                "submit": t,
                "duration": dur,
                "expected_duration": est,
                "requested_nodes": nodes,
                "requested_resources": per_node,
                "requested_processors": per_node["core"] * nodes,
                "requested_memory": mem * nodes,
                "user": user,
                "status": 1,
            }


def workload(n_jobs: int, seed: int, cores_per_node: int,
             params: Dict) -> DailyCycleWorkload:
    """The harness's entry: one lane's workload for ``seed``."""
    return DailyCycleWorkload(n_jobs, seed, cores_per_node, params)
