"""Best-Fit's node order on the fleet: the float64 rank table
(``fleet.state.bf_key``, DESIGN.md §8).

The host ``BestFit`` sorts nodes by the float64 load
``Σ_r used/max(cap, 1)``; float64 splits some loads that are equal as
fractions (on 8 cores x 12,288 MB, 0/8 + 2305/12288 reads above
1/8 + 769/12288), and neither float32 nor exact arithmetic orders those
pairs as the host does.  The table holds each usage vector's dense rank:

* exhaustively, ranks order every pair of usage vectors as their float64
  loads do, equal exactly where the loads are equal — on Seth's nodes,
  RICC's, and a machine of two node groups, ranked over the union;
* a crafted split tie on RICC-shaped nodes: all four Best-Fit rows on the
  fleet place a job where the host ``Simulator`` does;
* a small RICC-shaped ``Experiment`` of all eight Table-2 rows on the fleet
  is trace-equal to the host;
* a machine above the table's limit runs its Best-Fit rows on the host,
  tagged, and its FirstFit rows on the fleet;
* the allocator probe takes the first fitting nodes in its policy's order,
  against a numpy model.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                    FirstInFirstOut, LongestJobFirst,
                                    ShortestJobFirst)
from repro.core.job import JobFactory
from repro.core.resources import ResourceManager
from repro.experimentation import Experiment
from repro.fleet.engine import ALLOC_BF, ALLOC_FF, _select_nodes
from repro.fleet.state import (BF_KEY_MAX_ENTRIES, bf_key, bf_key_entries,
                               launch_bf_key)
from repro.workloads.synthetic import SyntheticWorkload

SETH = {"groups": {"seth": {"core": 4, "mem": 1024}}, "nodes": {"seth": 3}}
RICC = {"groups": {"ricc": {"core": 8, "mem": 12288}}, "nodes": {"ricc": 4}}
TWO_GROUPS = {"groups": {"a": {"core": 4, "mem": 1024},
                         "b": {"core": 8, "mem": 12288}},
              "nodes": {"a": 2, "b": 3}}
SCHEDULERS = [FirstInFirstOut, ShortestJobFirst, LongestJobFirst,
              EasyBackfilling]


def _capacity(system):
    return ResourceManager(system).capacity


def _usages(cap):
    """Every usage vector ``(cores, mem)`` of a node of capacity ``cap``
    and its float64 load, added left to right as the host adds it."""
    cores, mem = np.meshgrid(np.arange(cap[0] + 1), np.arange(cap[1] + 1),
                             indexing="ij")
    used = np.stack([cores.ravel(), mem.ravel()], axis=1)
    load = used[:, 0] / max(int(cap[0]), 1) + used[:, 1] / max(int(cap[1]), 1)
    return used, load


@pytest.mark.parametrize("system", [SETH, RICC, TWO_GROUPS],
                         ids=["seth", "ricc", "two-groups"])
def test_ranks_order_every_pair_as_float64(system):
    capacity = _capacity(system)
    key = bf_key(capacity)
    assert key.rank.dtype == key.base.dtype == key.stride.dtype == np.int32
    assert key.rank.shape[0] == bf_key_entries(capacity)
    ranks, loads = [], []
    for node in np.unique(capacity, axis=0, return_index=True)[1]:
        used, load = _usages(capacity[node])
        ranks.append(key.rank[key.base[node] + used @ key.stride[node]])
        loads.append(load)
    rank, load = np.concatenate(ranks), np.concatenate(loads)
    # every usage vector of the machine is covered once
    assert rank.shape == key.rank.shape
    order = np.argsort(load, kind="stable")
    # a strictly monotone map of the loads: along the sorted loads, the
    # rank steps up exactly where the load does, so every pair compares
    # alike and ties tie
    np.testing.assert_array_equal(np.sign(np.diff(load[order])),
                                  np.sign(np.diff(rank[order])))


def test_float64_splits_ties_that_the_ranks_keep():
    key = bf_key(_capacity(RICC))
    stride = key.stride[0]
    low, high = (1, 769), (0, 2305)           # equal as fractions
    assert 1 / 8 + 769 / 12288 < 0 / 8 + 2305 / 12288
    assert np.float32(1) / 8 + np.float32(769) / np.float32(12288) > \
        np.float32(0) / 8 + np.float32(2305) / np.float32(12288)
    assert key.rank[np.dot(low, stride)] < key.rank[np.dot(high, stride)]
    # RICC's 9 x 12,289 usages take 35,329 distinct float64 loads
    assert key.rank.shape[0] == 9 * 12289 and key.rank.max() == 35328


def test_key_is_built_once_per_machine():
    a, b = _capacity(RICC), _capacity(RICC).copy()
    assert bf_key(a) is bf_key(b)
    assert bf_key(_capacity(SETH)) is not bf_key(a)


def _records(rows):
    return [{"id": i + 1, "submit": t, "duration": d, "expected_duration": d,
             "requested_nodes": 1,
             "requested_resources": {"core": c, "mem": m}}
            for i, (t, d, c, m) in enumerate(rows)]


# on 4 RICC nodes: job 1 takes (1 core, 769 MB) of node 0; job 2 fills
# node 0's memory until t = 5, so job 3's (0 cores, 2,305 MB) goes to node
# 1.  At t = 10, job 4 has node 0 at (1, 769) and node 1 at (0, 2305), the
# same load as fractions; float64 reads node 1 busier, float32 node 0, an
# exact key ties them and takes node 0 by id.
SPLIT_TIE = _records([(0, 1000, 1, 769), (1, 4, 0, 11519),
                      (2, 1000, 0, 2305), (10, 100, 1, 100)])


def _traces(exp):
    out = {}
    for name, entry in exp.results.items():
        with open(entry["output"]) as fh:
            out[name] = {r["id"]: (r["start"], r["assigned"], r["state"])
                         for r in map(json.loads, fh)}
    return out


def _run(tmp_path, name, workload, system, schedulers, allocators,
         use_fleet):
    exp = Experiment(name, workload, system, output_dir=str(tmp_path),
                     use_fleet=use_fleet, job_factory=JobFactory())
    exp.gen_dispatchers(schedulers, allocators)
    results = exp.run_simulation(produce_plots=False)
    return exp, results


def test_split_tie_best_fit_rows_place_as_the_host(tmp_path):
    host, _ = _run(tmp_path, "host", SPLIT_TIE, RICC, SCHEDULERS, [BestFit],
                   use_fleet=False)
    fleet, results = _run(tmp_path, "fleet", SPLIT_TIE, RICC, SCHEDULERS,
                          [BestFit], use_fleet=True)
    assert {s["engine"] for r in results.values()
            for s in r["summaries"]} == {"fleet"}
    want, got = _traces(host), _traces(fleet)
    for name in want:
        assert want[name]["4"] == (10, [1], "COMPLETED"), name
        assert got[name] == want[name], name


def test_ricc_shaped_experiment_is_trace_equal_to_the_host(tmp_path):
    system = {"groups": {"ricc": {"core": 8, "mem": 12288}},
              "nodes": {"ricc": 64}}
    workload = SyntheticWorkload(
        300, seed=11, mean_interarrival_s=20.0, duration_median_s=1800.0,
        duration_sigma=1.2, node_weights={1: 0.5, 2: 0.25, 4: 0.15, 8: 0.1},
        resources={"core": (1, 8), "mem": (1, 12288)}, cores_per_node=8)
    host, _ = _run(tmp_path, "host", workload, system, SCHEDULERS,
                   [FirstFit, BestFit], use_fleet=False)
    fleet, results = _run(tmp_path, "fleet", workload, system, SCHEDULERS,
                          [FirstFit, BestFit], use_fleet=True)
    summaries = [s for r in results.values() for s in r["summaries"]]
    assert len(summaries) == 8
    assert {s["engine"] for s in summaries} == {"fleet"}
    # the jobs queue, so the schedulers differ
    assert len({json.dumps(sorted(t.items()))
                for t in _traces(host).values()}) == 8
    assert _traces(fleet) == _traces(host)


def test_machine_above_the_limit_runs_best_fit_on_the_host(tmp_path):
    # 65 x 262,145 usage vectors, above 2**24
    system = {"groups": {"big": {"core": 64, "mem": 262144}},
              "nodes": {"big": 4}}
    capacity = _capacity(system)
    assert bf_key_entries(capacity) > BF_KEY_MAX_ENTRIES
    assert bf_key(capacity) is None
    blank = launch_bf_key(capacity, best_fit=False)
    assert blank.rank.tolist() == [0] and not blank.stride.any()
    with pytest.raises(ValueError, match="Best-Fit key"):
        launch_bf_key(capacity, best_fit=True)
    workload = _records([(0, 50, 8, 4096), (5, 30, 64, 1), (6, 40, 2, 1)])
    host, _ = _run(tmp_path, "host", workload, system, [FirstInFirstOut],
                   [FirstFit, BestFit], use_fleet=False)
    exp, results = _run(tmp_path, "mixed", workload, system,
                        [FirstInFirstOut], [FirstFit, BestFit],
                        use_fleet=True)
    ff, bf = (results[n]["summaries"][0] for n in ("FIFO-FF", "FIFO-BF"))
    assert (ff["engine"], ff["fallback_reason"]) == ("fleet", None)
    assert (bf["engine"], bf["fallback_reason"]) == ("host",
                                                     "bf-key-too-large")
    assert _traces(exp) == _traces(host)


def _probe_model(alloc_id, pool, capacity, key, reqv, need, k_cap, elig):
    """The probe in numpy: the policy's stable order, the first ``need``
    fitting nodes in it, the slots padded with ``N``."""
    n = pool.shape[0]
    fit = (pool >= reqv).all(axis=1) & elig
    used = ((capacity - pool) * key.stride).sum(axis=1)
    rank = key.rank[key.base + used]
    order = (np.argsort(-rank, kind="stable") if alloc_id == ALLOC_BF
             else np.arange(n))
    chosen = [int(i) for i in order if fit[i]][:need]
    sel = np.zeros(n, bool)
    sel[chosen] = True
    nodes = np.full(k_cap, n)
    nodes[:len(chosen)] = chosen
    return fit.sum() >= need, sel, nodes


@pytest.mark.parametrize("alloc_id", [ALLOC_FF, ALLOC_BF], ids=["ff", "bf"])
@pytest.mark.parametrize("system", [SETH, RICC, TWO_GROUPS],
                         ids=["seth", "ricc", "two-groups"])
def test_select_nodes_takes_the_first_fitting_in_policy_order(alloc_id,
                                                               system):
    """The sort-and-slots probe against its numpy model, on random
    availabilities with many equal loads: need 0, partial fits (``ok``
    False, the fitting nodes still marked) and slots wider than ``need``."""
    nodes = {g: 12 for g in system["groups"]}
    capacity = _capacity(dict(system, nodes=nodes))
    key = bf_key(capacity)
    n, k_cap = capacity.shape[0], 4
    rng = np.random.default_rng(7)
    probe = jax.jit(lambda a, p, k, r, need, e: _select_nodes(
        a, p, jnp.asarray(capacity), k, r, need, k_cap, None, e))
    for _ in range(40):
        # few distinct usage levels, so loads tie often
        pool = capacity - capacity * rng.integers(0, 3, capacity.shape) // 2
        reqv = (rng.integers(0, 2, capacity.shape[1])
                * capacity.min(axis=0) // 2)
        need = int(rng.integers(0, k_cap + 1))
        elig = rng.random(n) > 0.2
        ok, sel, got = probe(alloc_id, pool.astype(np.int32), key,
                             reqv.astype(np.int32), need, elig)
        want = _probe_model(alloc_id, pool, capacity, key, reqv, need, k_cap,
                            elig)
        assert bool(ok) == want[0]
        np.testing.assert_array_equal(np.asarray(sel), want[1])
        np.testing.assert_array_equal(np.asarray(got), want[2])
