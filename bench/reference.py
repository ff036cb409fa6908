"""Plain reference of the simulated cluster, for the check of `correct`.

A straightforward event loop over one workload and one dispatcher of
the paper's Table 2 ({FIFO, SJF, LJF, EBF} x {FF, BF}), written from
the simulator's documented semantics and importing nothing of it:

- An event is a distinct time at which a job completes or is submitted.
  At each event, jobs whose end time has come release their nodes
  first, then every job submitted by then joins the queue in workload
  order.  A new job that no set of nodes of the machine could ever hold
  is rejected at once.  If jobs are queued the dispatcher plans once,
  and its starts take effect at that time.  The log then records the
  time, the queue length and the running count.
- When nothing can happen any more and jobs are still queued, they are
  rejected (not an event).
- FIFO takes the queue in arrival order; SJF by (estimate, queue time),
  LJF by (-estimate, queue time), ties in arrival order.  The estimate is
  max(expected duration, 1).  All three stop at the first job that does
  not fit.
- EBF starts jobs in arrival order while they fit.  For the first that
  does not (the head), it finds the shadow time: the earliest estimated
  release at which, with every release up to it applied, enough nodes
  fit the head.  A running job releases at max(start + estimate,
  now + 1); a job started in this plan at now + estimate.  It reserves
  the head's nodes there.  Every later queued job, in order, starts now
  if it fits now and either ends by its estimate no later than the
  shadow time, or fits within min(free now, free at the shadow time
  after the head's reservation).
- FF takes the lowest-numbered nodes that fit.  BF takes fitting nodes
  busiest first, where a node's load is the sum over resources of
  used / capacity on the availability being searched; ties go to the
  lower node number.

Two departures serve the control: ``simulate(..., time_quantum_s=Q)``
handles each event at the next multiple of Q seconds, the batching of
events that a faster event loop might take; ``load_dtype`` computes the
Best-Fit loads in another float type, as a faster node sort might.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
import numpy as np


def machine(sys_config: Dict):
    """``(resource types, capacity [N, R])``: node groups in name order,
    resource types in name order."""
    groups, counts = sys_config["groups"], sys_config["nodes"]
    rts = sorted({rt for g in groups.values() for rt in g})
    caps: List[List[int]] = []
    for g in sorted(groups):
        caps += [[int(groups[g].get(rt, 0)) for rt in rts]] * int(
            counts.get(g, 0))
    return rts, np.asarray(caps, dtype=np.int64)


def _fits(req, avail) -> np.ndarray:
    return (avail >= req[None, :]).all(axis=1)


def first_fit(req, n, avail, cap) -> Optional[np.ndarray]:
    idx = np.nonzero(_fits(req, avail))[0]
    return idx[:n] if idx.shape[0] >= n else None


def best_fit(req, n, avail, cap, dtype=np.float64) -> Optional[np.ndarray]:
    ok = _fits(req, avail)
    if int(ok.sum()) < n:
        return None
    used = (cap - avail).astype(dtype)
    load = (used / np.maximum(cap, 1).astype(dtype)).sum(axis=1, dtype=dtype)
    order = np.argsort(-load, kind="stable")
    return order[ok[order]][:n]


ALLOCATORS = {"FF": first_fit, "BF": best_fit}


class _Lane:
    """The state of one simulated machine and its jobs."""

    def __init__(self, jobs: Sequence[Dict], sys_config: Dict,
                 dispatcher: str, load_dtype: str,
                 time_quantum_s: int) -> None:
        self.sched, alloc = dispatcher.split("-")
        if alloc == "BF":
            dtype = np.dtype(load_dtype)
            self.find = lambda *a: best_fit(*a, dtype=dtype)
        else:
            self.find = ALLOCATORS[alloc]
        self.rts, self.cap = machine(sys_config)
        self.jobs = jobs
        self.req = np.asarray(
            [[int(j["requested_resources"].get(rt, 0)) for rt in self.rts]
             for j in jobs], dtype=np.int64).reshape(len(jobs), len(self.rts))
        self.key = [tuple(r) for r in self.req.tolist()]
        self.nodes = [int(j["requested_nodes"]) for j in jobs]
        self.submit = [int(j["submit"]) for j in jobs]
        self.dur = [int(j["duration"]) for j in jobs]
        self.est = [max(int(j["expected_duration"]), 1) for j in jobs]
        self.quantum = time_quantum_s
        self.avail = self.cap.copy()
        self.start: List[Optional[int]] = [None] * len(jobs)
        self.assigned: List[List[int]] = [[] for _ in jobs]
        self.state = ["LOADED"] * len(jobs)

    def fits_ever(self, j: int) -> bool:
        return int(_fits(self.req[j], self.cap).sum()) >= self.nodes[j]

    # ------------------------------------------------------------------
    def plan(self, now: int, queue: List[int], running: List[int]):
        if self.sched == "EBF":
            return self._ebf(now, queue, running)
        if self.sched == "FIFO":
            order = list(queue)
        else:
            sign = 1 if self.sched == "SJF" else -1
            order = sorted(queue, key=lambda j: (sign * self.est[j],
                                                 self.submit[j]))
        avail = self.avail.copy()
        starts = []
        for j in order:
            got = self.find(self.req[j], self.nodes[j], avail, self.cap)
            if got is None:
                break
            avail[got] -= self.req[j]
            starts.append((j, got))
        return starts

    def _ebf(self, now: int, queue: List[int], running: List[int]):
        req, cap, find = self.req, self.cap, self.find
        avail = self.avail.copy()
        starts = []
        i = 0
        while i < len(queue):
            j = queue[i]
            got = find(req[j], self.nodes[j], avail, cap)
            if got is None:
                break
            avail[got] -= req[j]
            starts.append((j, got))
            i += 1
        if i == len(queue):
            return starts
        head = queue[i]
        releases = [(max(self.start[r] + self.est[r], now + 1),
                     self.assigned[r], r) for r in running]
        releases += [(now + self.est[j], got, j) for j, got in starts]
        releases.sort(key=lambda ev: ev[0])
        cur = avail.copy()
        fit = _fits(req[head], cur)
        n_fit = int(fit.sum())
        shadow_t = None
        k = 0
        while k < len(releases):
            t = releases[k][0]
            while k < len(releases) and releases[k][0] == t:
                _, got, r = releases[k]
                cur[got] += req[r]
                now_fit = _fits(req[head], cur[got])
                n_fit += int(now_fit.sum()) - int(fit[got].sum())
                fit[got] = now_fit
                k += 1
            if n_fit >= self.nodes[head]:
                shadow_t = t
                break
        if shadow_t is None:
            return starts
        extra = cur.copy()
        extra[find(req[head], self.nodes[head], cur, cap)] -= req[head]
        rest = queue[i + 1:]
        # fitting-node counts per request vector, on the availability now
        # and on min(now, extra at the shadow time); both only change
        # when a job is admitted, so most misfits cost one lookup
        n_now: Dict[tuple, int] = {}
        n_both: Dict[tuple, int] = {}
        for j in rest:
            key = self.key[j]
            short = now + self.est[j] <= shadow_t
            counts = n_now if short else n_both
            if key not in counts:
                pool = avail if short else np.minimum(avail, extra)
                counts[key] = int(_fits(req[j], pool).sum())
            if counts[key] < self.nodes[j]:
                continue
            pool = avail if short else np.minimum(avail, extra)
            got = find(req[j], self.nodes[j], pool, cap)
            if not short:
                extra[got] -= req[j]
            avail[got] -= req[j]
            starts.append((j, got))
            n_now.clear()
            n_both.clear()
        return starts

    # ------------------------------------------------------------------
    def run(self):
        order = sorted(range(len(self.jobs)), key=lambda j: self.submit[j])
        queue: List[int] = []
        running: List[int] = []
        log = []
        nxt = 0
        now = 0
        counts = {"submitted": 0, "completed": 0, "rejected": 0}
        while nxt < len(order) or running or queue:
            times = [self.start[r] + self.dur[r] for r in running]
            if nxt < len(order):
                times.append(self.submit[order[nxt]])
            if not times:
                for j in queue:
                    self.state[j] = "REJECTED"
                counts["rejected"] += len(queue)
                queue = []
                break
            now = -(-min(times) // self.quantum) * self.quantum
            done = [r for r in running if self.start[r] + self.dur[r] <= now]
            for r in done:
                self.avail[self.assigned[r]] += self.req[r]
                self.state[r] = "COMPLETED"
            if done:
                running = [r for r in running if self.state[r] == "RUNNING"]
            counts["completed"] += len(done)
            while nxt < len(order) and self.submit[order[nxt]] <= now:
                j = order[nxt]
                nxt += 1
                counts["submitted"] += 1
                if self.fits_ever(j):
                    self.state[j] = "QUEUED"
                    queue.append(j)
                else:
                    self.state[j] = "REJECTED"
                    counts["rejected"] += 1
            if queue:
                started = set()
                for j, got in self.plan(now, queue, running):
                    self.avail[got] -= self.req[j]
                    self.start[j] = now
                    self.assigned[j] = [int(x) for x in got]
                    self.state[j] = "RUNNING"
                    running.append(j)
                    started.add(j)
                queue = [j for j in queue if j not in started]
            log.append((now, len(queue), len(running)))
        summary = dict(counts, events=len(log), sim_end_time=now)
        return self.records(), log, summary

    def records(self) -> Dict[str, Dict]:
        out = {}
        for j, job in enumerate(self.jobs):
            done = self.state[j] == "COMPLETED"
            t0 = self.start[j] if done else None
            run = max(self.dur[j], 1)
            out[str(job["id"])] = {
                "id": str(job["id"]),
                "user": int(job["user"]),
                "submit": self.submit[j],
                "start": t0,
                "end": t0 + self.dur[j] if done else None,
                "duration": self.dur[j],
                "expected_duration": int(job["expected_duration"]),
                "nodes": self.nodes[j],
                "resources": {rt: int(v) for rt, v in zip(self.rts,
                                                          self.req[j]) if v},
                "assigned": self.assigned[j] if done else [],
                "waiting": t0 - self.submit[j] if done else None,
                "slowdown": ((t0 - self.submit[j] + run) / run
                             if done else None),
                "state": self.state[j],
            }
        return out


def simulate(jobs: Sequence[Dict], sys_config: Dict, dispatcher: str,
             load_dtype: str = "float64", time_quantum_s: int = 1):
    """Run one lane to its end: ``(records by job id, event log of
    (time, queued, running), summary counts)``."""
    return _Lane(list(jobs), sys_config, dispatcher, load_dtype,
                 time_quantum_s).run()
