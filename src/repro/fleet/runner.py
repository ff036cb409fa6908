"""FleetRunner — whole dispatcher×seed grids in one device launch.

Batching model: each grid point (scheduler code × workload/seed) becomes
one :class:`~repro.fleet.state.SimState`; all states are padded to a
common shape (rows, assignment width), tree-stacked along a leading sim
axis, and advanced by ONE ``jit(vmap(advance))`` call.  With more than
one local device (or an explicit mesh) the sim axis is sharded with
``shard_map`` over :func:`repro.launch.mesh.fleet_mesh` — sims are
embarrassingly parallel, so the program contains no collectives.

Mixed grids are first split by dispatch *cost class* (EBF vs plain
blocking schedulers) into separate launches: vmapped lanes run in
lockstep, so one EBF lane's shadow-walk/backfill loop trips would
otherwise be paid by every cheap lane in the batch (the convoy effect —
``run(group_by_cost=False)`` keeps the single mixed launch, which stays
decision-identical and test-pinned).

The result object re-materializes the host contract: per-sim summaries
with the host ``Simulator.summary`` keys, per-job output records
(``Job.to_record`` schema), golden-trace dicts, and the two JSONL
streams (``{name}-output.jsonl`` / ``{name}-bench.jsonl``) that the
existing metrics/plots pipeline consumes — filled from each lane's columns
by line templates, byte for byte what ``json.dumps`` of the records and
event dicts would write; device wall time is amortized
uniformly over events for the per-event ``dispatch_s`` field, since the
compiled loop has no per-event host clock.

Timing lives in the host spans of :class:`~repro.telemetry.SpanRecorder`
(``perf_counter`` clock, each also a profiler annotation): ``fleet.build``
(``.load``, ``.export``, ``.bf_key``) per build — in an ``Experiment`` one
per repeat, shared by the repeat's rows, its ``lanes`` attribute the
lanes it serves; ``fleet.launch`` per cost
class with ``fleet.pad``, ``fleet.compile`` (cache misses only),
``fleet.execute``, ``fleet.fetch`` and ``fleet.unstack``;
``results.write`` per lane with ``results.records`` (the columns'
decode), ``results.jobs_file``, ``results.summary`` and
``results.events_file`` (each file's formatting and write, its ``lines``
attribute the lines written).
``FleetResult.launches`` carries each launch's ``wall_time_s`` (execute +
fetch), ``compile_time_s`` and its phase seconds.  On the device, the
engine's phases are named scopes (``fleet/engine.py``).
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from json.encoder import encode_basestring_ascii
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry.spans import SpanRecorder
from ..utils import rss_mb
from .engine import (ALLOC_BF, ALLOC_NAMES, SCHED_EBF, SCHED_NAMES,
                     advance_fn)
from .state import (COMPLETED, REJECTED, SimMeta, SimState, UNSET_I,
                    bf_key, launch_bf_key)


_STATE_NAMES = {COMPLETED: "COMPLETED", REJECTED: "REJECTED"}


def _json_str(s) -> str:
    """``json.dumps(s)``; strings skip its call overhead."""
    return encode_basestring_ascii(s) if isinstance(s, str) \
        else json.dumps(s)


def _job_lines(cols: Dict[str, list], rts: Sequence[str]) -> List[str]:
    """The lines of ``{name}-output.jsonl`` from
    :meth:`FleetResult._job_columns`: ``json.dumps`` of each
    :meth:`FleetResult.records` entry, keys in its order, filled into one
    line template.  A column holds Python ints (whose ``format`` is their
    JSON) or JSON text; the rows that did not start, and those that
    request zero of a resource, are patched by position."""
    req = cols["req"]
    keys = [_json_str(rt) + ": " for rt in rts]
    every = "{" + ", ".join(key.replace("%", "%%") + "%d"
                            for key in keys) + "}"
    resources = list(map(every.__mod__, zip(*req)))
    for r in cols["partial"]:
        resources[r] = "{" + ", ".join(
            key + str(col[r]) for key, col in zip(keys, req) if col[r]) + "}"
    start, end, waiting = (cols[k].copy()
                           for k in ("start", "end", "waiting"))
    reprs = list(map(float.__repr__, cols["slowdowns"]))
    slowdown = list(map(reprs.__getitem__, cols["slowdown_of"]))
    assigned = list(map(str, map(cols["assigned"].__getitem__, map(
        slice, cols["assigned_from"], cols["assigned_to"]))))
    for r in cols["unstarted"]:
        start[r] = end[r] = waiting[r] = slowdown[r] = "null"
        assigned[r] = "[]"
    state = {st: '"%s"' % _STATE_NAMES.get(st, f"STATE{st}")
             for st in set(cols["state"])}
    return [f'{{"id": {jid}, "user": {user}, "submit": {submit}, '
            f'"start": {t0}, "end": {t1}, "duration": {dur}, '
            f'"expected_duration": {exp}, "nodes": {n}, '
            f'"resources": {res}, "assigned": {nodes}, "waiting": {wait}, '
            f'"slowdown": {slow}, "state": {st}}}\n'
            for jid, user, submit, t0, t1, dur, exp, n, res, nodes, wait,
            slow, st in zip(
                map(_json_str, cols["id"]), cols["user"], cols["submit"],
                start, end, cols["duration"], cols["expected"],
                cols["nodes"], resources, assigned, waiting, slowdown,
                map(state.__getitem__, cols["state"]))]


def _write_text(path: str, lines: List[str]) -> None:
    """Write ASCII ``lines`` with one call."""
    with open(path, "wb") as fh:
        fh.write("".join(lines).encode())


@dataclass
class FleetSim:
    """One grid point: a named, ready-to-run simulation."""

    name: str
    state: SimState
    meta: SimMeta
    sched_id: int
    alloc_id: int = 0
    seed: Optional[int] = None


@dataclass
class FleetResult:
    """Unstacked per-sim final states + host-contract accessors."""

    sims: List[FleetSim]
    finals: List[SimState]
    wall_time_s: float            # total batched device wall time
    compile_time_s: float         # 0.0 on a compile-cache hit
    n_devices: int = 1
    cache_hit: bool = False       # every launch reused its executable
    # per-launch telemetry when run() split the grid by dispatch cost
    # class: [{"cost_class", "n_sims", "wall_time_s", ...}, ...]
    launches: List[Dict] = field(default_factory=list)
    # the grid's host spans; write_outputs and summary add theirs
    spans: SpanRecorder = field(default_factory=SpanRecorder)

    def __len__(self) -> int:
        return len(self.sims)

    # ------------------------------------------------------------------
    def summary(self, i: int) -> Dict[str, object]:
        """Host ``Simulator.summary``-schema summary for sim ``i``;
        wall/cpu/dispatch seconds are the batched run amortized per sim."""
        with self.spans.span("results.summary", lane=self.sims[i].name):
            f, sim = self.finals[i], self.sims[i]
            n_events = int(f.n_events)
            n_rounds = int(f.n_rounds)
            per_sim = self.wall_time_s / max(len(self.sims), 1)
            rss = rss_mb()
            out = {
                "dispatcher": f"{SCHED_NAMES[sim.sched_id]}-"
                              f"{ALLOC_NAMES[sim.alloc_id]}",
                "events": n_events,
                "submitted": int(f.n_submitted),
                "completed": int(f.n_completed),
                "rejected": int(f.n_rejected),
                "cpu_time_s": per_sim,
                "wall_time_s": per_sim,
                "dispatch_time_s": per_sim,
                # the host Simulator's schema; the fleet launches no kernel
                "kernel_launches": 0,
                "kernel_launches_per_event": 0.0,
                "sim_end_time": int(f.now),
                "mem_avg_mb": rss,
                "mem_max_mb": rss,
                "engine": "fleet",
            }
            if int(f.n_fail) > 0:
                out["failures"] = {
                    "requeued_jobs": int(f.n_requeued),
                    "lost_work_s": int(f.lost_work_s),
                    "node_downtime_s": int(f.node_downtime_s),
                }
            tele = self.telemetry(i)
            if tele is not None:
                out["telemetry"] = {
                    "stride": tele.stride,
                    "n_samples": tele.n_samples,
                    "phase_counters": dict(tele.phase_counters),
                }
            if sim.seed is not None:
                out["seed"] = sim.seed
            return out

    # ------------------------------------------------------------------
    def telemetry(self, i: int):
        """Decode sim ``i``'s device-resident telemetry buffers into the
        engine-neutral :class:`~repro.telemetry.TelemetryTrace`, or None
        when the lane ran without telemetry (S=0 or stride 0).

        ``fail_drain_trips`` is the failure-cursor delta between the
        initial and final states (the cursor advances exactly once per
        drain-loop trip, matching ``EventManager.n_fail_drain_trips``)."""
        f, sim = self.finals[i], self.sims[i]
        cap_s = int(f.tele_buf.shape[0])
        stride = int(f.tele_stride)
        if cap_s == 0 or stride <= 0:
            return None
        from ..telemetry import TelemetryTrace

        n = int(f.tele_n)
        samples = np.asarray(f.tele_buf)[:n].astype(np.int64)
        n_events = int(f.n_events)
        expected = -(-n_events // stride)
        if n_events and (n_events - 1) % stride:
            expected += 1             # the conditional end-of-sim sample
        counters = {
            "dispatch_trips": int(f.ct_disp_trips),
            "shadow_trips": int(f.ct_shadow_trips),
            "backfill_admits": int(f.ct_backfill),
            "misfit_skips": int(f.ct_misfit),
            "fail_drain_trips": int(f.fptr) - int(sim.state.fptr),
        }
        cap = np.asarray(f.capacity).sum(axis=0)
        rts = sim.meta.resource_types
        return TelemetryTrace(
            engine="fleet", name=sim.name, stride=stride,
            resource_types=tuple(rts), samples=samples,
            phase_counters=counters,
            capacity={rt: int(cap[c]) for c, rt in enumerate(rts)},
            truncated=expected > cap_s)

    # ------------------------------------------------------------------
    def records(self, i: int) -> List[Dict[str, object]]:
        """Per-job output records for sim ``i`` (``Job.to_record``
        schema), in row order."""
        f, meta = self.finals[i], self.sims[i].meta
        state = np.asarray(f.state)
        start = np.asarray(f.start)
        end = np.asarray(f.end)
        duration = np.asarray(f.duration)
        submit = np.asarray(f.submit)
        n_need = np.asarray(f.n_need)
        req = np.asarray(f.req)
        assigned = np.asarray(f.assigned)
        rts = meta.resource_types
        out = []
        for row, jid in enumerate(meta.ids):
            if jid is None:
                continue
            st = int(state[row])
            started = st == COMPLETED and start[row] != UNSET_I
            t0 = int(start[row]) if started else None
            waiting = (t0 - int(submit[row])) if started else None
            run = max(int(duration[row]), 1)
            out.append({
                "id": jid,
                "user": int(meta.user[row]),
                "submit": int(submit[row]),
                "start": t0,
                "end": int(end[row]) if started else None,
                "duration": int(duration[row]),
                "expected_duration": int(meta.expected[row]),
                "nodes": int(n_need[row]),
                "resources": {rt: int(req[row, c])
                              for c, rt in enumerate(rts) if req[row, c]},
                "assigned": ([int(x) for x in assigned[row, :n_need[row]]]
                             if started else []),
                "waiting": waiting,
                "slowdown": ((waiting + run) / run) if started else None,
                "state": ("COMPLETED" if st == COMPLETED else
                          "REJECTED" if st == REJECTED else f"STATE{st}"),
            })
        return out

    def trace(self, i: int) -> Dict[str, List]:
        """Golden-fixture format: ``{id: [start, [assigned], state]}``."""
        return {r["id"] if isinstance(r["id"], str) else str(r["id"]):
                [r["start"], r["assigned"], r["state"]]
                for r in self.records(i)}

    # ------------------------------------------------------------------
    def write_outputs(self, output_dir: str, i: int) -> Tuple[str, str]:
        """Write ``{name}-output.jsonl`` and ``{name}-bench.jsonl`` for
        sim ``i`` — byte-identical to ``json.dumps`` of :meth:`records`
        and of the host simulator's event dicts, so metrics/plots consume
        them unchanged.  Each file is one string filled from line
        templates and one ``write``; the ``lines`` attribute of
        ``results.jobs_file`` / ``results.events_file`` counts its lines."""
        name = self.sims[i].name
        out_path = os.path.join(output_dir, f"{name}-output.jsonl")
        bench_path = os.path.join(output_dir, f"{name}-bench.jsonl")
        spans = self.spans
        n_events = int(self.finals[i].n_events)
        with spans.span("results.write", lane=name, events=n_events):
            os.makedirs(output_dir, exist_ok=True)
            with spans.span("results.records"):
                cols = self._job_columns(i)
            with spans.span("results.jobs_file", lines=len(cols["id"])):
                _write_text(out_path, _job_lines(
                    cols, self.sims[i].meta.resource_types))
            summ = self.summary(i)
            with spans.span("results.events_file", lines=n_events + 1):
                self._write_events(bench_path, i, summ)
            self.write_telemetry(output_dir, i)
        return out_path, bench_path

    def _job_columns(self, i: int) -> Dict[str, list]:
        """Sim ``i``'s live jobs as flat host columns in row order — the
        fields of :meth:`records` as Python ints and floats, before JSON;
        ``req`` one column per resource type — with the positions of the
        jobs that did not start (``unstarted``) and of those that request
        zero of some resource (``partial``)."""
        f, meta = self.finals[i], self.sims[i].meta
        rows = np.flatnonzero([jid is not None for jid in meta.ids])
        state = np.asarray(f.state)[rows]
        start = np.asarray(f.start)[rows].astype(np.int64)
        submit = np.asarray(f.submit)[rows].astype(np.int64)
        duration = np.asarray(f.duration)[rows].astype(np.int64)
        n_need = np.asarray(f.n_need)[rows]
        req = np.asarray(f.req)[rows]
        assigned = np.asarray(f.assigned)[rows]
        width = assigned.shape[1]
        started = (state == COMPLETED) & (start != UNSET_I)
        waiting = start - submit
        run = np.maximum(duration, 1)
        slowdowns, slowdown_of = np.unique((waiting + run) / run,
                                           return_inverse=True)
        # flat columns only: a list per row would be a container per job
        # for the garbage collector to track
        return {
            "id": [meta.ids[r] for r in rows.tolist()],
            "user": meta.user[rows].tolist(),
            "submit": submit.tolist(),
            "start": start.tolist(),
            "end": np.asarray(f.end)[rows].tolist(),
            "duration": duration.tolist(),
            "expected": meta.expected[rows].tolist(),
            "nodes": n_need.tolist(),
            "req": [col.tolist() for col in req.T],
            # row r's nodes are assigned[assigned_from[r]:assigned_to[r]]
            "assigned": assigned.ravel().tolist(),
            "assigned_from": (np.arange(len(rows)) * width).tolist(),
            "assigned_to": (np.arange(len(rows)) * width
                            + np.minimum(n_need, width)).tolist(),
            "waiting": waiting.tolist(),
            # float64 division of exact ints is Python's int / int; each
            # distinct slowdown is printed once
            "slowdowns": slowdowns.tolist(),
            "slowdown_of": slowdown_of.reshape(-1).tolist(),
            "state": state.tolist(),
            "unstarted": np.flatnonzero(~started).tolist(),
            "partial": np.flatnonzero((req == 0).any(axis=1)).tolist(),
        }

    def _write_events(self, bench_path: str, i: int, summ: Dict) -> None:
        """Sim ``i``'s event log and summary line, as the host
        simulator's ``{name}-bench.jsonl``.  Only ``t``, ``queue`` and
        ``running`` vary per line; the rest is one tail, ``json.dumps``
        text formatted once."""
        f = self.finals[i]
        n_events = int(f.n_events)
        dispatch_amort = summ["dispatch_time_s"] / max(n_events, 1)
        rss = rss_mb()
        tail = json.dumps({"dispatch_s": dispatch_amort,
                           "kernel_launches": 0, "rss_mb": rss})[1:]
        lines = [f'{{"t": {t}, "queue": {q}, "running": {r}, {tail}\n'
                 for t, q, r in zip(
                     np.asarray(f.log_t)[:n_events].tolist(),
                     np.asarray(f.log_queue)[:n_events].tolist(),
                     np.asarray(f.log_running)[:n_events].tolist())]
        lines.append(json.dumps({"summary": summ}) + "\n")
        _write_text(bench_path, lines)

    def write_telemetry(self, output_dir: str, i: int) -> Optional[str]:
        """Write sim ``i``'s ``{name}-telemetry.jsonl`` (the same
        structured-trace stream the host simulator emits); no-op (None)
        for telemetry-free lanes."""
        tele = self.telemetry(i)
        if tele is None:
            return None
        os.makedirs(output_dir, exist_ok=True)
        return tele.write_jsonl(os.path.join(
            output_dir, f"{self.sims[i].name}-telemetry.jsonl"))


# padding buckets: row capacity rounds up to a multiple of _BUCKET_ROWS,
# assignment width to the next power of two — so grids of similar size
# share one compiled executable instead of recompiling per exact shape
_BUCKET_ROWS = 64


def _bucket_rows(m: int) -> int:
    return max(_BUCKET_ROWS, -(-m // _BUCKET_ROWS) * _BUCKET_ROWS)


def _bucket_width(k: int) -> int:
    w = 1
    while w < k:
        w *= 2
    return w


class FleetRunner:
    """Compiles and launches a batch of :class:`FleetSim` grid points.

    Parameters
    ----------
    mesh:
        A 1-D ``Mesh`` with axis ``"sims"`` (see
        :func:`repro.launch.mesh.fleet_mesh`) to shard the sim axis with
        ``shard_map``; default shards automatically when more than one
        local device is present.
    spans:
        The :class:`~repro.telemetry.SpanRecorder` the launches and the
        result's writing record into (``Experiment`` passes its grid's);
        a fresh one by default.

    Compile caching: sims are padded to *bucketed* ``(M, K)`` shapes
    (rows to a multiple of 64, width to a power of two, failure events
    to a multiple of 16, telemetry sample capacity to a multiple of 64 —
    0 stays 0 in both cases so the specialized engines survive; padding
    is inert, pinned by tests), and the AOT-compiled executable is cached
    process-wide per ``(batch, M, K, F, S, N, R, T, devices, backend)``
    (``T`` the Best-Fit key's length, fixed by the machine), so repeated
    grids of the same rounded-up shape skip the jit entirely
    (``FleetResult.cache_hit``; compile time was ~2.3x the run time of a
    36-sim grid before caching).
    """

    _compile_cache: Dict[Tuple, object] = {}

    def __init__(self, mesh=None,
                 spans: Optional[SpanRecorder] = None) -> None:
        import jax

        self._jax = jax
        self.mesh = mesh
        # the launches' spans; each FleetResult records into it too
        self.spans = SpanRecorder() if spans is None else spans

    # ------------------------------------------------------------------
    @staticmethod
    def build(name: str, workload: Iterable, sys_config: Dict,
              sched_id: int, alloc_id: int = 0, job_factory=None,
              seed: Optional[int] = None, failures=None,
              quarantine_s: int = 0, ckpt_every_s: int = 0,
              telemetry_stride: int = 0,
              telemetry_samples: Optional[int] = None,
              spans: Optional[SpanRecorder] = None,
              lanes: int = 1) -> FleetSim:
        """Materialize one grid point from a workload.  ``failures`` /
        ``quarantine_s`` / ``ckpt_every_s`` install a device-resident
        FAIL/REPAIR schedule (``Simulator(failures=...)`` semantics).
        ``telemetry_stride`` > 0 allocates device-resident telemetry
        buffers (DESIGN.md §10) decoded by ``FleetResult.telemetry``.
        The ``fleet.build`` span and its three children go to ``spans``;
        ``fleet.build.bf_key`` builds the machine's Best-Fit key or finds
        it in the cache (None above the limit, DESIGN.md §8).  ``lanes``
        is the number of lanes the caller derives from this state (its
        span's attribute; ``Experiment`` shares one build among a
        repeat's rows)."""
        spans = SpanRecorder() if spans is None else spans
        with spans.span("fleet.build", lane=name, lanes=lanes):
            with spans.span("fleet.build.load"):
                em = SimState.load_event_manager(
                    workload, sys_config, job_factory=job_factory,
                    failures=failures, quarantine_s=quarantine_s,
                    ckpt_every_s=ckpt_every_s)
            with spans.span("fleet.build.export"):
                state, meta = SimState.from_event_manager(
                    em, sched_id=sched_id, alloc_id=alloc_id,
                    telemetry_stride=telemetry_stride,
                    telemetry_samples=telemetry_samples)
            with spans.span("fleet.build.bf_key"):
                bf_key(state.capacity)      # built once, then cached
        return FleetSim(name=name, state=state, meta=meta,
                        sched_id=sched_id, alloc_id=alloc_id, seed=seed)

    # ------------------------------------------------------------------
    def run(self, sims: Sequence[FleetSim],
            group_by_cost: bool = True) -> FleetResult:
        """Advance every sim to completion in batched device launches.

        ``group_by_cost`` (default on) splits the batch into dispatch
        *cost classes* — EBF lanes vs plain blocking lanes — and launches
        each class separately.  Under vmap all lanes run in lockstep, so
        every inner ``while_loop`` runs max-over-lanes trips: one EBF
        lane's shadow walk + backfill scan taxes every FIFO lane sharing
        its launch (the convoy effect).  Grouping removes that tax
        without changing a single decision — each lane's trajectory is
        independent of its batch, pinned by tests.  Homogeneous batches
        always take the single-launch path; ``wall_time_s`` /
        ``compile_time_s`` sum over launches and ``cache_hit`` reports
        whether *every* launch reused its executable.  Each launch runs
        in a ``fleet.launch`` span; its entry in ``launches`` carries the
        seconds of the span's children (``pad_s``, ``execute_s``,
        ``fetch_s``, ``unstack_s``).
        """
        if not sims:
            raise ValueError("empty fleet")
        capacity = sims[0].state.capacity
        if any(not np.array_equal(s.state.capacity, capacity)
               for s in sims[1:]):
            # one Best-Fit key serves the launch, so one machine
            raise ValueError("sims target different systems: node "
                             "capacities differ")
        heavy = [i for i, s in enumerate(sims) if s.sched_id == SCHED_EBF]
        light = [i for i, s in enumerate(sims) if s.sched_id != SCHED_EBF]
        groups = ([light, heavy] if group_by_cost and light and heavy
                  else [list(range(len(sims)))])
        finals: List[Optional[SimState]] = [None] * len(sims)
        wall = compile_time = 0.0
        cache_hit = True
        n_dev = 1
        launches: List[Dict] = []
        spans = self.spans
        for idx in groups:
            classes = {"ebf" if sims[i].sched_id == SCHED_EBF else "blocking"
                       for i in idx}
            cost_class = classes.pop() if len(classes) == 1 else "mixed"
            at = len(spans.spans)
            with spans.span("fleet.launch", cost_class=cost_class):
                part, w, c, hit, nd = self._launch([sims[i] for i in idx])
            phase_s = {sp.name: sp.seconds for sp in spans.children(at)}
            for j, i in enumerate(idx):
                finals[i] = part[j]
            wall += w
            compile_time += c
            cache_hit &= hit
            n_dev = max(n_dev, nd)
            launches.append({
                "cost_class": cost_class,
                "n_sims": len(idx),
                "events": sum(int(part[j].n_events) for j in range(len(idx))),
                "wall_time_s": round(w, 6),
                "compile_time_s": round(c, 6),
                "cache_hit": hit,
                **{f"{ph}_s": round(phase_s.get(f"fleet.{ph}", 0.0), 6)
                   for ph in ("pad", "execute", "fetch", "unstack")},
            })
        return FleetResult(sims=list(sims), finals=finals,
                           wall_time_s=wall, compile_time_s=compile_time,
                           n_devices=n_dev,
                           cache_hit=cache_hit, launches=launches,
                           spans=spans)

    # ------------------------------------------------------------------
    def _launch(self, sims: Sequence[FleetSim]):
        """One padded/stacked/compiled launch of a homogeneous-cost batch;
        returns ``(finals, wall_s, compile_s, cache_hit, n_devices)``.
        ``wall_s`` runs from the call to the end of the copy back to the
        host (``fleet.execute`` + ``fleet.fetch``)."""
        jax = self._jax
        spans = self.spans
        with spans.span("fleet.pad"):
            fn, args, key, n_sims, n_dev = self._pad(sims)
        compiled = self._compile_cache.get(key)
        cache_hit = compiled is not None
        compile_time = 0.0
        if compiled is None:
            with spans.span("fleet.compile") as sp:
                compiled = jax.jit(fn).lower(*args).compile()
            compile_time = sp.seconds
            self._compile_cache[key] = compiled
        t0 = time.perf_counter()
        with spans.span("fleet.execute"):
            out = jax.block_until_ready(compiled(*args))
        with spans.span("fleet.fetch"):
            out = jax.tree.map(np.asarray, out)
        wall = time.perf_counter() - t0

        with spans.span("fleet.unstack"):
            finals = [jax.tree.map(lambda x: x[i], out)
                      for i in range(n_sims)]
        return finals, wall, compile_time, cache_hit, n_dev

    def _pad(self, sims: Sequence[FleetSim]):
        """The batch bucketed, padded and stacked, with the function to
        compile for it; returns ``(fn, args, cache_key, n_sims,
        n_devices)``, ``args`` being the stacked states and the machine's
        Best-Fit key, one copy for every lane."""
        jax = self._jax
        m = _bucket_rows(max(s.state.n_rows for s in sims))
        k = _bucket_width(max(s.state.assigned.shape[1] for s in sims))
        # failure schedules pad like jobs: bucket to a multiple of 16 so
        # nearby schedule lengths share an executable; fev == 0 (no sim
        # in the batch has a schedule) compiles the failure-free engine
        fev = max(s.state.fail_ev.shape[0] for s in sims)
        fev = -(-fev // 16) * 16 if fev else 0
        # telemetry sample capacity buckets like rows (multiple of 64) so
        # stride sweeps share an executable; ts == 0 (no sim in the batch
        # carries buffers) compiles the exact telemetry-free engine
        ts = max(s.state.tele_buf.shape[0] for s in sims)
        ts = -(-ts // _BUCKET_ROWS) * _BUCKET_ROWS if ts else 0
        padded = [s.state.pad_to(m, k, fev, ts) for s in sims]

        mesh = self.mesh
        n_dev = 1
        mesh_key = None
        if mesh is None and len(jax.devices()) > 1:
            from ..launch.mesh import fleet_mesh
            mesh = fleet_mesh()
        bf = launch_bf_key(sims[0].state.capacity,
                           any(s.alloc_id == ALLOC_BF for s in sims))
        fn = jax.vmap(advance_fn(), in_axes=(0, None))
        n_sims = len(padded)
        pad_sims = 0
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            n_dev = int(np.prod([d for d in mesh.devices.shape]))
            mesh_key = tuple(d.id for d in mesh.devices.flat)
            pad_sims = (-n_sims) % n_dev
            # check_vma=False: every output is fully sharded on "sims",
            # so there is no replication to track through the while_loop
            fn = jax.shard_map(fn, mesh=mesh, in_specs=(P("sims"), P()),
                               out_specs=P("sims"), check_vma=False)
        # round the batch up to the device count with copies of the last
        # sim (dropped after the run)
        batch = list(padded) + [padded[-1]] * pad_sims
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *batch)

        n, r = padded[0].avail.shape
        key = (len(batch), m, k, fev, ts, n, r, bf.rank.shape[0], mesh_key,
               jax.default_backend())
        return fn, (stacked, bf), key, n_sims, n_dev
