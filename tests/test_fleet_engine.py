"""Compiled fleet engine equality against the host core.

The decisive contract: for every compilable policy (FIFO/SJF/LJF/EBF ×
FirstFit/BestFit) the batched device engine must reproduce the host
engine's dispatch trace BIT-IDENTICALLY — same start times, same node
lists, same reject set — on the same golden scenario pinned by
``tests/test_trace_golden.py``.  On top of that: the Pallas scoring
kernel must not change a single decision (its prefilter is strictly
implied by the exact availability recheck), padding must be inert, the
padded-shape compile cache must reuse executables without changing
results, a mid-simulation host snapshot must continue identically on
device, mixed (sched, alloc) lanes in one vmapped launch must agree
with solo launches, and the shard_map path must agree with the
single-device path.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                    FirstInFirstOut, LongestJobFirst,
                                    ShortestJobFirst)
from repro.core.job import JobFactory
from repro.core.simulator import Simulator
from repro.fleet import (ALLOC_BF, ALLOC_FF, SCHED_EBF, SCHED_FIFO,
                         SCHED_LJF, SCHED_SJF, FleetResult, FleetRunner,
                         FleetSim, SimState, advance, alloc_code, compiles,
                         dispatch_code, sched_code)
from repro.workloads.synthetic import SyntheticWorkload

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "golden_traces.json")

# the golden scenario of test_trace_golden.py, verbatim
SYS = {"groups": {"a": {"core": 4, "mem": 1024}, "b": {"core": 8, "mem": 2048}},
       "nodes": {"a": 6, "b": 4}}

# the full compilable set: 4 schedulers x 2 allocators
TAGS = {"FIFO-FF": (SCHED_FIFO, ALLOC_FF), "FIFO-BF": (SCHED_FIFO, ALLOC_BF),
        "SJF-FF": (SCHED_SJF, ALLOC_FF), "SJF-BF": (SCHED_SJF, ALLOC_BF),
        "LJF-FF": (SCHED_LJF, ALLOC_FF), "LJF-BF": (SCHED_LJF, ALLOC_BF),
        "EBF-FF": (SCHED_EBF, ALLOC_FF), "EBF-BF": (SCHED_EBF, ALLOC_BF)}


def _workload(n=400, seed=29):
    return SyntheticWorkload(
        n, seed=seed, mean_interarrival_s=25.0, duration_median_s=900.0,
        duration_sigma=1.1, node_weights={1: 0.5, 2: 0.3, 4: 0.2},
        resources={"core": (1, 4), "mem": (64, 1024)})


def _host_trace(scheduler, tmp_path, n=150, seed=7):
    sim = Simulator(_workload(n, seed), SYS, scheduler,
                    job_factory=JobFactory(), output_dir=str(tmp_path),
                    name="host")
    out = sim.start_simulation()
    trace = {}
    with open(out) as fh:
        for line in fh:
            r = json.loads(line)
            trace[str(r["id"])] = [r["start"], list(r["assigned"]),
                                   r["state"]]
    return trace


@pytest.fixture(scope="module")
def fleet_result():
    """ONE batched launch of all eight compilable policies on the golden
    scenario — mixed (sched, alloc) lanes in the same vmapped call
    (``group_by_cost=False`` forces EBF and blocking lanes into the same
    launch; the default grouped path is pinned against this one by
    ``test_cost_grouping_is_decision_identical``)."""
    runner = FleetRunner()
    sims = [FleetRunner.build(tag, _workload(), SYS, sc, alloc_id=ac,
                              job_factory=JobFactory())
            for tag, (sc, ac) in sorted(TAGS.items())]
    return runner.run(sims, group_by_cost=False)


# ----------------------------------------------------------------------
def test_fleet_traces_match_host_golden(fleet_result):
    with open(GOLDEN_PATH) as fh:
        golden = json.load(fh)
    for i, tag in enumerate(sorted(TAGS)):
        got = fleet_result.trace(i)
        want = golden[tag]
        assert set(got) == set(want), f"{tag}: job id set diverged"
        diff = {jid: (want[jid], got[jid]) for jid in want
                if want[jid] != got[jid]}
        assert not diff, f"{tag}: {len(diff)} jobs diverged, e.g. " \
            f"{dict(list(diff.items())[:3])}"


def test_fleet_summary_matches_host_schema(fleet_result):
    host_keys = {"dispatcher", "events", "submitted", "completed",
                 "rejected", "cpu_time_s", "wall_time_s", "dispatch_time_s",
                 "kernel_launches", "kernel_launches_per_event",
                 "sim_end_time", "mem_avg_mb", "mem_max_mb"}
    for i, tag in enumerate(sorted(TAGS)):
        s = fleet_result.summary(i)
        assert host_keys <= set(s)
        assert s["dispatcher"] == tag and s["engine"] == "fleet"
        assert s["submitted"] == 400
        assert s["completed"] + s["rejected"] == 400
        assert s["events"] > 0 and s["sim_end_time"] > 0


def test_fleet_outputs_feed_metrics_pipeline(fleet_result, tmp_path):
    from repro.experimentation import metrics
    out, bench = fleet_result.write_outputs(str(tmp_path), 0)
    sl = metrics.slowdowns(out)
    assert sl and all(s >= 1.0 for s in sl)
    series = metrics.bench_series(bench)
    assert series["summary"]["completed"] == \
        fleet_result.summary(0)["completed"]
    assert metrics.dispatch_time_by_queue_size(bench)


@pytest.mark.parametrize("sched,sc,ac", [
    (lambda: LongestJobFirst(FirstFit()), SCHED_LJF, ALLOC_FF),
    (lambda: ShortestJobFirst(BestFit()), SCHED_SJF, ALLOC_BF),
    (lambda: EasyBackfilling(FirstFit()), SCHED_EBF, ALLOC_FF),
    (lambda: EasyBackfilling(BestFit()), SCHED_EBF, ALLOC_BF),
])
def test_single_sim_matches_host(sched, sc, ac, tmp_path):
    got = FleetRunner().run([FleetRunner.build(
        "solo", _workload(150, 7), SYS, sc, alloc_id=ac,
        job_factory=JobFactory())]).trace(0)
    want = _host_trace(sched(), tmp_path)
    assert got == want


def test_mixed_lanes_match_solo_launches(fleet_result):
    """An EBF lane (inner shadow/backfill loops) vmapped next to plain
    blocking lanes must decide exactly as when launched alone — masked
    lanes execute every inner loop body, so a masking bug would leak
    between policies."""
    tags = sorted(TAGS)
    for tag in ("EBF-BF", "FIFO-FF"):
        sc, ac = TAGS[tag]
        solo = FleetRunner().run([FleetRunner.build(
            tag, _workload(), SYS, sc, alloc_id=ac,
            job_factory=JobFactory())])
        assert solo.trace(0) == fleet_result.trace(tags.index(tag)), tag


def test_cost_grouping_is_decision_identical(fleet_result):
    """The default ``run`` splits EBF lanes into their own launch (vmap
    lockstep makes every lane pay the EBF round's inner-loop trips —
    grouping removes the convoy tax); every trajectory must match the
    forced single mixed launch exactly."""
    runner = FleetRunner()
    sims = [FleetRunner.build(tag, _workload(), SYS, sc, alloc_id=ac,
                              job_factory=JobFactory())
            for tag, (sc, ac) in sorted(TAGS.items())]
    grouped = runner.run(sims)
    for i, tag in enumerate(sorted(TAGS)):
        assert grouped.trace(i) == fleet_result.trace(i), tag
        assert grouped.summary(i)["events"] == \
            fleet_result.summary(i)["events"], tag


def test_compile_cache_reuses_executable():
    """Same bucketed (M, K) padded shape -> no recompile, same results;
    a different bucket misses the cache."""
    runner = FleetRunner()
    build = lambda n, seed, sc, ac: FleetRunner.build(
        f"c{n}-{seed}", _workload(n, seed), SYS, sc, alloc_id=ac,
        job_factory=JobFactory())
    r1 = runner.run([build(100, 3, SCHED_FIFO, ALLOC_FF)])
    # different workload size and policy, same padding bucket
    r2 = runner.run([build(90, 5, SCHED_EBF, ALLOC_BF)])
    assert not r1.cache_hit and r2.cache_hit
    assert r2.compile_time_s == 0.0
    # results must be identical to a fresh-runner (cold) launch
    cold = FleetRunner().run([build(90, 5, SCHED_EBF, ALLOC_BF)])
    assert r2.trace(0) == cold.trace(0)


def test_padding_is_inert():
    """pad_to (the fleet common-shape step) must not change results."""
    state, _ = SimState.from_workload(_workload(100, 3), SYS,
                                      job_factory=JobFactory())
    m, k = state.n_rows, state.assigned.shape[1]
    f1 = advance(state)
    f2 = advance(state.pad_to(m + 23, k + 3))
    for name in ("start", "end", "state", "queued_time"):
        assert np.array_equal(np.asarray(getattr(f1, name)),
                              np.asarray(getattr(f2, name))[:m]), name
    assert np.array_equal(np.asarray(f1.assigned),
                          np.asarray(f2.assigned)[:m, :k])
    assert int(f1.n_events) == int(f2.n_events)
    assert int(f1.now) == int(f2.now)


def test_midsim_snapshot_continues_identically(tmp_path):
    """Host runs 40 events, exports to SimState, device finishes the
    rest — final decisions must match the pure host run for every job
    still alive at the snapshot."""
    n, seed = 150, 7
    sim = Simulator(_workload(n, seed), SYS, FirstInFirstOut(FirstFit()),
                    job_factory=JobFactory(), lookahead_jobs=n + 1,
                    output_dir=str(tmp_path), name="cut")
    sim.start_simulation(max_events=40, write_output=False)
    state, meta = SimState.from_event_manager(sim.event_manager,
                                              sched_id=SCHED_FIFO)
    result = FleetResult(
        sims=[FleetSim("cut", state, meta, SCHED_FIFO)],
        finals=[advance(state)], wall_time_s=0.0, compile_time_s=0.0)
    got = result.trace(0)
    assert got, "snapshot carried no live jobs"
    want = _host_trace(FirstInFirstOut(FirstFit()), tmp_path, n, seed)
    diff = {jid: (want[jid], got[jid]) for jid in got
            if want[jid] != got[jid]}
    assert not diff, f"{len(diff)} jobs diverged after snapshot, e.g. " \
        f"{dict(list(diff.items())[:3])}"


# ----------------------------------------------------------------------
def test_dispatch_code_gating():
    assert dispatch_code(FirstInFirstOut(FirstFit())) == \
        (SCHED_FIFO, ALLOC_FF)
    assert dispatch_code(ShortestJobFirst(FirstFit())) == \
        (SCHED_SJF, ALLOC_FF)
    assert dispatch_code(LongestJobFirst(BestFit())) == \
        (SCHED_LJF, ALLOC_BF)
    assert dispatch_code(EasyBackfilling(FirstFit())) == \
        (SCHED_EBF, ALLOC_FF)
    assert dispatch_code(EasyBackfilling(BestFit())) == \
        (SCHED_EBF, ALLOC_BF)
    assert sched_code(EasyBackfilling(BestFit())) == SCHED_EBF
    assert alloc_code(FirstInFirstOut(BestFit())) == ALLOC_BF
    assert compiles(EasyBackfilling(BestFit()))

    # subclasses may override plan/find_nodes arbitrarily -> host only
    class TweakedFIFO(FirstInFirstOut):
        pass

    class TweakedFF(FirstFit):
        pass

    assert dispatch_code(TweakedFIFO(FirstFit())) is None
    assert dispatch_code(FirstInFirstOut(TweakedFF())) is None
    assert sched_code(TweakedFIFO(FirstFit())) is None
    assert not compiles(TweakedFIFO(FirstFit()))


def test_shard_map_multi_device(tmp_path):
    """5 sims over 4 forced host devices must match the host engine —
    run in a subprocess so XLA_FLAGS takes effect before jax init."""
    script = r"""
import json, sys
from repro.core.job import JobFactory
from repro.fleet import SCHED_FIFO, SCHED_SJF, SCHED_LJF, FleetRunner
from repro.workloads.synthetic import SyntheticWorkload
from repro.fleet import SCHED_EBF
import jax
assert jax.device_count() == 4, jax.device_count()
SYS = json.loads(sys.argv[1])
wl = lambda s: SyntheticWorkload(
    80, seed=s, mean_interarrival_s=25.0, duration_median_s=900.0,
    duration_sigma=1.1, node_weights={1: 0.5, 2: 0.3, 4: 0.2},
    resources={"core": (1, 4), "mem": (64, 1024)})
codes = [(SCHED_FIFO, 0), (SCHED_SJF, 1), (SCHED_LJF, 0),
         (SCHED_EBF, 1), (SCHED_SJF, 0)]
sims = [FleetRunner.build(f"s{i}", wl(30 + i % 2), SYS, sc, alloc_id=ac,
                          job_factory=JobFactory())
        for i, (sc, ac) in enumerate(codes)]
res = FleetRunner().run(sims)
assert res.n_devices == 4, res.n_devices
print(json.dumps([res.trace(i) for i in range(len(sims))]))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", script, json.dumps(SYS)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sharded = json.loads(proc.stdout.strip().splitlines()[-1])
    scheds = [FirstInFirstOut(FirstFit()), ShortestJobFirst(BestFit()),
              LongestJobFirst(FirstFit()), EasyBackfilling(BestFit()),
              ShortestJobFirst(FirstFit())]
    for i, sched in enumerate(scheds):
        sim = Simulator(_workload(80, 30 + i % 2), SYS, sched,
                        job_factory=JobFactory(), output_dir=str(tmp_path),
                        name=f"host{i}")
        out = sim.start_simulation()
        want = {}
        with open(out) as fh:
            for line in fh:
                r = json.loads(line)
                want[str(r["id"])] = [r["start"], list(r["assigned"]),
                                      r["state"]]
        assert sharded[i] == want, f"sim {i} diverged under shard_map"
