"""One lane state per repeat, shared by the repeat's dispatcher rows.

``Experiment._run_fleet`` calls ``FleetRunner.build`` once per repeat and
derives every row's lane from it by replacing the two scalars
``sched_id`` / ``alloc_id``.  Pinned here on a grid of four fleet rows ×
two repeats plus one host-fallback row, for a seeded workload and for a
list of records:

* ``FleetRunner.build`` runs once per repeat, counted by a wrapper around
  it as the benchmark harness wraps it, and its span's ``lanes`` count
  the rows;
* every file the grid writes is byte for byte what the planner wrote when
  it built one state per lane (reimplemented below), with the clocks and
  the resident-set reading held fixed;
* every lane carries its own row's codes, in its state and its summary;
* the arrays the lanes share are the build's, unchanged by the run.
"""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import simulator as simulator_mod
from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                    FirstInFirstOut, LongestJobFirst,
                                    ShortestJobFirst)
from repro.core.resources import ResourceManager
from repro.core.simulator import default_job_factory
from repro.experimentation import Experiment
from repro.fleet import FleetRunner
from repro.fleet import runner as runner_mod
from repro.fleet.engine import dispatch_code
from repro.workloads.synthetic import SyntheticWorkload

SYS = {"groups": {"a": {"core": 4, "mem": 1024}, "b": {"core": 8, "mem": 2048}},
       "nodes": {"a": 6, "b": 4}}
FLEET_ROWS = ["FIFO-FF", "SJF-BF", "LJF-FF", "EBF-BF"]
REPEATS = 2


class TweakedFIFO(FirstInFirstOut):
    """Not exactly FirstInFirstOut, so its row runs on the host."""
    name = "TFIFO"


def _rows():
    return [FirstInFirstOut(FirstFit()), ShortestJobFirst(BestFit()),
            LongestJobFirst(FirstFit()), EasyBackfilling(BestFit()),
            TweakedFIFO(FirstFit())]


def _synthetic():
    # arrivals fast enough that jobs queue, so the rows decide apart
    return SyntheticWorkload(
        90, seed=21, mean_interarrival_s=20.0, duration_median_s=900.0,
        duration_sigma=1.1, node_weights={1: 0.5, 2: 0.3, 4: 0.2},
        resources={"core": (1, 4), "mem": (64, 1024)})


WORKLOADS = {"synthetic": _synthetic,
             "records": lambda: list(_synthetic())}


class _FixedClock:
    """Stands in for the ``time`` module: every reading is 0."""

    @staticmethod
    def perf_counter():
        return 0.0

    process_time = time = perf_counter


def _per_lane_run_fleet(self, scheds, spans):
    """The planner as it was: one ``FleetRunner.build`` per lane."""
    factory = default_job_factory(ResourceManager(self.sys_config))
    sims, keys = [], []
    for sched in scheds:
        name = sched.dispatcher_name
        s_code, a_code = dispatch_code(sched)
        for rep in range(self.repeats):
            workload, seed = self._repeat_workload(rep)
            sims.append(FleetRunner.build(
                self._rep_name(name, rep), workload, self.sys_config,
                s_code, alloc_id=a_code, job_factory=factory, seed=seed,
                spans=spans))
            keys.append((name, rep))
    result = FleetRunner(spans=spans).run(sims)
    out = {}
    for i, (name, rep) in enumerate(keys):
        out_path, bench_path = result.write_outputs(self.output_dir, i)
        entry = out.setdefault(name, {"summaries": []})
        summary = result.summary(i)
        summary["fallback_reason"] = None
        entry["summaries"].append(summary)
        entry["output"] = out_path
        entry["bench"] = bench_path
    return out


def _grid(tmp, name, workload, per_lane):
    """Run the grid; return the experiment, each build's sim with a copy
    of its arrays as built, and the lanes handed to ``FleetRunner.run``."""
    builds, lanes = [], []
    build = FleetRunner.__dict__["build"].__func__
    run = FleetRunner.run

    def counted_build(*args, **kwargs):
        sim = build(*args, **kwargs)
        builds.append((sim, {k: np.array(v, copy=True)
                             for k, v in sim.state._asdict().items()}))
        return sim

    def captured_run(self, sims, **kwargs):
        lanes.extend(sims)
        return run(self, sims, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        for mod in (runner_mod, simulator_mod):
            mp.setattr(mod, "time", _FixedClock)
            mp.setattr(mod, "rss_mb", lambda: 100.0)
        mp.setattr(FleetRunner, "build", staticmethod(counted_build))
        mp.setattr(FleetRunner, "run", captured_run)
        if per_lane:
            mp.setattr(Experiment, "_run_fleet", _per_lane_run_fleet)
        exp = Experiment(name, workload, SYS, output_dir=str(tmp),
                         repeats=REPEATS)
        for sched in _rows():
            exp.add_dispatcher(sched)
        results = exp.run_simulation(produce_plots=False)
    return SimpleNamespace(exp=exp, results=results, builds=builds,
                           lanes=lanes)


@pytest.fixture(scope="module", params=sorted(WORKLOADS))
def grids(request, tmp_path_factory):
    tmp = tmp_path_factory.mktemp(request.param)
    make = WORKLOADS[request.param]
    return (_grid(tmp, "shared", make(), per_lane=False),
            _grid(tmp, "per_lane", make(), per_lane=True))


def test_one_build_per_repeat(grids):
    shared, per_lane = grids
    assert len(shared.builds) == REPEATS
    assert len(per_lane.builds) == len(FLEET_ROWS) * REPEATS
    spans = [s for s in shared.exp.spans if s.name == "fleet.build"]
    assert [s.attrs["lane"] for s in spans] == ["FIFO-FF-r0", "FIFO-FF-r1"]
    assert [s.attrs["lanes"] for s in spans] == [len(FLEET_ROWS)] * REPEATS
    assert len(shared.lanes) == len(FLEET_ROWS) * REPEATS
    # the host row still runs on the host, once per repeat
    assert {s["engine"] for s in shared.results["TFIFO-FF"]["summaries"]} \
        == {"host"}


def test_outputs_match_per_lane_builds(grids):
    shared, per_lane = grids
    names = sorted(os.listdir(shared.exp.output_dir))
    assert names == sorted(os.listdir(per_lane.exp.output_dir))
    expected = {"summaries.json"} | {
        f"{row}-r{rep}-{kind}.jsonl" for row in FLEET_ROWS + ["TFIFO-FF"]
        for rep in range(REPEATS) for kind in ("output", "bench")}
    assert set(names) == expected
    for fname in names:
        with open(os.path.join(shared.exp.output_dir, fname), "rb") as a, \
                open(os.path.join(per_lane.exp.output_dir, fname), "rb") as b:
            assert a.read() == b.read(), fname
    # the rows decide apart, so a lane run with another row's codes shows
    outputs = set()
    for row in FLEET_ROWS:
        with open(shared.results[row]["output"], "rb") as fh:
            outputs.add(fh.read())
    assert len(outputs) == len(FLEET_ROWS)


def test_lanes_carry_their_rows_codes(grids):
    shared, _ = grids
    codes = {s.dispatcher_name: dispatch_code(s) for s in _rows()[:-1]}
    # lanes in the planner's order: rows in dispatcher order, then repeats
    assert [lane.name for lane in shared.lanes] == [
        f"{row}-r{rep}" for row in FLEET_ROWS for rep in range(REPEATS)]
    for lane in shared.lanes:
        row = lane.name.rsplit("-r", 1)[0]
        assert (lane.sched_id, lane.alloc_id) == codes[row]
        assert (int(lane.state.sched_id), int(lane.state.alloc_id)) \
            == codes[row]
    for row in FLEET_ROWS:
        assert [s["dispatcher"] for s in shared.results[row]["summaries"]] \
            == [row] * REPEATS


def test_shared_arrays_survive_the_run(grids):
    shared, _ = grids
    for rep, (base, built) in enumerate(shared.builds):
        same_rep = [lane for lane in shared.lanes
                    if lane.name.endswith(f"-r{rep}")]
        assert len(same_rep) == len(FLEET_ROWS)
        for lane in same_rep:
            # shared, not copied
            assert lane.meta is base.meta and lane.seed == base.seed
            for k, v in base.state._asdict().items():
                if np.ndim(v):
                    assert getattr(lane.state, k) is v, k
        # and untouched by padding, stacking and the launch
        for k, v in base.state._asdict().items():
            np.testing.assert_array_equal(v, built[k], err_msg=k)
