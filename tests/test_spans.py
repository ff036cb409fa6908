"""Host spans and the engine's named scopes (DESIGN.md §10).

* ``SpanRecorder``: nesting, parent links, the grid id, totals;
* an ``Experiment`` grid records the span tree of the fleet path — one
  ``experiment.run``, ``fleet.build`` with its three children per
  repeat (its ``lanes`` the rows that share it),
  ``fleet.launch`` with pad / execute / fetch / unstack per cost class
  (``fleet.compile`` only on a cache miss), ``results.write`` with its
  four children per lane, the two files' spans counting their lines —
  every child inside its parent;
* the compiled loop names its phases in the ops' ``op_name`` metadata,
  and the node ordering of every allocator probe ``select_nodes``.
"""
import re
import time

import jax
import pytest

from repro.cluster import FailureInjector
from repro.core.dispatchers import EasyBackfilling, FirstFit, FirstInFirstOut
from repro.experimentation import Experiment
from repro.fleet import SCHED_FIFO, FleetRunner, advance_fn
from repro.fleet.engine import PHASES
from repro.telemetry import SpanRecorder
from repro.workloads.synthetic import SyntheticWorkload

SYS = {"groups": {"a": {"core": 4, "mem": 1024}, "b": {"core": 8, "mem": 2048}},
       "nodes": {"a": 6, "b": 4}}
N_JOBS = 100
WRITE_CHILDREN = ["results.records", "results.jobs_file", "results.summary",
                  "results.events_file"]
LAUNCH_CHILDREN = ["fleet.pad", "fleet.execute", "fleet.fetch",
                   "fleet.unstack"]


def _workload(seed=5):
    return SyntheticWorkload(
        N_JOBS, seed=seed, mean_interarrival_s=40.0,
        duration_median_s=600.0, node_weights={1: 0.6, 2: 0.3, 4: 0.1},
        resources={"core": (1, 4), "mem": (64, 1024)})


# ----------------------------------------------------------------------
# the recorder
# ----------------------------------------------------------------------
def test_recorder_nests_and_links_parents():
    rec = SpanRecorder(grid=7)
    with rec.span("a", lane="x") as a:
        with rec.span("a.b"):
            with rec.span("a.b.c"):
                pass
        with rec.span("a.d"):
            pass
    with rec.span("e"):
        pass
    names = [s.name for s in rec.spans]
    assert names == ["a", "a.b", "a.b.c", "a.d", "e"]
    assert [s.parent for s in rec.spans] == [-1, 0, 1, 0, -1]
    assert {s.grid for s in rec.spans} == {7}
    assert a.attrs == {"lane": "x"}
    assert [s.name for s in rec.children(0)] == ["a.b", "a.d"]
    for s in rec.spans:
        assert s.end >= s.start
        if s.parent >= 0:
            p = rec.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end


def test_recorder_grid_ids_differ_and_totals_sum_durations():
    r1, r2 = SpanRecorder(), SpanRecorder()
    assert r1.grid != r2.grid
    for _ in range(3):
        with r1.span("x"):
            time.sleep(0.001)
    with r1.span("y"):
        pass
    totals = r1.totals()
    assert set(totals) == {"x", "y"}
    assert totals["x"] == pytest.approx(
        sum(s.seconds for s in r1.spans if s.name == "x"), rel=1e-12)
    assert totals["x"] >= 0.003
    assert sum(totals.values()) == pytest.approx(
        sum(s.seconds for s in r1.spans), rel=1e-12)


def test_recorder_closes_span_on_error():
    rec = SpanRecorder()
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise ValueError("boom")
    with rec.span("after"):
        pass
    assert [s.parent for s in rec.spans] == [-1, 0, -1]
    assert all(s.end >= s.start > 0 for s in rec.spans)


# ----------------------------------------------------------------------
# the span tree of an Experiment grid
# ----------------------------------------------------------------------
def _grid(tmp_path, name):
    exp = Experiment(name, _workload(), SYS, output_dir=str(tmp_path),
                     repeats=2)
    exp.add_dispatcher(FirstInFirstOut(FirstFit()))
    exp.add_dispatcher(EasyBackfilling(FirstFit()))
    exp.run_simulation(produce_plots=False)
    return exp


@pytest.fixture(scope="module")
def grids(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    # the second grid reuses both launches' executables
    return _grid(tmp, "first"), _grid(tmp, "second")


def _kids(spans, span):
    i = next(j for j, s in enumerate(spans) if s is span)
    return [s for s in spans if s.parent == i]


@pytest.mark.parametrize("which", [0, 1], ids=["first", "second"])
def test_experiment_span_tree(grids, which):
    exp = grids[which]
    spans = exp.spans
    lanes = {"FIFO-FF-r0", "FIFO-FF-r1", "EBF-FF-r0", "EBF-FF-r1"}
    roots = [s for s in spans if s.parent == -1]
    assert [s.name for s in roots] == ["experiment.run"]
    assert len({s.grid for s in spans}) == 1

    top = _kids(spans, roots[0])
    # one build per repeat, shared by the repeat's two rows
    builds = [s for s in top if s.name == "fleet.build"]
    assert [s.attrs["lane"] for s in builds] == ["FIFO-FF-r0", "FIFO-FF-r1"]
    assert sum(s.attrs["lanes"] for s in builds) == len(lanes)
    for b in builds:
        assert [c.name for c in _kids(spans, b)] == [
            "fleet.build.load", "fleet.build.export", "fleet.build.bf_key"]

    launches = [s for s in top if s.name == "fleet.launch"]
    assert sorted(s.attrs["cost_class"] for s in launches) == [
        "blocking", "ebf"]
    misses = sum(not ln["cache_hit"] for ln in exp.fleet_launches)
    compiles = 0
    for ln in launches:
        kids = [c.name for c in _kids(spans, ln)]
        compiles += kids.count("fleet.compile")
        assert [k for k in kids if k != "fleet.compile"] == LAUNCH_CHILDREN
    assert compiles == misses
    if which == 1:
        assert misses == 0

    writes = [s for s in top if s.name == "results.write"]
    assert {s.attrs["lane"] for s in writes} == lanes and len(writes) == 4
    for w in writes:
        kids = _kids(spans, w)
        assert [c.name for c in kids] == \
            WRITE_CHILDREN
        # one line per job; one per event and the summary line
        jobs_file, events_file = kids[1], kids[3]
        assert jobs_file.attrs["lines"] == N_JOBS
        assert events_file.attrs["lines"] == w.attrs["events"] + 1
    # the summary _run_fleet reads after writing is a child of the run
    assert sum(s.name == "results.summary" for s in top) == 4

    for s in spans:
        assert s.end >= s.start
        if s.parent >= 0:
            p = spans[s.parent]
            assert p.start <= s.start and s.end <= p.end, (s.name, p.name)


def test_write_spans_count_the_lanes_events(grids):
    for exp in grids:
        writes = [s for s in exp.spans if s.name == "results.write"]
        assert sum(s.attrs["events"] for s in writes) == sum(
            ln["events"] for ln in exp.fleet_launches) > 0


def test_launch_entries_split_wall_time(grids):
    for exp in grids:
        for ln in exp.fleet_launches:
            for key in ("pad_s", "execute_s", "fetch_s", "unstack_s"):
                assert ln[key] >= 0.0
            # entries are rounded to the microsecond
            assert ln["execute_s"] + ln["fetch_s"] <= ln["wall_time_s"] + 1e-6


def test_launch_entry_matches_its_spans(grids):
    exp = grids[1]
    spans = exp.spans
    launches = [s for s in spans if s.name == "fleet.launch"]
    for ln, entry in zip(launches, exp.fleet_launches):
        assert ln.attrs["cost_class"] == entry["cost_class"]
        kids = {c.name: c.seconds for c in _kids(spans, ln)}
        for ph in ("pad", "execute", "fetch", "unstack"):
            assert entry[f"{ph}_s"] == round(kids[f"fleet.{ph}"], 6)


def test_fleet_runner_alone_records_into_its_own_recorder():
    sims = [FleetRunner.build("a", _workload(), SYS, SCHED_FIFO)]
    runner = FleetRunner()
    result = runner.run(sims)
    names = [s.name for s in runner.spans.spans]
    assert names[0] == "fleet.launch"
    assert result.spans is runner.spans
    result.summary(0)
    assert runner.spans.spans[-1].name == "results.summary"


# ----------------------------------------------------------------------
# named scopes on the engine's phases
# ----------------------------------------------------------------------
def _lowered(failures=None):
    """The fleet program of two FIFO-FF lanes, lowered as the runner
    lowers it: vmapped over the states, one Best-Fit key for both."""
    sims = [FleetRunner.build(f"s{i}", _workload(seed), SYS, SCHED_FIFO,
                              failures=failures)
            for i, seed in enumerate((5, 6))]
    _, args, _, _, _ = FleetRunner()._pad(sims)
    return jax.jit(jax.vmap(advance_fn(), in_axes=(0, None))).lower(*args)


def _scopes(text):
    return set(re.findall(r"[\w.]+", " ".join(
        re.findall(r'op_name="([^"]*)"', text)
        + re.findall(r'loc\("([^"]*)"', text))))


def _phases(text):
    return {p for p in PHASES if p in _scopes(text)}


def test_engine_phases_name_the_compiled_ops():
    lowered = _lowered()
    want = {"prologue", "next_event", "complete", "admit", "dispatch",
            "backfill", "record"}
    assert want <= _phases(lowered.as_text(debug_info=True))
    assert want <= _phases(lowered.compile().as_text())
    assert "drain" not in _phases(lowered.as_text(debug_info=True))


def test_select_nodes_scope_names_the_compiled_ops():
    """Every allocator probe's node ordering runs under ``select_nodes``,
    nested inside the phase that probes (the first phase on the path is
    what a device trace charges, so the phases keep their meaning)."""
    lowered = _lowered()
    assert "select_nodes" in _scopes(lowered.as_text(debug_info=True))
    paths = [p for p in re.findall(r'op_name="([^"]*)"',
                                   lowered.compile().as_text())
             if "select_nodes" in re.split(r"[/()]", p)]
    assert paths
    assert {next(t for t in re.split(r"[/()]", p) if t in PHASES)
            for p in paths} == {"dispatch", "backfill"}


def test_drain_scope_with_failures():
    inj = FailureInjector(10, mtbf_s=4000.0, repair_s=900.0,
                          horizon_s=6000, seed=3)
    lowered = _lowered(inj)
    assert {"drain", "epilogue"} <= _phases(lowered.as_text(debug_info=True))


def test_scopes_change_no_decision(grids, tmp_path):
    """The scoped engine's lanes equal the host engine's, job for job."""
    import json

    host = Experiment("host", _workload(), SYS, output_dir=str(tmp_path),
                      repeats=2, use_fleet=False)
    host.add_dispatcher(FirstInFirstOut(FirstFit()))
    host.add_dispatcher(EasyBackfilling(FirstFit()))
    host.run_simulation(produce_plots=False)
    for lane in ("FIFO-FF-r0", "FIFO-FF-r1", "EBF-FF-r0", "EBF-FF-r1"):
        runs = []
        for exp in (grids[0], host):
            with open(f"{exp.output_dir}/{lane}-output.jsonl") as fh:
                runs.append(sorted(
                    (r["id"], r["start"], r["assigned"], r["state"])
                    for r in map(json.loads, fh)))
        assert runs[0] == runs[1], lane
