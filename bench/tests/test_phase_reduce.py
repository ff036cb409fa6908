"""Device time by engine phase, idle and host time by program span
(``phase_reduce.reduce_phases``) and the readers of the metrics built on
it: on hand-made intervals, on the small trace recorded before the engine
named its phases and the program opened spans (everything comes out
``unscoped``, and every reader reports nothing), and on a trace recorded
once on a TPU v5e by ``record_trace.py`` with the phases named (one
seth.table2 grid of 8 lanes x 16 jobs, with the program's spans and the
harness's), gzipped."""
import gzip
import os
import shutil

import pytest

import phase_reduce
from phase_reduce import (PHASES, coverage, label_innermost, op_phase,
                          reduce_phases, self_times, stat_number)
from run import load_module
from trace_reduce import reduce_trace, union

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "small.xplane.pb.gz")
NEW = os.path.join(DATA, "phases.xplane.pb.gz")
# the phases every grid runs (drain needs failures, epilogue failures or
# telemetry)
RUN_PHASES = ("prologue", "next_event", "complete", "admit", "dispatch",
              "backfill", "record")


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(<lambda>)/vmap()/while/body/complete/while/body/scatter-add:",
     "complete"),
    ("jit(<lambda>)/vmap()/while/body/dispatch/while/body/argmin:",
     "dispatch"),
    ("jit(<lambda>)/vmap()/while/body_pred/next_event/lt:", "next_event"),
    ("jit(<lambda>)/vmap(prologue)/sort:", "prologue"),
    ("jit(<lambda>)/vmap(jit(_where))/select_n:", "unscoped"),
    ("jit(<lambda>)/vmap()/while/body/select_n;"
     "jit(<lambda>)/vmap()/while/body/record/scatter:", "unscoped"),
    ("jit(<lambda>)/vmap()/while/body/record/scatter;"
     "jit(<lambda>)/vmap()/while/body/select_n:", "record"),
    ("s.est:", "unscoped"),
    ("", "unscoped"),
])
def test_op_phase(tf_op, phase):
    assert op_phase(tf_op) == phase


def test_self_times_nested_and_overlapping():
    # a loop [0, 100) with two body ops, one holding a nested op
    ops = [(0, 100, "loop"), (10, 40, "a"), (20, 30, "b"), (50, 90, "c")]
    assert self_times(ops) == {"loop": 30, "a": 20, "b": 10, "c": 40}
    # partial overlap: the later start owns the instants it covers
    assert self_times([(0, 10, "x"), (5, 15, "y")]) == {"x": 5, "y": 10}
    # equal intervals: the later listed; same start: the shorter
    assert self_times([(0, 10, "x"), (0, 10, "y")]) == {"y": 10}
    assert self_times([(0, 10, "x"), (0, 4, "y")]) == {"y": 4, "x": 6}
    # gaps and empty intervals are nobody's
    assert self_times([(0, 5, "x"), (7, 7, "z"), (9, 12, "x")]) == {"x": 8}
    assert self_times([]) == {}


def test_self_times_add_up_to_the_union():
    ops = [(0, 50, "p"), (3, 9, "q"), (8, 20, "r"), (19, 60, "p"),
           (70, 80, "q"), (71, 72, "r"), (79, 85, "s"), (85, 90, "t")]
    got = self_times(ops)
    assert sum(got.values()) == sum(
        e - s for s, e in union([(s, e) for s, e, _ in ops]))


def test_label_innermost():
    spans = [(0, 100, "experiment.run"), (10, 40, "fleet.launch"),
             (12, 30, "fleet.execute"), (60, 90, "results.write"),
             (61, 70, "results.records")]
    idle = [(5, 11), (25, 35), (55, 65), (95, 110)]
    assert label_innermost(idle, spans) == {
        "experiment.run": 5 + 5 + 5, "fleet.launch": 1 + 5,
        "fleet.execute": 5, "results.write": 1, "results.records": 4,
        "none": 10}
    assert label_innermost(idle, []) == {"none": 6 + 10 + 10 + 15}


def test_old_trace_is_all_unscoped():
    out = reduce_phases(OLD)
    assert set(out["phases_s"]) == {"unscoped"}
    assert out["n_scoped_ops"] == 0
    assert set(out["idle_by_span"]) == {"none"}
    old = reduce_trace(OLD)
    assert out["window_s"] == pytest.approx(old["window_s"], rel=1e-6)
    # reduce_trace reads whole nanoseconds, this picoseconds
    assert out["busy_s"] == pytest.approx(old["busy_s"],
                                          abs=1e-9 * out["n_ops"])


@pytest.fixture(scope="module")
def new():
    return reduce_phases(NEW)


def test_phases_add_up_to_busy_time(new):
    assert sum(new["phases_s"].values()) == pytest.approx(new["busy_s"],
                                                          rel=1e-6)
    assert 0 < new["busy_s"] < new["window_s"]
    assert set(new["phases_s"]) <= set(PHASES) | {"unscoped"}


def test_every_phase_of_the_grid_appears(new):
    for phase in RUN_PHASES:
        assert new["phases_s"].get(phase, 0) > 0, phase
    assert new["n_scoped_ops"] > 0


def test_idle_by_program_span_adds_up(new):
    idle = sum(new["idle_by_span"].values())
    assert idle == pytest.approx(new["window_s"] - new["busy_s"], rel=1e-6)
    labels = set(new["idle_by_span"])
    assert {"fleet.build.load", "results.events_file"} <= labels
    assert all(n == "none" or n.startswith(("experiment.", "fleet.",
                                            "results."))
               for n in labels)


def test_program_spans_and_events(new):
    spans = new["program_spans"]
    for name in ("experiment.run", "fleet.build", "fleet.launch",
                 "fleet.execute", "results.write", "results.records",
                 "results.jobs_file", "results.events_file",
                 "results.summary"):
        assert spans.get(name, 0) > 0, name
    assert 0 < new["write_children_s"] <= spans["results.write"]
    assert set(new["harness_spans"]) == {"build", "launch", "write"}
    # 8 lanes of 16 jobs: a submission and an end per job at most
    assert 0 < new["events"] <= 8 * 2 * 16
    cov = coverage(new)
    assert 0 < cov["write_children_over_write"] <= 1
    assert 0 < cov["build_over_build"] <= 1
    assert 0 <= cov["idle_in_run_only_share"] < 1


def test_old_trace_has_no_program_spans():
    out = reduce_phases(OLD)
    assert out["program_spans"] == {} and out["write_children_s"] == 0
    assert out["events"] is None
    assert coverage(out) is None


def test_stat_number():
    plane = phase_reduce._xspace_class()().planes.add()
    stats = plane.lines.add().events.add().stats
    values = [("int64_value", 4086, 4086), ("uint64_value", 7, 7),
              ("double_value", 0.5, 0.5), ("str_value", "12", 12.0),
              ("str_value", "FIFO-FF-r0", None), ("ref_value", 3, None)]
    for field, value, want in values:
        st = stats.add()
        setattr(st, field, value)
        assert stat_number(st) == want, field
    assert stat_number(stats.add()) is None


NEW_METRICS = ["records_share", "jobs_file_share", "events_file_share",
               "device_unscoped_share"] + [
    f"device_us_per_event.{p}" for p in
    ("next_event", "complete", "admit", "dispatch", "backfill", "record")]


def _traced_run(tmp_path, monkeypatch, src):
    """A traced run's result as the harness hands it to the readers,
    with ``src`` where the harness leaves its trace."""
    root = tmp_path / "results" / "bench"
    trace = root / "seth.table2" / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    path = trace / "host.xplane.pb"
    with gzip.open(src, "rb") as fh, open(path, "wb") as out:
        shutil.copyfileobj(fh, out)
    monkeypatch.setattr(phase_reduce, "OUT_ROOT", str(root))
    return {"trace": {"bytes": os.path.getsize(path)}}


def test_readers_on_the_new_trace(tmp_path, monkeypatch, new):
    run = _traced_run(tmp_path, monkeypatch, NEW)
    assert phase_reduce.trace_path(run) is not None
    got = {m: _load(m).read(run) for m in NEW_METRICS}
    for m, value in got.items():
        assert value is not None and value > 0, m
    assert got["records_share"] == pytest.approx(
        100 * new["program_spans"]["results.records"] / new["window_s"])
    assert got["device_us_per_event.dispatch"] == pytest.approx(
        1e6 * new["phases_s"]["dispatch"] / new["events"])
    assert got["device_unscoped_share"] == pytest.approx(
        100 * new["phases_s"]["unscoped"] / new["busy_s"], rel=1e-6)


def test_readers_report_nothing_without_spans_or_scopes(tmp_path,
                                                        monkeypatch):
    run = _traced_run(tmp_path, monkeypatch, OLD)
    assert {m: _load(m).read(run) for m in NEW_METRICS} == dict.fromkeys(
        NEW_METRICS)


@pytest.mark.parametrize("trace", [{}, {"bytes": 1}],
                         ids=["untraced", "no-such-trace"])
def test_readers_report_nothing_without_a_trace(tmp_path, monkeypatch,
                                                trace):
    _traced_run(tmp_path, monkeypatch, NEW)
    assert {m: _load(m).read({"trace": trace}) for m in NEW_METRICS} == \
        dict.fromkeys(NEW_METRICS)


def _load(metric):
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_module(os.path.join(bench, "metrics", metric + ".py"))
