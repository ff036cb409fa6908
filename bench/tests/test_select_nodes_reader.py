"""The reader of ``device_us_per_event.select_nodes``: device self time under
the engine's ``select_nodes`` scope per event of the traced grid.

It reports nothing on the traces recorded before the scope existed
(``small`` and ``phases``), and a value on ``select_nodes.xplane.pb.gz``,
recorded once on a TPU v5e by ``record_trace.py`` with the scope in the
program (one seth.table2 grid of 8 lanes x 16 jobs), gzipped: the scope
nests inside ``dispatch`` and ``backfill``, so its time is part of theirs.
"""
import gzip
import os
import shutil

import pytest

import phase_reduce
from phase_reduce import reduce_phases
from run import load_module

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = [os.path.join(DATA, "small.xplane.pb.gz"),
       os.path.join(DATA, "phases.xplane.pb.gz")]
NEW = os.path.join(DATA, "select_nodes.xplane.pb.gz")
METRIC = "device_us_per_event.select_nodes"


def _reader():
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_module(os.path.join(bench, "metrics", METRIC + ".py"))


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


@pytest.mark.parametrize("src", OLD, ids=["small", "phases"])
def test_nothing_on_a_trace_without_the_scope(tmp_path, monkeypatch, src):
    run = _traced_run(tmp_path, monkeypatch, src)
    assert _reader().read(run) is None
    assert _reader().scope_self_time_s(src) is None


def test_nothing_without_a_trace():
    assert _reader().read({"trace": {}}) is None


def test_a_value_inside_dispatch_and_backfill(tmp_path, monkeypatch):
    run = _traced_run(tmp_path, monkeypatch, NEW)
    got = _reader().read(run)
    phases = reduce_phases(NEW)
    per_event = {p: 1e6 * s / phases["events"]
                 for p, s in phases["phases_s"].items()}
    assert 0 < got < per_event["dispatch"] + per_event["backfill"]
    assert got == pytest.approx(
        1e6 * _reader().scope_self_time_s(NEW) / phases["events"])
