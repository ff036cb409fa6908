"""The reduction from a profiler trace to busy time, top operations and
idle gaps: on hand-made intervals, and on a small trace recorded once on
a TPU v5e by ``record_trace.py`` (one seth.table2 grid of 8 lanes x 16
jobs, with the harness's spans), gzipped."""
import os

import pytest

from trace_reduce import (clip, gaps, label_gaps, overlap, reduce_trace,
                          union)

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb.gz")


def test_union_gaps_and_overlap():
    busy = union([(5, 8), (0, 2), (1, 3), (7, 9), (9, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert gaps(clip(busy, -1, 4), -1, 4) == [(-1, 0), (3, 4)]
    assert overlap([(0, 3), (5, 9)], [(2, 6), (8, 20)]) == 1 + 1 + 1


def test_label_gaps_by_span():
    idle = [(3, 5), (9, 12)]
    spans = {"build": [(0, 4)], "write": [(10, 11)], "launch": [(4, 9)]}
    assert label_gaps(idle, spans) == {"build": 1, "launch": 1, "write": 1,
                                       "other": 2}


def test_recorded_trace():
    out = reduce_trace(TRACE)
    assert out["op_line"] == "XLA Ops"
    assert out["n_devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    labels = {name for name, _ in out["idle_gaps"]}
    assert labels <= {"build", "launch", "write", "other"}
    assert {"build", "write"} <= labels
    idle = sum(s for _, s in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
    names = [name for name, _ in out["device_ops"]]
    assert names[0].startswith("%while")
    assert all(" " not in name for name in names)
    assert len(out["device_ops"]) <= 10
