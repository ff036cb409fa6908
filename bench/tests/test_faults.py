"""A run of the harness with the timed path broken underneath must come
out ``correct: false``.  The run skips the look for a chip and drives a
small seth.table2 (1 seed x 96 jobs per lane) on the CPU.

Faults, each planted in ``FleetRunner._launch``:
- the state returned unchanged (the engine never advances);
- half of the batch left out (its lanes come back unadvanced);
- an answer altered where it is produced (one started job per lane
  starts a second later).
The exchange between chips does not exist in a one-chip cell.
"""
import numpy as np
import pytest

import run


def _stale(self, sims, orig):
    finals, wall, comp, hit, nd = orig(self, sims)
    return [s.state for s in sims], wall, comp, hit, nd


def _half(self, sims, orig):
    finals, wall, comp, hit, nd = orig(self, sims)
    keep = len(sims) // 2
    return (finals[:keep] + [s.state for s in sims[keep:]], wall, comp,
            hit, nd)


def _altered(self, sims, orig):
    finals, wall, comp, hit, nd = orig(self, sims)
    out = []
    for f in finals:
        start = np.array(f.start)
        row = int(np.argmax(start >= 0))
        start[row] += 1
        out.append(f._replace(start=start))
    return out, wall, comp, hit, nd


def _run(monkeypatch, tmp_path, fault=None):
    from repro.fleet.runner import FleetRunner

    monkeypatch.setattr(run, "OUT_ROOT", str(tmp_path))
    if fault is not None:
        orig = FleetRunner._launch
        monkeypatch.setattr(FleetRunner, "_launch",
                            lambda self, sims: fault(self, sims, orig))
    cell = run.find_cell(run.load_spec(), "seth.table2")
    return run.run_cell(cell, seed=2 ** 31 + 101, seconds=0.5, trace=False,
                        require_tpu=False, jobs=96, seeds_per_grid=1)


def test_sound_run_is_correct(monkeypatch, tmp_path):
    out = _run(monkeypatch, tmp_path)
    assert out["correct"] is True
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["state_unchanged", "half_batch", "altered"])
def test_fault_is_caught(monkeypatch, tmp_path, fault):
    out = _run(monkeypatch, tmp_path, fault)
    assert out["correct"] is False
    assert out["failed"] > 0
