"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

    reduce_trace(path) -> {"busy_s", "window_s", "n_devices", "op_line",
                           "device_ops", "idle_gaps"}

- The window is the host span named ``window`` that the harness opens
  around the traced grids (the whole trace where there is none).
- Device busy time is the union of the intervals of the device's
  operations (the ``XLA Ops`` line of each ``/device:`` plane, or its
  ``XLA Modules`` line where it has no ops line), clipped to the window
  and averaged over the devices that ran anything.
- ``device_ops``: the operations that took most device time, summed by
  the name the trace prints (up to its `` = ``, so ``%while.659``), over
  all devices.  Operations nest: a loop's time includes its body's.
- ``idle_gaps``: the device's idle time in the window (first device),
  summed by the host span it falls in: each gap is cut at the edges of
  the harness's spans (``build``, ``launch``, ``write``), and a piece
  that no span covers is ``other``.
"""
from __future__ import annotations

import gzip
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

SPANS = ("build", "launch", "write")
OP_LINES = ("XLA Ops", "XLA Modules")

Interval = Tuple[int, int]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``[start, end)`` intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of a disjoint sorted ``busy`` within ``[lo, hi)``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two sorted, disjoint lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def label_gaps(idle: Sequence[Interval],
               spans: Dict[str, List[Interval]]) -> Dict[str, int]:
    """Nanoseconds of ``idle`` under each span name; the rest ``other``."""
    out = {name: overlap(idle, union(ivs)) for name, ivs in spans.items()}
    covered = union([iv for ivs in spans.values() for iv in ivs])
    out["other"] = sum(e - s for s, e in idle) - overlap(idle, covered)
    return {k: v for k, v in out.items() if v > 0}


def reduce_trace(path: str, top: int = 10) -> Dict[str, object]:
    """Reduce the ``.xplane.pb`` (or gzipped ``.xplane.pb.gz``) at ``path``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = defaultdict(list)
    window: List[Interval] = []
    devices: List[List[Interval]] = []
    op_ns: Dict[str, int] = defaultdict(int)
    op_line = None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    if ev.name == "window":
                        window.append(iv)
                    elif ev.name in SPANS:
                        spans[ev.name].append(iv)
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in OP_LINES if n in lines), None)
            if name is None:
                continue
            op_line = name
            ivs = []
            for ev in lines[name].events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                ivs.append((start, start + dur))
                op_ns[ev.name.split(" = ", 1)[0]] += dur
            if ivs:
                devices.append(union(ivs))
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
    else:
        every = [iv for ivs in devices for iv in ivs] + \
            [iv for ivs in spans.values() for iv in ivs]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    busy = [sum(e - s for s, e in clip(ivs, lo, hi)) for ivs in devices]
    idle = gaps(clip(devices[0], lo, hi), lo, hi)
    by_span = label_gaps(idle, spans)
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "n_devices": len(devices),
        "op_line": op_line,
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[n, ns / 1e9] for n, ns in
                      sorted(by_span.items(), key=lambda kv: -kv[1])[:top]],
    }
