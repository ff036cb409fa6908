"""Reduce a profiler trace to device time by engine phase and host time by
program span.

    reduce_phases(path) -> {"busy_s", "window_s", "phases_s", "unscoped_ops",
                            "idle_by_span", "program_spans",
                            "write_children_s", "harness_spans", "events",
                            "n_ops", "n_scoped_ops"}
    traced(run)         -> reduce_phases of the trace the harness's traced
                           grid left, for a per-layer metric's reader
    python3 bench/phase_reduce.py <trace.xplane.pb[.gz]>   # prints both

- Reads the XSpace itself (``read_xspace``: the protobuf wire format, with
  each device op's metadata and each host event's stats, which
  ``ProfileData`` does not expose).
- The window is the host span named ``window``, as in ``trace_reduce``.
- ``phases_s``: device time of the first device in the window by engine
  phase.  Each op's *self time* is the instants it covers and no op
  nested in it covers (so a loop's own time is what its body leaves);
  an op's phase is the first of :data:`PHASES` on the scope path of its
  ``tf_op`` (JAX's op_name, ``.../while/body/complete/...``), else
  ``unscoped``.  Self times add up to busy time exactly.  A fusion
  carries the op_name of its root, so a phase is exact only at fusion
  boundaries.  ``unscoped_ops``: the ops with most unscoped self time, by
  trace name (up to `` = ``) and ``tf_op``.
- ``idle_by_span``: the device's idle time in the window by the
  innermost program span (``experiment.*``, ``fleet.*``, ``results.*``
  host annotations) covering it; ``none`` where no program span does.
- ``program_spans``: host seconds in the window per program span name;
  ``write_children_s`` the seconds of the ``results.*`` spans opened
  inside a ``results.write``; ``harness_spans`` the seconds of the
  harness's own ``build``/``launch``/``write`` spans.
- ``events``: the simulated events of the lanes written in the window,
  the sum of the ``events`` stat of the ``results.write`` spans; None
  where no span carries one.

A trace of a program that names no phases and opens no spans gives
everything ``unscoped``, no program spans and no events: the readers
then report nothing.
"""
from __future__ import annotations

import functools
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from trace_reduce import SPANS, Interval, gaps, overlap, union

# the engine's named scopes (repro/fleet/engine.py), in program order
PHASES = ("prologue", "next_event", "complete", "drain", "admit",
          "dispatch", "backfill", "record", "epilogue")
# host annotations of the program's own spans (repro/telemetry/spans.py)
PROGRAM_SPAN_PREFIXES = ("experiment.", "fleet.", "results.")
# where bench/run.py's traced grid leaves its trace:
# <checkout>/results/bench/<cell>/trace/
OUT_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results", "bench")


@functools.lru_cache(maxsize=None)
def _xspace_class():
    """A message class for the parts of ``tsl/profiler/protobuf/
    xplane.proto`` read here, built at run time (no generated module is
    installed); fields left out are skipped by the parser."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    fd = descriptor_pb2.FieldDescriptorProto
    one, many = fd.LABEL_OPTIONAL, fd.LABEL_REPEATED
    i64, u64, f64 = fd.TYPE_INT64, fd.TYPE_UINT64, fd.TYPE_DOUBLE
    text, msg_t = fd.TYPE_STRING, fd.TYPE_MESSAGE
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for fname, number, ftype, label, *ref in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if ref:
                f.type_name = ".bench_xplane." + ref[0]
        return m

    types = proto.message_type
    stat = message(types, "XStat", [
        ("metadata_id", 1, i64, one), ("double_value", 2, f64, one),
        ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
        ("str_value", 5, text, one), ("ref_value", 7, u64, one)])
    stat.oneof_decl.add(name="value")
    for f in stat.field[1:]:
        f.oneof_index = 0
    message(types, "XEvent", [
        ("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
        ("duration_ps", 3, i64, one), ("stats", 4, msg_t, many, "XStat")])
    message(types, "XLine", [
        ("name", 2, text, one), ("timestamp_ns", 3, i64, one),
        ("events", 4, msg_t, many, "XEvent")])
    message(types, "XEventMetadata", [
        ("id", 1, i64, one), ("name", 2, text, one),
        ("stats", 5, msg_t, many, "XStat")])
    message(types, "XStatMetadata", [
        ("id", 1, i64, one), ("name", 2, text, one)])
    plane = message(types, "XPlane", [
        ("name", 2, text, one), ("lines", 3, msg_t, many, "XLine"),
        ("event_metadata", 4, msg_t, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg_t, many, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = message(plane.nested_type, entry, [
            ("key", 1, i64, one), ("value", 2, msg_t, one, value)])
        m.options.map_entry = True
    message(types, "XSpace", [("planes", 1, msg_t, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_xspace(path: str):
    """The XSpace at ``path`` (``.xplane.pb``, or gzipped ``.gz``)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as fh:
        return _xspace_class().FromString(fh.read())


def stat_number(stat) -> Optional[float]:
    """An XStat's value as a number, where it holds one."""
    kind = stat.WhichOneof("value")
    if kind in ("int64_value", "uint64_value", "double_value"):
        return getattr(stat, kind)
    if kind == "str_value":
        try:
            return float(stat.str_value)
        except ValueError:
            return None
    return None


def op_phase(tf_op: str) -> str:
    """The engine phase of an op from its ``tf_op`` (JAX's op_name, with
    ``:type`` after it and ``;`` between the names of merged ops): the
    first of :data:`PHASES` on the first name's scope path, where a
    transform wraps a scope as ``vmap(prologue)``; else ``unscoped``."""
    path = tf_op.split(";", 1)[0].rsplit(":", 1)[0]
    for token in re.split(r"[/()]", path):
        if token in PHASES:
            return token
    return "unscoped"


def self_times(ops: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Self time of ``(start, end, label)`` intervals summed by label:
    each instant covered by any interval goes to the innermost one
    covering it — the latest to start; of two that start together, the
    one that ends first; of two equal ones, the later listed.  The values
    add up to the length of the intervals' union."""
    out: Dict[str, int] = defaultdict(int)
    # open intervals, their ends non-increasing towards the top, which
    # is the innermost; ``t`` is the instant charged up to
    stack: List[Tuple[int, str]] = []
    t = 0
    for start, end, label in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= start:
            e, top = stack.pop()
            if e > t:
                out[top] += e - t
                t = e
        if stack and start > t:
            out[stack[-1][1]] += start - t
        t = start
        # an open interval that ends before this one never owns an
        # instant again: this one starts later and covers the rest
        while stack and stack[-1][0] <= end:
            stack.pop()
        if end > start:
            stack.append((end, label))
    while stack:
        e, top = stack.pop()
        if e > t:
            out[top] += e - t
            t = e
    return dict(out)


def label_innermost(idle: Sequence[Interval],
                    spans: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Length of ``idle`` (sorted, disjoint) under each span name, by the
    innermost span covering each instant (the latest to start); ``none``
    for what no span covers."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    pieces: Dict[str, List[Interval]] = defaultdict(list)
    for lo, hi in zip(bounds, bounds[1:]):
        cover = [sp for sp in spans if sp[0] <= lo and hi <= sp[1]]
        if cover:
            name = max(cover, key=lambda sp: (sp[0], -sp[1]))[2]
            pieces[name].append((lo, hi))
    out = {name: overlap(idle, union(ivs)) for name, ivs in pieces.items()}
    out["none"] = sum(e - s for s, e in idle) - sum(out.values())
    return {k: v for k, v in out.items() if v > 0}


def reduce_phases(path: str, top: int = 8) -> Dict[str, object]:
    """Device self time by engine phase, idle time by program span and
    host time by span in the window of the trace at ``path`` (see the
    module docstring)."""
    space = read_xspace(path)
    window: List[Interval] = []
    # (start, end, name, events stat or None) of the host spans read here
    host: List[Tuple[int, int, str, Optional[float]]] = []
    device = None
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            names = {k: v.name for k, v in plane.event_metadata.items()}
            events_stat = {k for k, v in plane.stat_metadata.items()
                           if v.name == "events"}
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    if name == "window" or name in SPANS or name.startswith(
                            PROGRAM_SPAN_PREFIXES):
                        iv = (base + ev.offset_ps,
                              base + ev.offset_ps + ev.duration_ps)
                        if name == "window":
                            window.append(iv)
                            continue
                        events = next((stat_number(st) for st in ev.stats
                                       if st.metadata_id in events_stat),
                                      None)
                        host.append(iv + (name, events))
        elif device is None and plane.name.startswith("/device:") and any(
                line.name == "XLA Ops" and line.events
                for line in plane.lines):
            device = plane
    if device is None:
        raise ValueError(f"{path}: no device operations in the trace")
    tf_stat = {k for k, v in device.stat_metadata.items()
               if v.name == "tf_op"}
    tf_op = {k: next((st.str_value for st in meta.stats
                      if st.metadata_id in tf_stat), "")
             for k, meta in device.event_metadata.items()}
    phase_of = {k: op_phase(op) for k, op in tf_op.items()}
    line = next(ln for ln in device.lines if ln.name == "XLA Ops")
    base = line.timestamp_ns * 1000
    ops = [(base + ev.offset_ps, base + ev.offset_ps + ev.duration_ps,
            ev.metadata_id) for ev in line.events]
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
    else:
        lo = min([s for s, _, _ in ops] + [h[0] for h in host])
        hi = max([e for _, e, _ in ops] + [h[1] for h in host])
    ops = [(max(s, lo), min(e, hi), p) for s, e, p in ops
           if min(e, hi) > max(s, lo)]
    host = [(max(s, lo), min(e, hi), n, ev) for s, e, n, ev in host
            if min(e, hi) > max(s, lo)]
    by_op = self_times(ops)
    by_phase: Dict[str, int] = defaultdict(int)
    unscoped: Dict[str, int] = defaultdict(int)
    for k, ps in by_op.items():
        phase = phase_of.get(k, "unscoped")
        by_phase[phase] += ps
        if phase == "unscoped":
            name = device.event_metadata[k].name.split(" = ", 1)[0] \
                if k in device.event_metadata else str(k)
            unscoped[f"{name} {tf_op.get(k, '')}"] += ps
    busy = union([(s, e) for s, e, _ in ops])
    idle = gaps(busy, lo, hi)
    program = [h for h in host if h[2].startswith(PROGRAM_SPAN_PREFIXES)]
    by_span = label_innermost(idle, [h[:3] for h in program])
    span_ps: Dict[str, int] = defaultdict(int)
    for s, e, name, _ in host:
        span_ps[name] += e - s
    writes = [(s, e) for s, e, name, _ in program if name == "results.write"]
    children = sum(e - s for s, e, name, _ in program
                   if name.startswith("results.") and name != "results.write"
                   and any(ws <= s and e <= we for ws, we in writes))
    counted = [ev for _, _, name, ev in program
               if name == "results.write" and ev is not None]
    order = {p: i for i, p in enumerate(PHASES + ("unscoped",))}
    return {
        "busy_s": sum(e - s for s, e in busy) / 1e12,
        "window_s": (hi - lo) / 1e12,
        "phases_s": {p: by_phase[p] / 1e12
                     for p in sorted(by_phase, key=order.get)},
        "unscoped_ops": [[n, ps / 1e12] for n, ps in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_span": {n: ps / 1e12 for n, ps in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
        "program_spans": {n: ps / 1e12 for n, ps in sorted(span_ps.items())
                          if n.startswith(PROGRAM_SPAN_PREFIXES)},
        "write_children_s": children / 1e12,
        "harness_spans": {n: span_ps[n] / 1e12 for n in SPANS
                          if n in span_ps},
        "events": int(sum(counted)) if counted else None,
        "n_ops": len(ops),
        "n_scoped_ops": sum(phase_of.get(k, "unscoped") != "unscoped"
                            for _, _, k in ops),
    }


def coverage(out: Dict[str, object]) -> Optional[Dict[str, float]]:
    """How much of the harness's spans the program's spans account for
    in one reduced trace: the children of ``results.write`` over the
    harness's ``write``, ``fleet.build`` over its ``build``, and the idle
    time inside ``experiment.run`` but in none of its children over the
    window.  None where either side is missing."""
    harness, prog = out["harness_spans"], out["program_spans"]
    if not prog or not harness.get("write") or not harness.get("build"):
        return None
    return {
        "write_children_over_write": out["write_children_s"]
        / harness["write"],
        "build_over_build": prog.get("fleet.build", 0.0) / harness["build"],
        "idle_in_run_only_share": out["idle_by_span"].get(
            "experiment.run", 0.0) / out["window_s"]}


def trace_path(run: Dict) -> Optional[str]:
    """The trace ``bench/run.py``'s traced grid left: the newest
    ``.xplane.pb`` under the cells' trace directories whose size is the
    ``bytes`` the harness read off it.  None in an untraced run."""
    size = (run.get("trace") or {}).get("bytes")
    if size is None:
        return None
    paths = [p for p in glob.glob(os.path.join(
        OUT_ROOT, "*", "trace", "**", "*.xplane.pb"), recursive=True)
        if os.path.getsize(p) == size]
    return max(paths, key=os.path.getmtime) if paths else None


@functools.lru_cache(maxsize=4)
def _reduced(path: str, mtime: float) -> Optional[Dict[str, object]]:
    try:
        return reduce_phases(path)
    except ValueError:          # no device ops: nothing to attribute
        return None


def traced(run: Dict) -> Optional[Dict[str, object]]:
    """``reduce_phases`` of the traced grid of ``run`` (the object the
    harness hands each reader), reduced once for all readers; None where
    there is no such trace."""
    path = trace_path(run)
    return _reduced(path, os.path.getmtime(path)) if path else None


def main(argv: Sequence[str]) -> None:
    out = reduce_phases(argv[0])
    out["coverage"] = coverage(out)
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:])
