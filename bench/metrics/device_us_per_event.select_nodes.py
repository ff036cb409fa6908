"""Device time under the engine's ``select_nodes`` scope per simulated event
of the traced grid: the self time of the ops whose op_name path carries the
scope (every allocator probe's node ordering, cumsum and slot read-back,
nested in ``dispatch`` or ``backfill``), over the events of the lanes the
grid wrote (``phase_reduce.traced``), in microseconds.  Self time and the
window are as ``phase_reduce.reduce_phases`` takes them.  Nothing for a
program without the scope or the count."""
import re

from phase_reduce import read_xspace, self_times, trace_path, traced

SCOPE = "select_nodes"


def scope_self_time_s(path, scope=SCOPE):
    """Self time, in seconds, of the first device's ops in the trace's
    ``window`` whose ``tf_op`` path names ``scope``; None where no op
    does."""
    window, device = [], None
    for plane in read_xspace(path).planes:
        if plane.name.startswith("/host:"):
            ids = {k for k, v in plane.event_metadata.items()
                   if v.name == "window"}
            window += [(ln.timestamp_ns * 1000 + ev.offset_ps,
                        ln.timestamp_ns * 1000 + ev.offset_ps
                        + ev.duration_ps)
                       for ln in plane.lines for ev in ln.events
                       if ev.metadata_id in ids]
        elif device is None and plane.name.startswith("/device:") and any(
                ln.name == "XLA Ops" and ln.events for ln in plane.lines):
            device = plane
    if device is None:
        return None
    tf_stat = {k for k, v in device.stat_metadata.items()
               if v.name == "tf_op"}
    scoped = {k for k, meta in device.event_metadata.items()
              if any(st.metadata_id in tf_stat and scope in re.split(
                  r"[/()]", st.str_value.split(";", 1)[0].rsplit(":", 1)[0])
                  for st in meta.stats)}
    if not scoped:
        return None
    line = next(ln for ln in device.lines if ln.name == "XLA Ops")
    base = line.timestamp_ns * 1000
    ops = [(base + ev.offset_ps, base + ev.offset_ps + ev.duration_ps,
            ev.metadata_id) for ev in line.events]
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
        ops = [(max(s, lo), min(e, hi), k) for s, e, k in ops
               if min(e, hi) > max(s, lo)]
    by_op = self_times(ops)
    return sum(ps for k, ps in by_op.items() if k in scoped) / 1e12


def read(run):
    tr = traced(run)
    if not tr or not tr["events"]:
        return None
    seconds = scope_self_time_s(trace_path(run))
    return None if seconds is None else 1e6 * seconds / tr["events"]
