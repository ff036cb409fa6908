"""Device time under the engine's ``backfill`` scope per simulated event of
the traced grid: the self time of the ops whose op_name carries the scope,
over the events of the lanes the grid wrote (the ``events`` of its
``results.write`` spans; ``phase_reduce.traced``), in microseconds.
Nothing for a program without the scope or the count."""
from phase_reduce import traced


def read(run):
    tr = traced(run)
    if not tr or "backfill" not in tr["phases_s"] or not tr["events"]:
        return None
    return 1e6 * tr["phases_s"]["backfill"] / tr["events"]
