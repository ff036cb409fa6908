"""Share of the traced grid's device busy time under no engine scope:
the self time of ops whose op_name carries none of the engine's phases
(``phase_reduce.traced``) over busy time, in %.  Nothing where no op
carries a scope (a program without them)."""
from phase_reduce import traced


def read(run):
    tr = traced(run)
    phases = tr["phases_s"] if tr else {}
    busy = sum(phases.values())
    if busy <= 0 or set(phases) <= {"unscoped"}:
        return None
    return 100.0 * phases.get("unscoped", 0.0) / busy
