"""Backfill candidates that could not start, per simulated event, over
the EBF lanes of the telemetry-on grid that follows the window (the
engine's ``misfit_skips`` counter over the lanes' events)."""


def read(run):
    lanes = [s for s in run["telemetry"] if s["row"].startswith("EBF-")]
    events = sum(s["events"] for s in lanes)
    if not events:
        return None
    skips = sum(s["telemetry"]["phase_counters"]["misfit_skips"]
                for s in lanes)
    return skips / events
