"""Device launch time per simulated event of the window's ``blocking``
launches: ``FleetResult.launches`` wall seconds (the call to the
compiled program and the copy of its result to the host) over their
events, in microseconds."""


def read(run):
    launches = [ln for ln in run["window"]["launches"]
                if ln["cost_class"] == "blocking"]
    events = sum(ln["events"] for ln in launches)
    if not events:
        return None
    return 1e6 * sum(ln["wall_time_s"] for ln in launches) / events
