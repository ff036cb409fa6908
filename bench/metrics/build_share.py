"""Share of the window spent building lanes: the harness's span around
``FleetRunner.build`` (``SimState.from_workload`` per lane), in %."""


def read(run):
    return 100.0 * run["spans"]["build"] / run["window"]["seconds"]
