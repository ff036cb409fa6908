"""Share of the window spent writing lanes' outputs: the harness's span
around ``FleetResult.write_outputs`` (records, summary, JSON lines), in %."""


def read(run):
    return 100.0 * run["spans"]["write"] / run["window"]["seconds"]
