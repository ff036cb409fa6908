"""Share of the traced grid spent turning lanes' final device arrays into job
records (``FleetResult.records``): the program's
``results.records`` spans on the trace's host plane over the traced grid
(``phase_reduce.traced``), in %.  Nothing for a program without the
span."""
from phase_reduce import traced


def read(run):
    tr = traced(run)
    if not tr or "results.records" not in tr["program_spans"]:
        return None
    return 100.0 * tr["program_spans"]["results.records"] / tr["window_s"]
