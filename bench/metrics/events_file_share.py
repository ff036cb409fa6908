"""Share of the traced grid spent building, encoding and writing the lanes'
event logs (``{name}-bench.jsonl``): the program's
``results.events_file`` spans on the trace's host plane over the traced grid
(``phase_reduce.traced``), in %.  Nothing for a program without the
span."""
from phase_reduce import traced


def read(run):
    tr = traced(run)
    if not tr or "results.events_file" not in tr["program_spans"]:
        return None
    return 100.0 * tr["program_spans"]["results.events_file"] / tr["window_s"]
