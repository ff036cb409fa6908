"""Share of the traced grid spent encoding and writing the lanes' job records
(``{name}-output.jsonl``): the program's
``results.jobs_file`` spans on the trace's host plane over the traced grid
(``phase_reduce.traced``), in %.  Nothing for a program without the
span."""
from phase_reduce import traced


def read(run):
    tr = traced(run)
    if not tr or "results.jobs_file" not in tr["program_spans"]:
        return None
    return 100.0 * tr["program_spans"]["results.jobs_file"] / tr["window_s"]
