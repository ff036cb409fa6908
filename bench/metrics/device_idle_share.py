"""Share of the traced grid in which no operation ran on the device:
1 - (union of device-op intervals) / window, from the profiler trace
(``bench/trace_reduce.py``), in %."""


def read(run):
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
