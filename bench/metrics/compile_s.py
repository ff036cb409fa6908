"""Compile seconds of the set-up grid: the sum of ``compile_time_s`` over
its launches (``FleetResult.launches``), a persistent-cache read where the
cache holds the program."""


def read(run):
    launches = run["warmup_launches"]
    if not launches:
        return None
    return sum(ln["compile_time_s"] for ln in launches)
