"""The control of the check: the reference, put in the program's place,
with one departure that a faster engine would be tempted to take.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--control quantum60]

Controls:
- ``quantum60`` (the control): every event handled at the next whole
  minute (``reference.simulate(..., time_quantum_s=60)``), the batching
  of events that would cut the engine's loop trips;
- ``bfloat16``: Best-Fit's node loads rounded to bfloat16, the shortcut
  a faster node sort would take;
- ``float32``: the loads in float32, as the program computes them (the
  reference uses float64): how far the program's own precision is from
  the reference's.

For each seed it draws a run's sample (the same number of lanes per
dispatcher row as a run compares, each on a lane seed drawn from the
seed), runs the control and the reference on every lane at the cell's
own size, and compares them with the harness's own comparison.  One
JSON line per seed gives the summed numbers; the control has to read
above every limit on some number, on every seed.
"""
from __future__ import annotations

import argparse
import json
import math
import random

import reference
import run

CONTROLS = {"quantum60": {"time_quantum_s": 60},
            "bfloat16": {"load_dtype": "bfloat16"},
            "float32": {"load_dtype": "float32"}}


def control_reading(cell: run.Cell, seed: int, control: str) -> dict:
    rng = random.Random(seed)
    per_row = max(1, math.ceil(run.SAMPLE_LANES / len(cell.rows)))
    totals = {"jobs_wrong": 0, "events_wrong": 0, "summary_wrong": 0,
              "jobs_compared": 0}
    for row in cell.rows:
        for _ in range(per_row):
            jobs = list(cell.workload(rng.randrange(2 ** 31)))
            want = reference.simulate(jobs, cell.machine, row)
            got_jobs, got_log, got_summary = reference.simulate(
                jobs, cell.machine, row, **CONTROLS[control])
            got = run.compare(got_jobs, got_log, [got_summary], want)
            for k in totals:
                totals[k] += got[k]
    return totals


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="quantum60", choices=CONTROLS)
    args = ap.parse_args()
    cell = run.Cell(run.find_cell(run.load_spec(), args.workload))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          **control_reading(cell, seed, args.control)}),
              flush=True)


if __name__ == "__main__":
    main()
