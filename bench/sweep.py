"""Seed sweep of one cell: how long a grid takes at each seed count.

    python3 bench/sweep.py --workload <cell> --seeds 4,8,16 [--grids 3] [--jobs 4096]

For each seed count, in one process: one grid to compile (or read the
cache), then ``--grids`` timed grids; ``--jobs`` sets the jobs per lane
in place of the configuration's.  Prints one JSON line per count:
lanes, seconds per grid (each), events per grid and events/s.  The
chosen ``seeds_per_grid`` goes into the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import random
import time

import run


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="4,8,16")
    ap.add_argument("--grids", type=int, default=3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--jobs", type=int, default=None)
    args = ap.parse_args()
    spec = run.load_spec()
    cell_spec = run.find_cell(spec, args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: JAX found no TPU; nothing was run")
    run.enable_cache()
    rng = random.Random(args.seed)
    for n in (int(s) for s in args.seeds.split(",")):
        cell = run.Cell(cell_spec, jobs=args.jobs, seeds_per_grid=n)
        t0 = time.perf_counter()
        exp, _ = cell.run_grid(rng.randrange(2 ** 31))
        first = time.perf_counter() - t0
        walls, events, launches = [], [], []
        for _ in range(args.grids):
            t0 = time.perf_counter()
            exp, results = cell.run_grid(rng.randrange(2 ** 31))
            walls.append(time.perf_counter() - t0)
            events.append(sum(s["events"] for r in results.values()
                              for s in r["summaries"]))
            launches.append([(ln["cost_class"], ln["wall_time_s"])
                             for ln in exp.fleet_launches])
        print(json.dumps({
            "workload": args.workload, "seeds_per_grid": n,
            "jobs": cell.jobs,
            "lanes": n * len(cell.rows), "first_grid_s": first,
            "grid_s": walls, "events": events,
            "events_per_s": sum(events) / sum(walls),
            "launch_walls": launches}), flush=True)


if __name__ == "__main__":
    main()
