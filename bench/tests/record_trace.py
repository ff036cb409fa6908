"""Record the small trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb.gz

On a TPU: one seth.table2 grid cut to 1 seed x 16 jobs per lane, with
the harness's spans, under the profiler, inside a ``window`` annotation;
the ``.xplane.pb`` is written, gzipped, to the path given.
"""
from __future__ import annotations

import glob
import gzip
import os
import random
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402


def main() -> None:
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: JAX found no TPU; nothing was run")
    run.enable_cache()
    cell = run.Cell(run.find_cell(run.load_spec(), "seth.table2"),
                    jobs=16, seeds_per_grid=1)
    rng = random.Random(3)
    cell.run_grid(rng.randrange(2 ** 31))
    spans = run.Spans()
    spans.install()
    trace_dir = os.path.join(cell.out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(trace_dir,
                            profiler_options=run.profile_options()):
        with jax.profiler.TraceAnnotation("window"):
            cell.run_grid(rng.randrange(2 ** 31))
    spans.remove()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as src, gzip.open(sys.argv[1], "wb") as dst:
        shutil.copyfileobj(src, dst)
    print(sys.argv[1], os.path.getsize(sys.argv[1]), spans.seconds)


if __name__ == "__main__":
    main()
