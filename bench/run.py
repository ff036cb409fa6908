"""The chip benchmark: experiment grids through ``Experiment``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --list        # the cells, found by name, and their files

Everything is found by name from ``BENCHMARK.json``: a cell names its
configuration (the file the entry gives, under ``bench/configs/``) and
its traffic (``bench/traffic/<traffic>.json``); the traffic names its
generator (``bench/generators/<generator>.py``); each per-layer metric
is read by ``bench/metrics/<metric>.py``.  Adding a cell, a mix, a
generator or a metric adds files and entries and edits none.

One run is one process:

1. set-up: load, turn on JAX's persistent compilation cache at
   ``<checkout>/.jax_cache``, and run one whole grid, which compiles the
   cell's launches or reads them from the cache.  ``setup_s`` runs from
   the start of this script to the end of that grid;
2. the window: for ``--seconds``, grids back to back.  Each grid is a
   fresh ``Experiment(...).run_simulation(produce_plots=False)`` over
   the traffic's dispatcher rows x ``seeds_per_grid`` seeds, with the
   seeds drawn from ``--seed``, writing into one output directory that
   the next grid overwrites.  A grid that starts before the deadline
   runs to its end and counts.  ``events_per_s`` is every simulated
   event of those grids over the whole time they took;
3. with ``--trace 1``, the window runs with the harness's spans around
   ``FleetRunner.build``, ``FleetRunner._launch`` and
   ``FleetResult.write_outputs``; then one more grid runs under the
   profiler, and one telemetry-on grid of the same seeds gives the
   engine's phase counters.  The per-layer metrics come from these;
4. the check: a sample of the window's lanes, drawn from the seed (a
   fixed number per dispatcher row), kept as ``Experiment`` wrote them.
   After the window, and after the device's peak memory is read, each
   is run again through the plain reference (``bench/reference.py``)
   and compared job by job, event by event and count by count.  Every
   lane must have run on the fleet, and nothing may compile inside the
   window.  Each number and its limit is printed last on standard error
   and under ``checks`` in the result line.

The last line of standard output is the result, one JSON object.  The
script exits non-zero and prints no result where JAX finds no TPU or
another number of chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_ROOT = os.path.join(ROOT, "results", "bench")
# lanes of the window compared with the reference, per run
SAMPLE_LANES = 8
# telemetry stride of the counter grid: the counters are exact at any
# stride, so a coarse one keeps its sample buffer small
TELEMETRY_STRIDE = 4096

for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# the benchmark's own files
# ----------------------------------------------------------------------
def load_spec() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(spec: Dict, name: str) -> Dict:
    """The cell ``name`` with its configuration, traffic and metrics
    loaded from their files."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no cell {name!r} in BENCHMARK.json "
                         f"(cells: {', '.join(cells)})")
    cell = dict(cells[name])
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as fh:
        cell["machine_config"] = json.load(fh)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as fh:
        cell["traffic_mix"] = json.load(fh)
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def list_cells(spec: Dict) -> None:
    """The dry listing: each cell and the files it is found by."""
    for w in spec["workloads"]:
        cell = find_cell(spec, w["name"])
        mix = cell["traffic_mix"]
        files = [next(c["file"] for c in spec["configs"]
                      if c["name"] == w["config"]),
                 f"bench/traffic/{w['traffic']}.json",
                 f"bench/generators/{mix['generator']}.py"]
        files += [f"bench/metrics/{m['name']}.py" for m in cell["per_layer"]]
        missing = [f for f in files if not os.path.exists(
            os.path.join(ROOT, f))]
        print(f"{w['name']}: chips={w['chips']} rows={len(mix['rows'])} "
              f"seeds_per_grid={mix['seeds_per_grid']} "
              f"jobs={cell['machine_config']['jobs']} files={files}"
              + (f" MISSING={missing}" if missing else ""))


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------
def make_dispatcher(row: str):
    from repro.core import dispatchers as d

    sched, alloc = row.split("-")
    scheds = {"FIFO": d.FirstInFirstOut, "SJF": d.ShortestJobFirst,
              "LJF": d.LongestJobFirst, "EBF": d.EasyBackfilling}
    allocs = {"FF": d.FirstFit, "BF": d.BestFit}
    return scheds[sched](allocs[alloc]())


class Cell:
    """One cell's grids: the workload of a grid, the grid itself, and
    the lanes it runs."""

    def __init__(self, cell: Dict, jobs: Optional[int] = None,
                 seeds_per_grid: Optional[int] = None) -> None:
        self.name = cell["name"]
        cfg, mix = cell["machine_config"], cell["traffic_mix"]
        self.machine = cfg["machine"]
        self.cores = max(g["core"] for g in self.machine["groups"].values())
        self.jobs = jobs or cfg["jobs"]
        self.rows = list(mix["rows"])
        self.seeds = seeds_per_grid or mix["seeds_per_grid"]
        self.params = mix["params"]
        self.gen = load_module(os.path.join(
            BENCH, "generators", mix["generator"] + ".py"))
        self.out_dir = os.path.join(OUT_ROOT, self.name)

    def workload(self, seed: int):
        return self.gen.workload(self.jobs, seed, self.cores, self.params)

    def lane_file(self, row: str, rep: int, kind: str) -> str:
        name = f"{row}-r{rep}" if self.seeds > 1 else row
        return os.path.join(self.out_dir, "grid", f"{name}-{kind}.jsonl")

    def run_grid(self, base_seed: int, **sim_kwargs):
        """One ``Experiment`` grid; returns ``(experiment, results)``."""
        from repro.experimentation import Experiment

        exp = Experiment("grid", self.workload(base_seed), self.machine,
                         output_dir=self.out_dir, repeats=self.seeds,
                         **sim_kwargs)
        for row in self.rows:
            exp.add_dispatcher(make_dispatcher(row))
        return exp, exp.run_simulation(produce_plots=False)


# ----------------------------------------------------------------------
# spans and compile counting
# ----------------------------------------------------------------------
class Spans:
    """Seconds spent in the harness's spans, which wrap the program's
    layers from outside; each span is also a profiler annotation."""

    def __init__(self) -> None:
        self.seconds = {"build": 0.0, "launch": 0.0, "write": 0.0}
        self._saved = []

    def _wrap(self, name: str, fn):
        import jax

        seconds = self.seconds

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(*args, **kwargs)
            seconds[name] += time.perf_counter() - t0
            return out
        return wrapped

    def install(self) -> None:
        from repro.fleet.runner import FleetResult, FleetRunner

        build = FleetRunner.__dict__["build"]
        launch = FleetRunner.__dict__["_launch"]
        write = FleetResult.__dict__["write_outputs"]
        self._saved = [(FleetRunner, "build", build),
                       (FleetRunner, "_launch", launch),
                       (FleetResult, "write_outputs", write)]
        FleetRunner.build = staticmethod(self._wrap("build", build.__func__))
        FleetRunner._launch = self._wrap("launch", launch)
        FleetResult.write_outputs = self._wrap("write", write)

    def remove(self) -> None:
        for owner, attr, fn in self._saved:
            setattr(owner, attr, fn)
        self._saved = []


class Compiles:
    """Backend compiles and persistent-cache hits in this process."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def enable_cache() -> None:
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no eviction: with it on, an entry written without eviction (another
    # machine's, in a copied checkout) makes every later write fail
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
class Sample:
    """Lanes of the window kept for the check: a reservoir of
    ``per_row`` lanes for each dispatcher row, drawn from the seed.  A
    kept lane's two output files are moved out of the grid directory
    before the next grid overwrites them."""

    def __init__(self, cell: Cell, rng: random.Random, per_row: int) -> None:
        self.cell, self.rng, self.per_row = cell, rng, per_row
        self.seen = {row: 0 for row in cell.rows}
        self.kept: Dict[str, List[Optional[Dict]]] = {
            row: [None] * per_row for row in cell.rows}
        self.dir = os.path.join(cell.out_dir, "kept")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)

    def offer(self, results: Dict) -> None:
        for row in self.cell.rows:
            for rep, summary in enumerate(results[row]["summaries"]):
                n = self.seen[row]
                self.seen[row] += 1
                slot = n if n < self.per_row else self.rng.randrange(n + 1)
                if slot >= self.per_row:
                    continue
                lane = {"row": row, "seed": summary.get("seed"),
                        "summary": summary}
                for kind in ("output", "bench"):
                    dst = os.path.join(self.dir, f"{row}-{slot}-{kind}.jsonl")
                    os.replace(self.cell.lane_file(row, rep, kind), dst)
                    lane[kind] = dst
                self.kept[row][slot] = lane

    def lanes(self) -> List[Dict]:
        return [lane for row in self.cell.rows for lane in self.kept[row]
                if lane is not None]


def run_window(cell: Cell, rng: random.Random, seconds: float,
               sample: Optional[Sample], compiles: Compiles) -> Dict:
    """Grids back to back for ``seconds``; returns what they did."""
    events = grids = lanes = host_lanes = 0
    launches: List[Dict] = []
    compiles0 = compiles.compiles
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        exp, results = cell.run_grid(rng.randrange(2 ** 31))
        grids += 1
        launches += exp.fleet_launches
        for row in cell.rows:
            for summary in results[row]["summaries"]:
                lanes += 1
                events += int(summary["events"])
                host_lanes += summary.get("engine") != "fleet"
        if sample is not None:
            sample.offer(results)
    elapsed = time.perf_counter() - t0
    return {"seconds": elapsed, "events": events, "grids": grids,
            "lanes": lanes, "host_lanes": host_lanes, "launches": launches,
            "compiles": compiles.compiles - compiles0,
            "launch_compiles": sum(not ln["cache_hit"] for ln in launches)}


# ----------------------------------------------------------------------
# the check
# ----------------------------------------------------------------------
def read_jsonl(path: str) -> List[Dict]:
    with open(path) as fh:
        return [json.loads(line) for line in fh]


SUMMARY_KEYS = ("events", "submitted", "completed", "rejected",
                "sim_end_time")


def compare(got_jobs: Dict[str, Dict], got_log: List, got_summaries,
            want) -> Dict[str, int]:
    """Mismatches of one lane's records, event log and summaries against
    the reference's ``(records, log, summary)``."""
    want_jobs, want_log, want_summary = want
    ids = set(want_jobs) | set(got_jobs)
    return {
        "jobs_wrong": sum(got_jobs.get(i) != want_jobs.get(i) for i in ids),
        "events_wrong": sum(tuple(a) != tuple(b)
                            for a, b in zip(got_log, want_log))
        + abs(len(got_log) - len(want_log)),
        "summary_wrong": sum(s.get(k) != want_summary[k]
                             for s in got_summaries for k in SUMMARY_KEYS),
        "jobs_compared": len(ids)}


def compare_lane(cell: Cell, lane: Dict, reference) -> Dict[str, int]:
    """Mismatches of one kept lane, as ``Experiment`` wrote it and
    summarized it, against the plain reference on the lane's seed."""
    want = reference.simulate(list(cell.workload(lane["seed"])),
                              cell.machine, lane["row"])
    got_jobs = {str(r["id"]): r for r in read_jsonl(lane["output"])}
    bench = read_jsonl(lane["bench"])
    got_log = [(e["t"], e["queue"], e["running"]) for e in bench
               if "summary" not in e]
    written = [e["summary"] for e in bench if "summary" in e]
    return compare(got_jobs, got_log, [lane["summary"]] + written[:1], want)


def check(cell: Cell, lanes: List[Dict], window: Dict, reference) -> Dict:
    t0 = time.perf_counter()
    totals = {"jobs_wrong": 0, "events_wrong": 0, "summary_wrong": 0,
              "jobs_compared": 0}
    bad_lanes = 0
    for lane in lanes:
        got = compare_lane(cell, lane, reference)
        bad_lanes += any(got[k] for k in ("jobs_wrong", "events_wrong",
                                          "summary_wrong"))
        for k in totals:
            totals[k] += got[k]
        log(f"check {lane['row']} seed={lane['seed']}: "
            + " ".join(f"{k}={v}" for k, v in got.items()))
    checks = {
        "jobs_wrong": [totals["jobs_wrong"], 0],
        "events_wrong": [totals["events_wrong"], 0],
        "summary_wrong": [totals["summary_wrong"], 0],
        "host_lanes": [window["host_lanes"], 0],
        "window_compiles": [window["compiles"] + window["launch_compiles"], 0],
        "lanes_unchecked": [len(cell.rows) - len({ln["row"] for ln in lanes}),
                            0],
    }
    return {"checks": checks, "bad_lanes": bad_lanes,
            "lanes_compared": len(lanes),
            "jobs_compared": totals["jobs_compared"],
            "seconds": time.perf_counter() - t0}


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def device_info(devices) -> Dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def profile_options():
    """Host annotations and device ops; no Python function events."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def traced_grid(cell: Cell, rng: random.Random) -> Dict:
    """One grid under the profiler, reduced to device busy time, top
    operations and idle gaps by host span."""
    import glob

    import jax
    from trace_reduce import reduce_trace

    trace_dir = os.path.join(cell.out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    seed = rng.randrange(2 ** 31)
    with jax.profiler.trace(trace_dir, profiler_options=profile_options()):
        with jax.profiler.TraceAnnotation("window"):
            cell.run_grid(seed)
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    t0 = time.perf_counter()
    out = reduce_trace(path)
    out["reduce_s"] = time.perf_counter() - t0
    out["bytes"] = os.path.getsize(path)
    return out


def telemetry_lanes(cell: Cell, base_seed: int) -> List[Dict]:
    """Phase counters of one telemetry-on grid of the given seeds."""
    _, results = cell.run_grid(base_seed, telemetry_stride=TELEMETRY_STRIDE)
    return [dict(s, row=row) for row in cell.rows
            for s in results[row]["summaries"]]


def run_cell(cell_spec: Dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, jobs: Optional[int] = None,
             seeds_per_grid: Optional[int] = None) -> Dict:
    """One run of a cell; returns the result line's object.  The
    arguments after ``trace`` exist for the benchmark's own tests, which
    drive a run at a small size off the chip."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    import reference
    import repro  # noqa: F401  (the system under test, from the checkout)

    devices = jax.devices()
    chips = int(cell_spec["chips"])
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if require_tpu and len(devices) != chips:
        raise SystemExit(f"bench: cell {cell_spec['name']} asks for {chips} "
                         f"chips, JAX sees {len(devices)}; nothing was run")
    enable_cache()
    compiles = Compiles()
    cell = Cell(cell_spec, jobs=jobs, seeds_per_grid=seeds_per_grid)
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    rng = random.Random(seed)
    warm_exp, _ = cell.run_grid(rng.randrange(2 ** 31))
    warmup_launches = warm_exp.fleet_launches
    setup_s = time.perf_counter() - T_START
    log(f"setup: {setup_s:.3f} s, compiles={compiles.compiles} "
        f"persistent_cache_hits={compiles.cache_hits} "
        f"launches={warmup_launches}")

    spans = Spans()
    if trace:
        spans.install()
    per_row = max(1, math.ceil(SAMPLE_LANES / len(cell.rows)))
    sample = Sample(cell, random.Random(rng.randrange(2 ** 31)), per_row)
    try:
        window = run_window(cell, rng, seconds, sample, compiles)
        log(f"window: {window['grids']} grids, {window['lanes']} lanes, "
            f"{window['events']} events in {window['seconds']:.3f} s; "
            f"compiles={window['compiles']} host_lanes={window['host_lanes']}")
        extra: Dict = {}
        if trace:
            span_s = dict(spans.seconds)
            extra["trace"] = traced_grid(cell, rng)
            extra["telemetry"] = telemetry_lanes(cell, rng.randrange(2 ** 31))
    finally:
        spans.remove()
    device = device_info(devices[:chips] if require_tpu else devices[:1])
    gc.collect()

    result_check = check(cell, sample.lanes(), window, reference)
    checks = result_check["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    metrics: Dict[str, Dict] = {}
    if not trace:
        values = {"events_per_s": window["events"] / window["seconds"],
                  "setup_s": setup_s}
        for m in cell_spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        run = {"window": window, "spans": span_s,
               "warmup_launches": warmup_launches,
               "telemetry": extra["telemetry"], "trace": extra["trace"]}
        for m in cell_spec["per_layer"]:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        tr = extra["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    out = {"correct": correct, "attempted": window["lanes"],
           "failed": window["host_lanes"] + result_check["bad_lanes"],
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": extra["trace"]["device_ops"],
                            "idle_gaps": extra["trace"]["idle_gaps"]}
        log(f"trace: {json.dumps({k: v for k, v in extra['trace'].items()})}")
    log(f"check: {result_check['lanes_compared']} lanes, "
        f"{result_check['jobs_compared']} jobs compared in "
        f"{result_check['seconds']:.3f} s")
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"{k} {v} limit {lim}")
    return out


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true",
                    help="list the cells and the files each is found by")
    args = ap.parse_args(argv)
    spec = load_spec()
    if args.list:
        list_cells(spec)
        return
    if not args.workload:
        ap.error("--workload is required")
    seconds = args.seconds if args.seconds is not None \
        else spec["run_seconds"]
    out = run_cell(find_cell(spec, args.workload), args.seed, seconds,
                   bool(args.trace))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
