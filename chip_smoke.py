"""Chip smoke test: the simulator's main path, once, on TPU.

    python3 chip_smoke.py              # one chip: the phases below
    python3 chip_smoke.py --chips 4    # four chips: the sharded grid only

One chip, three phases, all checked against a reference:

1. Main grid.  The paper's eight Table-2 dispatchers ({FIFO, SJF, LJF,
   EBF} x {FirstFit, BestFit}) x 4 seeds = 32 lanes of 2,048 Seth-like
   jobs (``benchmarks/common.py::seth_jobs``, about two simulated days of
   arrivals) on the Seth machine (120 nodes x 4 cores x 1 GB), through
   ``Experiment.run_simulation``, one experiment per seed.  Every row
   must lower onto the compiled fleet engine, and every lane's event,
   completion and rejection counts, makespan and golden trace (start,
   assigned nodes and state per job) must equal the host ``Simulator``'s
   on the same workload.
2. Kernel on.  ``FleetRunner(use_kernel=True)`` over FIFO-FF and EBF-BF:
   the ``alloc_score_batch`` Pallas kernel compiled into every dispatch
   round; traces must equal the kernel-off lanes of phase 1.
3. Host path.  The vectorized host dispatchers, which launch the
   compiled ``alloc_score_batch`` and ``ebf_shadow`` kernels from the
   host engine, against their numpy twins on the same workload.

With ``--chips 4`` the only phase is the same 32-lane grid sharded by
``FleetRunner`` over four chips, compared lane by lane with the grid on
one, after checking that the compiled launch spreads the lanes over all
four devices.

Where JAX finds no TPU the script exits non-zero and prints no result.
Compiled executables persist in ``JAX_COMPILATION_CACHE_DIR`` when it is
set and in ``<checkout>/.jax_cache`` otherwise.  Lines before the last
report per-launch compile seconds, cache hits and walls; the last line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
N_JOBS = 2048
SEEDS = (0, 1, 2, 3)
HOST_PATH_JOBS = 300
SUMMARY_KEYS = ("events", "completed", "rejected", "sim_end_time")


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(chips: int):
    """The TPU devices, or exit: this script never runs on another
    backend."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) != chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devs)}")
    return devs


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------
def table2():
    """Fresh instances of the paper's eight Table-2 dispatchers."""
    from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                        FirstInFirstOut, LongestJobFirst,
                                        ShortestJobFirst)
    return [s(a()) for s in (FirstInFirstOut, ShortestJobFirst,
                             LongestJobFirst, EasyBackfilling)
            for a in (FirstFit, BestFit)]


def read_trace(path: str):
    """Golden-trace dict ``{id: [start, assigned, state]}`` of a
    ``{name}-output.jsonl`` stream (the ``FleetResult.trace`` format)."""
    trace = {}
    with open(path) as fh:
        for line in fh:
            r = json.loads(line)
            trace[str(r["id"])] = [r["start"], list(r["assigned"]),
                                   r["state"]]
    return trace


def check_equal(label: str, got, want) -> None:
    if got == want:
        return
    if isinstance(got, dict) and isinstance(want, dict):
        job = next(k for k in sorted(set(got) | set(want))
                   if got.get(k) != want.get(k))
        raise AssertionError(f"{label}: job {job}: got {got.get(job)}, "
                             f"reference {want.get(job)}")
    raise AssertionError(f"{label}: got {got}, reference {want}")


def log_launches(label: str, launches) -> None:
    for ln in launches:
        log(f"launch {label} {ln['cost_class']}: lanes={ln['n_sims']} "
            f"events={ln['events']} compile_s={ln['compile_time_s']} "
            f"in_process_cache_hit={ln['cache_hit']} "
            f"wall_s={ln['wall_time_s']}")


def build_sims(n_jobs: int, seeds, scheds_fn):
    """Fleet lanes of ``scheds_fn()`` x ``seeds``, built as
    ``Experiment._run_fleet`` builds them."""
    from benchmarks.common import seth_jobs
    from repro.configs.seth import SYSTEM
    from repro.core.resources import ResourceManager
    from repro.core.simulator import default_job_factory
    from repro.fleet import FleetRunner, dispatch_code

    factory = default_job_factory(ResourceManager(SYSTEM))
    sims = []
    for seed in seeds:
        for sched in scheds_fn():
            sc, ac = dispatch_code(sched)
            sims.append(FleetRunner.build(
                sched.dispatcher_name, list(seth_jobs(n_jobs, seed=seed)),
                SYSTEM, sc, alloc_id=ac, job_factory=factory, seed=seed))
    return sims


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def main_grid(n_jobs: int, seeds, out_dir: str, cache=None):
    """Phase 1; returns ``{(seed, dispatcher): golden trace}``."""
    from benchmarks.common import seth_jobs
    from repro.configs.seth import SYSTEM
    from repro.core.simulator import Simulator
    from repro.experimentation import Experiment

    traces = {}
    for seed in seeds:
        exp = Experiment(f"seth-s{seed}", list(seth_jobs(n_jobs, seed=seed)),
                         SYSTEM, output_dir=out_dir)
        for sched in table2():
            exp.add_dispatcher(sched)
        hits0 = cache.hits if cache else 0
        t0 = time.perf_counter()
        results = exp.run_simulation(produce_plots=False)
        wall = time.perf_counter() - t0
        log_launches(f"seed={seed}", exp.fleet_launches)
        log(f"experiment seed={seed}: wall_s={wall:.3f} "
            f"persistent_cache_hits={(cache.hits if cache else 0) - hits0}")

        for sched in table2():
            name = sched.dispatcher_name
            summ = results[name]["summaries"][0]
            if summ["engine"] != "fleet" or summ["fallback_reason"] is not None:
                raise AssertionError(
                    f"{name} seed={seed} ran on the host: "
                    f"{summ['fallback_reason']}")
            t0 = time.perf_counter()
            host = Simulator(list(seth_jobs(n_jobs, seed=seed)), SYSTEM, sched,
                             output_dir=os.path.join(out_dir, "host"),
                             name=f"{name}-s{seed}")
            host_out = host.start_simulation()
            host_s = time.perf_counter() - t0
            label = f"{name} seed={seed}"
            for key in SUMMARY_KEYS:
                check_equal(f"{label} {key}", summ[key], host.summary[key])
            trace = read_trace(results[name]["output"])
            check_equal(f"{label} trace", trace, read_trace(host_out))
            traces[(seed, name)] = trace
            log(f"lane {label}: events={summ['events']} "
                f"completed={summ['completed']} rejected={summ['rejected']} "
                f"sim_end_time={summ['sim_end_time']} "
                f"host_reference_s={host_s:.3f} equal=True")
    return traces


def kernel_phase(n_jobs: int, seed: int, want):
    """Phase 2; returns the runner so the caller can check its mode."""
    from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                        FirstInFirstOut)
    from repro.fleet import FleetRunner

    sims = build_sims(n_jobs, (seed,), lambda: [
        FirstInFirstOut(FirstFit()), EasyBackfilling(BestFit())])
    runner = FleetRunner(use_kernel=True)
    res = runner.run(sims)
    log_launches(f"kernel-on seed={seed}", res.launches)
    for i, sim in enumerate(sims):
        check_equal(f"kernel-on {sim.name} seed={seed} trace", res.trace(i),
                    want[(seed, sim.name)])
        log(f"lane kernel-on {sim.name} seed={seed}: "
            f"events={int(res.finals[i].n_events)} equal_to_kernel_off=True")
    return runner


def host_path_phase(n_jobs: int, out_dir: str):
    """Phase 3; returns the kernel launches it made, by kernel."""
    from benchmarks.common import seth_jobs
    from repro.configs.seth import SYSTEM
    from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                        FirstInFirstOut)
    from repro.core.dispatchers.vectorized import (VectorizedAllocator,
                                                   VectorizedEasyBackfilling)
    from repro.experimentation import Experiment
    from repro.kernels.counters import launch_stats

    pairs = [
        (FirstInFirstOut(VectorizedAllocator("FF")), FirstInFirstOut(FirstFit())),
        (FirstInFirstOut(VectorizedAllocator("BF")), FirstInFirstOut(BestFit())),
        (VectorizedEasyBackfilling(VectorizedAllocator("FF")),
         EasyBackfilling(FirstFit())),
    ]
    exp = Experiment("host-path", list(seth_jobs(n_jobs, seed=7)), SYSTEM,
                     output_dir=out_dir, use_fleet=False)
    for vec, twin in pairs:
        exp.add_dispatcher(vec)
        exp.add_dispatcher(twin)
    before = launch_stats()
    t0 = time.perf_counter()
    results = exp.run_simulation(produce_plots=False)
    wall = time.perf_counter() - t0
    after = launch_stats()
    launched = {k: after.get(k, 0) - before.get(k, 0)
                for k in ("alloc_score_batch", "ebf_shadow")}
    for kernel, n in launched.items():
        if n == 0:
            raise AssertionError(f"host path launched no {kernel} kernel")
    for vec, twin in pairs:
        v, t = vec.dispatcher_name, twin.dispatcher_name
        vs, ts = results[v]["summaries"][0], results[t]["summaries"][0]
        check_equal(f"{v} makespan vs {t}", vs["sim_end_time"],
                    ts["sim_end_time"])
        check_equal(f"{v} trace vs {t}", read_trace(results[v]["output"]),
                    read_trace(results[t]["output"]))
        log(f"host-path {v}: makespan={vs['sim_end_time']} equal_to={t} "
            f"kernel_launches_per_event="
            f"{vs['kernel_launches_per_event']:.3f}")
    log(f"host-path: jobs={n_jobs} wall_s={wall:.3f} launches={launched}")
    return launched


def four_chip_phase(n_jobs: int, seeds):
    """The grid sharded over every device vs the grid on one device;
    returns the number of devices the sharded launches spread over."""
    import jax
    from repro.fleet import FleetRunner
    from repro.launch.mesh import fleet_mesh

    sims = build_sims(n_jobs, seeds, table2)
    n_dev = len(jax.devices())
    runs = {}
    for label, mesh in ((f"{n_dev}-device", fleet_mesh()),
                        ("1-device", fleet_mesh(1))):
        t0 = time.perf_counter()
        res = FleetRunner(mesh=mesh).run(sims)
        log_launches(label, res.launches)
        log(f"grid {label}: lanes={len(sims)} n_devices={res.n_devices} "
            f"wall_s={time.perf_counter() - t0:.3f}")
        runs[label] = res
    sharded, single = runs[f"{n_dev}-device"], runs["1-device"]
    if sharded.n_devices != n_dev:
        raise AssertionError(f"sharded run used {sharded.n_devices} devices")
    for i, sim in enumerate(sims):
        check_equal(f"{sim.name} seed={sim.seed} sharded vs 1-device trace",
                    sharded.trace(i), single.trace(i))
    # where the compiled sharded launches place their inputs: every
    # SimState field split over all devices on the lane axis, none held
    # whole by one device.  Zero-size fields (no failure schedule, no
    # telemetry buffer) hold no lanes, and a field the program never
    # reads is pruned (no sharding): both are skipped.
    spread = 0
    for key, compiled in FleetRunner._compile_cache.items():
        mesh_key = key[-2]
        if mesh_key is None or len(mesh_key) != n_dev:
            continue
        state_sh = compiled.input_shardings[0][0]
        for field, sh in zip(state_sh._fields, state_sh):
            if sh is None or getattr(sims[0].state, field).size == 0:
                log(f"  {field}: {'pruned' if sh is None else 'zero-size'}")
                continue
            if ({d.id for d in sh.device_set} != set(mesh_key)
                    or sh.is_fully_replicated):
                raise AssertionError(f"{field} not spread over {n_dev} "
                                     f"devices: {sh}")
        spread += 1
        log(f"sharded launch batch={key[0]}: every field split over "
            f"devices {sorted(mesh_key)}, {key[0] // n_dev} lanes each, "
            f"argument_bytes_per_device="
            f"{compiled.memory_analysis().argument_size_in_bytes}")
    if spread == 0:
        raise AssertionError("no sharded launch was compiled")
    log(f"grid: {len(sims)} lanes equal on {n_dev} devices and on 1")
    return n_dev


# ----------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the grid sharded over four chips")
    args = ap.parse_args()
    devs = tpu_devices(args.chips)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.fleet.engine import default_interpret
    from repro.kernels.ops import kernel_mode
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    t_start = time.perf_counter()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    log(f"kernel_mode={kernel_mode()} fleet_interpret={default_interpret()} "
        f"compile_cache={cache.path}")
    if kernel_mode() != "tpu" or default_interpret():
        raise AssertionError("kernels would not run compiled on the TPU")

    if args.chips == 4:
        four_chip_phase(N_JOBS, SEEDS)
    else:
        out_dir = os.path.join(ROOT, "results", "chip_smoke")
        shutil.rmtree(out_dir, ignore_errors=True)
        traces = main_grid(N_JOBS, SEEDS, out_dir, cache)
        runner = kernel_phase(N_JOBS, SEEDS[0], traces)
        if runner.interpret:
            raise AssertionError("kernel-on launch ran the Pallas interpreter")
        host_path_phase(HOST_PATH_JOBS, out_dir)
    log(f"total_wall_s={time.perf_counter() - t_start:.3f} "
        f"persistent_cache_hits={cache.hits} "
        f"persistent_cache_writes={cache.writes}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
