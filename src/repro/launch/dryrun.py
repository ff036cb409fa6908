import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture × input shape × mesh) cell this:
  1. builds the jitted step function (train_step / prefill / decode) with
     explicit NamedShardings from the logical-axis rules,
  2. ``.lower(**ShapeDtypeStructs)`` + ``.compile()`` — no allocation,
  3. records ``memory_analysis()`` (per-device fit), ``cost_analysis()``
     (raw) and the scan-corrected HLO walk (FLOPs / bytes / collective
     bytes by kind) from ``hlo_analysis``,
  4. writes one JSON per cell under ``results/dryrun/``.

The 512 placeholder host devices exist ONLY in this process (the env var
above is set before any jax import); tests and benches see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --rules baseline --out results/dryrun
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional, Tuple

import jax

from ..configs import ARCH_IDS, SHAPES, applicable_shapes, get_config
from ..models import build_model
from ..serving.serve_step import make_decode_step, make_prefill_step
from ..sharding import shardings_from_axes, use_rules
from ..training import AdamWConfig, TrainStepConfig, adamw_init, make_train_step
from ..training.optimizer import opt_state_logical_axes
from .hlo_analysis import analyze_hlo_text
from .mesh import chip_peaks, make_production_mesh

# the chip the dry-run's placeholder devices stand for (its roofline
# denominators come from ``mesh.CHIP_PEAKS``)
TARGET_KIND = "TPU v5 lite"

# microbatch defaults per shape kind (activation-memory knob; §Perf)
DEFAULT_MICROBATCHES = {"train": 4, "prefill": 1, "decode": 1}


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference); N active for MoE."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        d = shape.tokens
        return 6.0 * n * d
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch          # decode: 1 token/sample


def build_cell(arch: str, shape_name: str, mesh, rules: str,
               microbatches: Optional[int], smoke: bool = False,
               remat: str = "full", state_dtype: str = "float32",
               moe_group_size: Optional[int] = None,
               kv_cache_dtype: str = ""):
    cfg = get_config(arch, smoke=smoke)
    if moe_group_size:
        cfg = cfg.replace(moe_group_size=moe_group_size)
    if kv_cache_dtype:
        cfg = cfg.replace(kv_cache_dtype=kv_cache_dtype)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    mb = microbatches or DEFAULT_MICROBATCHES[shape.kind]

    paxes = model.param_logical_axes()
    pshapes = model.param_shapes()
    p_sh = shardings_from_axes(paxes, mesh, rules, pshapes)

    if shape.kind == "train":
        ocfg = AdamWConfig(state_dtype=state_dtype)
        tcfg = TrainStepConfig(microbatches=mb, remat=remat)
        oshapes = jax.eval_shape(lambda: adamw_init(pshapes, ocfg))
        o_sh = shardings_from_axes(opt_state_logical_axes(paxes, ocfg),
                                   mesh, rules, oshapes)
        ispecs, iaxes = model.input_specs(shape)
        i_sh = shardings_from_axes(iaxes, mesh, rules, ispecs)
        fn = make_train_step(model, ocfg, tcfg)
        jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, i_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        args = (pshapes, oshapes, ispecs)
    elif shape.kind == "prefill":
        ispecs, iaxes = model.input_specs(shape)
        i_sh = shardings_from_axes(iaxes, mesh, rules, ispecs)
        fn = make_prefill_step(model)
        jitted = jax.jit(fn, in_shardings=(p_sh, i_sh))
        args = (pshapes, ispecs)
    else:  # decode
        ispecs, iaxes = model.input_specs(shape)
        i_sh = shardings_from_axes(iaxes, mesh, rules, ispecs)
        cshapes = model.cache_shapes(shape.global_batch, shape.seq_len)
        c_sh = shardings_from_axes(model.cache_logical_axes(), mesh, rules,
                                   cshapes)
        fn = make_decode_step(model)
        jitted = jax.jit(fn, in_shardings=(p_sh, i_sh["tokens"], c_sh),
                         out_shardings=(None, c_sh), donate_argnums=(2,))
        args = (pshapes, ispecs["tokens"], cshapes)
    return cfg, shape, jitted, args, mb


def run_cell(arch: str, shape_name: str, mesh_kind: str, rules: str,
             microbatches: Optional[int] = None, smoke: bool = False,
             remat: str = "full", state_dtype: str = "float32",
             scan_impl: str = "ref", moe_group_size: Optional[int] = None,
             kv_cache_dtype: str = "", tag: Optional[str] = None) -> Dict:
    if mesh_kind == "debug":            # CI-scale: 8 host devices
        from .mesh import make_debug_mesh
        mesh = make_debug_mesh(4, 2)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    peaks = chip_peaks(TARGET_KIND)
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "device_kind": TARGET_KIND, "chips": int(chips),
                 "rules": tag or rules, "rules_base": rules, "ok": False,
                 "knobs": {"remat": remat, "state_dtype": state_dtype,
                            "scan_impl": scan_impl,
                            "moe_group_size": moe_group_size}}
    prev_kernels = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "stub" if scan_impl == "stub" else "ref"
    t0 = time.time()
    try:
        with use_rules(mesh, rules):
            cfg, shape, jitted, args, mb = build_cell(
                arch, shape_name, mesh, rules, microbatches, smoke,
                remat=remat, state_dtype=state_dtype,
                moe_group_size=moe_group_size,
                kv_cache_dtype=kv_cache_dtype)
            rec["microbatches"] = mb
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

            ma = compiled.memory_analysis()
            per_dev = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            rec["memory"] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "per_device_bytes": int(per_dev),
                "per_device_gib": round(per_dev / 2**30, 3),
                "fits_16gib_hbm": bool(per_dev <= peaks["hbm_bytes"]),
            }
            ca = compiled.cost_analysis() or {}
            rec["cost_analysis_raw"] = {
                "flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
            }
            txt = compiled.as_text()
            model_axis = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
                "model", 1)
            cost = analyze_hlo_text(txt, default_group=model_axis)
            rec["hlo"] = {
                "flops_per_device": cost.flops,
                "bytes_per_device": cost.bytes,
                "collective_bytes": dict(cost.collective_bytes),
                "collective_link_bytes": dict(cost.collective_link_bytes),
                "collective_count": dict(cost.collective_count),
            }
            mf = model_flops(cfg, shape)
            compute_s = cost.flops / peaks["peak_flops_bf16"]
            memory_s = cost.bytes / peaks["hbm_bw"]
            coll_s = (cost.total_collective_link_bytes
                      / peaks["ici_bw_per_link"])
            dominant = max(
                (("compute", compute_s), ("memory", memory_s),
                 ("collective", coll_s)), key=lambda kv: kv[1])[0]
            rec["roofline"] = {
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": coll_s,
                "dominant": dominant,
                "model_flops_global": mf,
                "model_flops_per_chip": mf / chips,
                "useful_flops_ratio": (mf / chips) / cost.flops if cost.flops else 0.0,
                "bound_step_time_s": max(compute_s, memory_s, coll_s),
            }
            rec["ok"] = True
    except Exception as e:  # record the failure, don't kill the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    finally:
        if prev_kernels is None:
            os.environ.pop("REPRO_KERNELS", None)
        else:
            os.environ["REPRO_KERNELS"] = prev_kernels
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--rules", default="baseline")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        cfg = get_config(arch, smoke=args.smoke)
        shapes = (applicable_shapes(cfg) if args.shape == "all"
                  else args.shape.split(","))
        skipped = set(SHAPES) - set(applicable_shapes(cfg))
        for sk in sorted(skipped):
            if args.shape == "all":
                print(f"[skip] {arch} × {sk}: quadratic attention @ 524k "
                      f"(DESIGN.md §Arch-applicability)")
        for shape_name in shapes:
            for mesh_kind in meshes:
                tag = f"{arch}__{shape_name}__{mesh_kind}__{args.rules}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    n_skip += 1
                    continue
                rec = run_cell(arch, shape_name, mesh_kind, args.rules,
                               args.microbatches or None, smoke=args.smoke)
                with open(path, "w") as fh:
                    json.dump(rec, fh, indent=1)
                if rec["ok"]:
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"[ok]   {tag}: compile={rec['compile_s']}s "
                          f"mem/dev={rec['memory']['per_device_gib']}GiB "
                          f"dominant={r['dominant']} "
                          f"bound={r['bound_step_time_s']:.4f}s "
                          f"useful={r['useful_flops_ratio']:.2f}")
                else:
                    n_fail += 1
                    print(f"[FAIL] {tag}: {rec['error']}")
    print(f"done: ok={n_ok} fail={n_fail} skip={n_skip}")


if __name__ == "__main__":
    main()
