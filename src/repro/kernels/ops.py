"""Public jit'd wrappers for the kernel layer.

Dispatch policy: the Pallas path runs compiled on a TPU backend
(``interpret=False``).  On any other backend — e.g. the CPU-hosted
multi-pod dry-run — the ``ref.py`` oracles run instead, whose HLO is what
XLA:TPU would see anyway for these memory-bound ops.
``REPRO_KERNELS=interpret|ref|stub`` overrides that off the TPU (tests run
the Pallas interpreter on the CPU); on a TPU backend any value but
``tpu`` is refused, so a setting can never hide the device.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import ref
from .alloc_score import alloc_score_batch_pallas, alloc_score_pallas
from .ebf_shadow import ebf_shadow_pallas
from .selective_scan import selective_scan_pallas


def kernel_mode() -> str:
    """``tpu`` (compiled Pallas), ``interpret``, ``ref`` or ``stub``."""
    forced = os.environ.get("REPRO_KERNELS")
    if jax.default_backend() == "tpu":
        if forced not in (None, "", "tpu"):
            raise RuntimeError(
                f"REPRO_KERNELS={forced} would run the kernels off the "
                f"TPU this process holds; unset it to run them compiled")
        return "tpu"
    if forced in ("interpret", "ref", "tpu", "stub"):
        return forced
    return "ref"


# ----------------------------------------------------------------------
# Launch accounting (see counters.py).  Every public wrapper below counts
# as ONE launch per call (a jit'd ref call stands in for the kernel on
# non-TPU backends, so it costs a dispatch all the same).
# ``DispatchPlan.stats`` snapshots this to prove the batched path is O(1)
# launches per dispatch event.
# ----------------------------------------------------------------------
from .counters import launch_count, launch_stats, record as _record


def _scan_traffic_stub(u, delta, A, B, C, D):
    """HBM-traffic-equivalent stand-in for the Pallas selective-scan.

    Used ONLY for dry-run lowering (REPRO_KERNELS=stub): one streaming
    pass over u/delta/B/C -> y, mirroring the kernel's BlockSpec-implied
    HBM traffic (the SSM state lives in VMEM scratch and never touches
    HBM — the whole point of the kernel, DESIGN.md §2).  The recurrence's
    FLOPs (~Di·S·10 per token, <1% of the block matmuls) are intentionally
    approximated; numerics are NOT equivalent — never use outside lowering.
    """
    import jax.numpy as jnp
    mix = (B * C).sum(-1)[..., None]                         # [Bt, L, 1]
    y = u * jax.nn.silu(delta) + u * mix + D[None, None, :]
    h_last = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]),
                       jnp.float32) + A.sum() * 0.0
    return y.astype(jnp.float32), h_last


def alloc_score(avail, capacity, req):
    """(fit int32[N], score f32[N]) for one job request (FF/BF inner loop)."""
    _record("alloc_score")
    mode = kernel_mode()
    if mode == "ref":
        return jax.jit(ref.alloc_score_ref)(avail, capacity, req)
    return alloc_score_pallas(avail, capacity, req,
                              interpret=(mode == "interpret"))


def alloc_score_batch(avail, capacity, req):
    """(fit int32[J, N], score f32[J, N]) for the whole queue in ONE
    launch (``DispatchContext.req`` × availability — the batched dispatch
    path's only kernel).

    The job axis is padded to the next power of two (>= 8) before the
    jit'd implementation: queue depth changes at every dispatch event,
    and bucketing keeps the jit/lowering cache to O(log J) entries
    instead of one per distinct depth.  Pad and slice happen on the host
    (numpy) — doing them as eager jnp ops would compile a fresh tiny
    executable per distinct J, which is exactly the churn the bucket
    avoids.  Zero request rows fit everywhere and are sliced off before
    returning (as numpy arrays; the greedy commit is host-side anyway).
    """
    import numpy as np

    _record("alloc_score_batch")
    mode = kernel_mode()
    req = np.asarray(req)
    j = req.shape[0]
    j_bucket = max(8, 1 << max(j - 1, 0).bit_length())
    if j_bucket != j:
        req = np.concatenate(
            [req, np.zeros((j_bucket - j, req.shape[1]), dtype=req.dtype)])
    if mode == "ref":
        fit, score = jax.jit(ref.alloc_score_batch_ref)(avail, capacity, req)
    else:
        fit, score = alloc_score_batch_pallas(
            avail, capacity, req, interpret=(mode == "interpret"))
    return np.asarray(fit)[:j], np.asarray(score)[:j]


def ebf_shadow_fits(avail, deltas, req):
    """fits int32[M]: fitting-node count per release prefix (EBF shadow).

    The prefix axis is padded on the host to the next power of two (>= 8)
    with zero deltas, which repeat the last prefix's count and are sliced
    off: every blocked head has its own number of distinct release
    times, and without the bucket each would compile its own kernel."""
    import numpy as np

    _record("ebf_shadow")
    mode = kernel_mode()
    deltas = np.asarray(deltas)
    m = deltas.shape[0]
    m_bucket = max(8, 1 << max(m - 1, 0).bit_length())
    if m_bucket != m:
        deltas = np.concatenate([deltas, np.zeros(
            (m_bucket - m,) + deltas.shape[1:], dtype=deltas.dtype)])
    if mode == "ref":
        fits = jax.jit(ref.ebf_shadow_ref)(avail, deltas, req)
    else:
        fits = ebf_shadow_pallas(avail, deltas, req,
                                 interpret=(mode == "interpret"))
    return np.asarray(fits)[:m]


def selective_scan(u, delta, A, B, C, D, chunk: int = 128):
    """Mamba-1 selective scan: (y, h_last)."""
    _record("selective_scan")
    mode = kernel_mode()
    if mode == "stub":
        return _scan_traffic_stub(u, delta, A, B, C, D)
    if mode == "ref":
        return ref.selective_scan_ref(u, delta, A, B, C, D)
    L, di = u.shape[1], u.shape[2]
    chunk = min(chunk, L)
    while chunk > 4 and L % chunk:
        chunk //= 2
    block_d = 512
    while block_d > 4 and di % block_d:
        block_d //= 2
    if L % chunk or di % block_d:      # irregular shapes: oracle path
        return ref.selective_scan_ref(u, delta, A, B, C, D)
    return _scan_with_ref_grad(u, delta, A, B, C, D, chunk, block_d,
                               interpret=(mode == "interpret"))


def _scan_with_ref_grad(u, delta, A, B, C, D, chunk, block_d, interpret):
    """Pallas forward + ref-oracle backward (pallas_call has no built-in
    AD; a production deployment would pair this with a handwritten
    backward kernel — the ref VJP is the correctness-preserving default)."""

    @jax.custom_vjp
    def f(u, delta, A, B, C, D):
        return selective_scan_pallas(u, delta, A, B, C, D, chunk=chunk,
                                     block_d=block_d, interpret=interpret)

    def fwd(u, delta, A, B, C, D):
        out = selective_scan_pallas(u, delta, A, B, C, D, chunk=chunk,
                                    block_d=block_d, interpret=interpret)
        return out, (u, delta, A, B, C, D)

    def bwd(res, ct):
        _, vjp = jax.vjp(ref.selective_scan_ref, *res)
        return vjp(ct)

    f.defvjp(fwd, bwd)
    return f(u, delta, A, B, C, D)
