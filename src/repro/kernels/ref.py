"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantic ground truth: each kernel's test sweeps shapes and
dtypes and asserts allclose against these functions.  They are also the
portable fallback used when lowering for a non-TPU backend (e.g. the
CPU-hosted multi-pod dry-run), so they must be jit-friendly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------------
# alloc_score: per-node fit mask + load score for one job request
# ----------------------------------------------------------------------
def alloc_score_ref(avail: jax.Array, capacity: jax.Array, req: jax.Array):
    """avail/capacity: int32[N, R]; req: int32[R].

    Returns (fit int32[N], score f32[N]) where fit[n] = 1 iff node n can
    host one rank of the job, and score[n] = fraction-in-use summed over
    resource types (Best-Fit's busiest-first key, paper §3).  In float32
    this score orders some nodes unlike the host's float64 loads (ties
    that float64 splits, e.g. on 12,288 MB nodes), as the fleet engine's
    rank key no longer does (DESIGN.md §8); only the host-vectorized
    dispatchers read it.
    """
    fit = jnp.all(avail >= req[None, :], axis=1).astype(jnp.int32)
    cap = jnp.maximum(capacity, 1).astype(jnp.float32)
    score = ((capacity - avail).astype(jnp.float32) / cap).sum(axis=1)
    return fit, score


# ----------------------------------------------------------------------
# alloc_score_batch: queue×node fit mask + load score in one shot
# ----------------------------------------------------------------------
def alloc_score_batch_ref(avail: jax.Array, capacity: jax.Array,
                          req: jax.Array):
    """avail/capacity: int32[N, R]; req: int32[J, R] (whole-queue request
    matrix from ``DispatchContext.req``).

    Returns (fit int32[J, N], score f32[J, N]) where fit[j, n] = 1 iff
    node n can host one rank of job j, and score[j, n] is node n's
    fraction-in-use summed over resource types (identical for all j — the
    Best-Fit key depends only on node state — but materialized [J, N] to
    match the batched kernel's block layout).  Like
    :func:`alloc_score_ref`'s, this float32 score can order nodes unlike
    the host's float64 loads, which the fleet engine's rank key follows.
    """
    fit = jnp.all(avail[None, :, :] >= req[:, None, :], axis=2)
    cap = jnp.maximum(capacity, 1).astype(jnp.float32)
    score = ((capacity - avail).astype(jnp.float32) / cap).sum(axis=1)
    score = jnp.broadcast_to(score[None, :], fit.shape)
    return fit.astype(jnp.int32), score


# ----------------------------------------------------------------------
# ebf_shadow: fit-count per release-prefix for EASY backfilling
# ----------------------------------------------------------------------
def ebf_shadow_ref(avail: jax.Array, deltas: jax.Array, req: jax.Array):
    """avail: int32[N, R]; deltas: int32[M, N, R] (resource release deltas
    grouped by distinct estimated release time, sorted ascending);
    req: int32[R] (the blocked head job's per-node request).

    Returns fits int32[M]: fits[m] = number of nodes that satisfy ``req``
    after applying release prefixes 0..m.  The shadow index is the first m
    with fits[m] >= requested_nodes (found by the caller).
    """
    cum = avail[None, :, :] + jnp.cumsum(deltas, axis=0)   # [M, N, R]
    fit = jnp.all(cum >= req[None, None, :], axis=2)       # [M, N]
    return fit.sum(axis=1).astype(jnp.int32)


# ----------------------------------------------------------------------
# selective_scan: Mamba-1 diagonal SSM recurrence
# ----------------------------------------------------------------------
def selective_scan_ref(u, delta, A, B, C, D, h0=None):
    """Sequential oracle of the selective scan.

    u, delta: f32[Bt, L, Di]; A: f32[Di, S]; B, C: f32[Bt, L, S];
    D: f32[Di].  Returns (y f32[Bt, L, Di], h_last f32[Bt, Di, S]).

    Recurrence (ZOH discretization, diagonal A):
        dA_t = exp(delta_t[:, None] * A)            [Di, S]
        dB_t = delta_t[:, None] * B_t[None, :]      [Di, S]
        h_t  = dA_t * h_{t-1} + dB_t * u_t[:, None]
        y_t  = (h_t @ C_t) + D * u_t
    """
    Bt, L, Di = u.shape
    S = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((Bt, Di, S), dtype=jnp.float32)

    def step(h, xs):
        u_t, d_t, B_t, C_t = xs          # [Bt,Di], [Bt,Di], [Bt,S], [Bt,S]
        dA = jnp.exp(d_t[..., None] * A[None, :, :])          # [Bt, Di, S]
        dB = d_t[..., None] * B_t[:, None, :]                 # [Bt, Di, S]
        h = dA * h + dB * u_t[..., None]
        y = jnp.einsum("bds,bs->bd", h, C_t) + D[None, :] * u_t
        return h, y

    xs = (jnp.moveaxis(u, 1, 0), jnp.moveaxis(delta, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    h_last, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h_last
