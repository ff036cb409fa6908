"""EASY-backfilling shadow-time computation — the ONE module both
engines share (DESIGN.md §8).

The paper's measured hot spot (Table 2: EBF spends 21:41 of 22:24 total in
dispatching) is the shadow-time computation: walk release events of
running jobs in estimated-release order, accumulate freed resources, and
find the first prefix at which the blocked head job fits.

Three entry points over the same semantics (tie-grouped prefix scan:
every release sharing a timestamp is applied before the fit test):

* :func:`ebf_shadow_pallas` — the TPU kernel.  Release events are grouped
  by distinct release time into a dense delta tensor ``deltas[M, N, R]``
  (host-side, cheap: one scatter per running job).  The kernel tiles
  nodes into 128-lane VMEM blocks and walks the M release prefixes in a
  ``fori_loop`` that carries the running availability ``[R, BN]`` and
  writes one lane-dense fit row per prefix; the per-prefix node count
  is a sum outside the kernel.
* :func:`shadow_from_releases` — the host-path driver on top of it:
  groups the ``(time, nodes, vec)`` release tuples, launches the
  fit-count scan (``ops.ebf_shadow_fits``: kernel or jnp reference), and
  returns ``(shadow_time, shadow_avail)`` — what
  ``VectorizedEasyBackfilling`` calls per blocked head.
* :func:`shadow_walk` — the *compiled-loop* twin: a vmap-safe jnp
  ``while_loop`` releasing ONE job per trip straight from the fleet
  engine's row arrays (no host grouping step), used once per blocked
  round inside ``fleet.engine``'s dispatch phase.  One release per trip
  beats the dense [M, N, R] cumsum there: the scatter building the
  delta tensor serializes badly on CPU backends and would be paid
  straight-line on EVERY event by EVERY vmapped lane, while the loop is
  a zero-trip no-op whenever no lane has a blocked head (its body costs
  a single masked argmin per release thanks to a carried next-minimum).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 128

# masked-minimum sentinel, same value as fleet.state.INF_I (kept local —
# kernels must not import the fleet package)
INF_I = 1 << 30


def _ebf_shadow_kernel(req_ref, avail_ref, deltas_ref, fit_ref):
    # Mosaic has no cumsum: the prefix scan is a loop over the M release
    # groups carrying the running availability [R, BN].  Prefixes index
    # the leading (untiled) dim of deltas [M, R, BN] and fit [M, 1, BN],
    # so every load and store is a whole (sublane, lane) tile.
    r = req_ref[...]                                      # [R, 1]

    def body(i, cur):
        cur = cur + deltas_ref[i]                         # [R, BN]
        fit_ref[i] = jnp.all(cur >= r, axis=0,
                             keepdims=True).astype(jnp.int32)
        return cur

    lax.fori_loop(0, deltas_ref.shape[0], body, avail_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ebf_shadow_pallas(
    avail: jax.Array,      # int32[N, R]
    deltas: jax.Array,     # int32[M, N, R]
    req: jax.Array,        # int32[R]
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Returns fits int32[M] — see ``ref.ebf_shadow_ref``."""
    m, n, r = deltas.shape
    n_pad = -(-n // block_n) * block_n
    # padded nodes start at -1 and receive no releases: they never fit
    avail_t = jnp.full((r, n_pad), -1, dtype=jnp.int32)
    avail_t = avail_t.at[:, :n].set(avail.astype(jnp.int32).T)
    deltas_t = jnp.zeros((m, r, n_pad), dtype=jnp.int32)
    deltas_t = deltas_t.at[:, :, :n].set(
        jnp.moveaxis(deltas.astype(jnp.int32), 2, 1))
    req2 = req.astype(jnp.int32).reshape(r, 1)

    fit = pl.pallas_call(
        _ebf_shadow_kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((r, 1), lambda j: (0, 0)),
            pl.BlockSpec((r, block_n), lambda j: (0, j)),
            pl.BlockSpec((m, r, block_n), lambda j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((m, 1, block_n), lambda j: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((m, 1, n_pad), jnp.int32),
        interpret=interpret,
        name="ebf_shadow",
    )(req2, avail_t, deltas_t)
    return fit.sum(axis=(1, 2), dtype=jnp.int32)


# ----------------------------------------------------------------------
# host path: release tuples -> (shadow_time, shadow_avail)
# ----------------------------------------------------------------------
def group_releases(avail: np.ndarray, releases: Sequence[Tuple]
                   ) -> Tuple[List[int], np.ndarray]:
    """Group sorted ``(time, node_idx, per_node_vec)`` release tuples by
    distinct release time into ``(times, deltas[M, N, R])`` — the dense
    input layout of the prefix-scan kernel."""
    times: List[int] = []
    deltas: List[np.ndarray] = []
    cur_t = None
    for t, idx, vec in releases:
        if t != cur_t:
            times.append(t)
            deltas.append(np.zeros_like(avail))
            cur_t = t
        deltas[-1][idx] += vec[None, :]
    if not deltas:
        return times, np.zeros((0,) + avail.shape, dtype=np.int32)
    return times, np.stack(deltas).astype(np.int32)


def shadow_from_releases(avail: np.ndarray, head_vec: np.ndarray,
                         n_nodes: int, releases: Sequence[Tuple]
                         ) -> Tuple[Optional[int], Optional[np.ndarray]]:
    """Earliest estimated time the blocked head fits, and the availability
    at that instant — ``EasyBackfilling._shadow`` semantics on the batched
    fit-count scan (one kernel launch regardless of release count)."""
    if not releases:
        return None, None
    from . import ops  # local: ops imports this module at load time

    times, deltas = group_releases(avail, releases)
    fits = np.asarray(ops.ebf_shadow_fits(
        np.ascontiguousarray(avail, dtype=np.int32), deltas,
        np.ascontiguousarray(head_vec, dtype=np.int32)))
    hit = np.nonzero(fits >= n_nodes)[0]
    if hit.shape[0] == 0:
        return None, None
    m = int(hit[0])
    shadow_avail = avail + deltas[: m + 1].sum(axis=0)
    return times[m], shadow_avail


# ----------------------------------------------------------------------
# compiled path: one release per while-loop trip (fleet engine)
# ----------------------------------------------------------------------
def shadow_walk(avail, rel, assigned, req, head_req, need, node_ok=None):
    """Shadow scan as a jnp ``while_loop`` over the fleet engine's row
    arrays — semantics identical to :func:`shadow_from_releases`.

    ``avail int32[N, R]`` is the availability the walk starts from (post
    greedy-phase); ``rel int32[M]`` the per-row estimated release times,
    ``INF_I`` on every row that must not participate (not running, or the
    walk is disabled for this lane — an all-INF ``rel`` makes the loop a
    vmap-safe no-op); ``assigned int32[M, K]`` node ids padded with N;
    ``req int32[M, R]``; ``head_req int32[R]`` / ``need`` the blocked
    head's request.  ``node_ok bool[N]`` (optional) excludes ineligible
    nodes (down/quarantined) from the fit count — the compiled twin of
    the host walk starting from an availability floored to -1 at those
    nodes (release deltas there are filtered host-side).

    Each trip releases the earliest-releasing row and, only once no
    remaining row shares that timestamp (the tie-grouping of the host
    walk), counts fitting nodes.  The next release's ``(row, time)`` is
    carried between trips, so a trip costs one masked ``[M]`` argmin —
    this loop runs max-over-lanes trips under vmap, so its body must
    stay minimal.  Returns ``(found, shadow_time, shadow_avail)``; when
    ``found`` is False the other outputs are meaningless.
    """
    n, r = avail.shape
    k_cap = assigned.shape[1]

    def cond(c):
        _, _, found, _, _, t_j = c
        return (~found) & (t_j < INF_I)

    def body(c):
        cur, rel, found, sh_t, j, t_j = c
        # release req[j] on its K assigned nodes; pad entries land on the
        # trash row n of the padded buffer and drop out
        add = jnp.zeros((n + 1, r), jnp.int32).at[assigned[j]].add(
            jnp.broadcast_to(req[j][None, :], (k_cap, r)))
        cur = cur + add[:n]
        rel = rel.at[j].set(INF_I)
        j2 = jnp.argmin(rel).astype(jnp.int32)
        t2 = rel[j2]
        group_done = t2 > t_j
        fitn = (cur >= head_req[None, :]).all(axis=1)
        if node_ok is not None:
            fitn = fitn & node_ok
        fit_cnt = fitn.sum(dtype=jnp.int32)
        hit = group_done & (fit_cnt >= need)
        return cur, rel, found | hit, jnp.where(hit, t_j, sh_t), j2, t2

    j0 = jnp.argmin(rel).astype(jnp.int32)
    init = (avail, rel, jnp.array(False), jnp.int32(0), j0, rel[j0])
    cur, _, found, sh_t, _, _ = lax.while_loop(cond, body, init)
    return found, sh_t, cur
