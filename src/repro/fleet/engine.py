"""Compiled steady-state advance: the whole event loop as ONE jitted
``lax.while_loop`` over :class:`~repro.fleet.state.SimState` (DESIGN.md §8).

The host simulator pays a host↔device round trip per event; this engine
runs *thousands of events per host interaction*: next-event time,
completion release, submission batch, and a full dispatch round all
execute as masked array ops inside one while loop, so a fleet of
simulations `vmap`s along a leading sim axis with zero host involvement.

Covered dispatchers: {FIFO, SJF, LJF, EBF} (``sched_code``) × {FirstFit,
BestFit} (``alloc_code``) — the paper's full Table-2 policy set.

**Scheduling.**  The blocking policies sort queue indices by
``(est, queued_time)`` (stable over FIFO arrival order) and stop at the
first allocation failure.  Every key of that order is static — estimates
never change, ranks follow the fixed ``pending`` order and a row's
``queued_time`` is its submit time — so :func:`_priority_order` ranks the
rows once per sim into static positions, and each greedy trip picks the
next job by one masked argmin over those positions of the queued rows:

    FIFO  fifo_rank
    SJF   lexsort by (est,  queued_time, fifo_rank)
    LJF   lexsort by (-est, queued_time, fifo_rank)
    EBF   fifo_rank                              # FIFO priority

A failure drain re-ranks requeued victims, so with failures the order is
recomputed after each drain.

**EASY-backfilling** extends the round with a ``(shadow_time, extra)``
carry: when the greedy phase hits its first blocked job (the *head*),
the shadow walk (``kernels.ebf_shadow.shadow_walk`` — one estimated
release per trip, tie-grouped exactly like the host scan) finds the
earliest instant the head fits, the allocator reserves the head's nodes
at that instant, and the round switches to a backfill phase: remaining
queued jobs (FIFO order, tracked by a rank cursor) start iff they fit
*now* and either finish (by estimate) before the shadow time or fit
inside ``min(avail, extra)`` — the resources left over after the head's
reservation.  Skips don't end the backfill phase; the cursor strictly
advances, bounding the round.

**Allocation.**  FirstFit picks the first ``n_need`` fitting nodes by
node id, BestFit the first ``n_need`` *busiest-first*, through one
shared probe (no dynamic-size ``nonzero``): a stable sort of (key, node
id, fit) puts the nodes in the policy's order, ``sel = fit & (cumsum <=
need)`` marks the chosen ones, and slot ``j`` of the ``[K]`` assignment
list is the chosen node whose running count is ``j + 1`` (a one-hot min;
the node mask is read back from the slots by a ``[N, K]`` compare, as a
scatter or gather of node ids runs element by element on TPU).
FirstFit's key is 0, so the stable sort keeps node-id order.  BestFit's
key is the negated load ``Σ_r (cap - avail)/cap``, as the int32 rank of
its float64 value, gathered from the machine's
:class:`~repro.fleet.state.BestFitKey` at the node's usage vector (equal
float64 loads, equal ranks: float64 splits some loads that are equal as
fractions, and neither float32 nor exact arithmetic orders those as the
host does); ties go by node id, as ``np.argsort(..., kind="stable")``,
so each admitted job lands on its tightest-fitting nodes and the
assignment list order matches the host's busiest-first output.  The
probe runs under the ``select_nodes`` scope, inside ``dispatch`` or
``backfill``.

Everything is int32 (no x64 on the accelerator path); ``INF_I = 2**30``
is the masked-minimum sentinel.  Termination: every outer iteration
either advances the submission pointer or retires >= 1 completion, so
the loop runs at most ``2M + 8`` steps (also the event-log length and
the runaway guard); inside a round, every trip either starts a job or
advances the backfill cursor past one queued rank.

**Phases.**  Each phase runs under a ``jax.named_scope`` (:data:`PHASES`):
``prologue`` (the static priority order), then per event ``next_event``
(event time and the loop test), ``complete``, ``drain`` (failures only),
``admit``, ``dispatch`` (queued count, greedy loop), ``backfill``
(shadow walk, head reservation, backfill loop, stats) and ``record``
(event log, telemetry sample, counters), and ``epilogue`` after the
loop.  A scope names the HLO ops it emits (``op_name`` metadata, the
profiler's ``tf_op``) and changes nothing else, so a device trace
attributes device time to phases.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels.ebf_shadow import shadow_walk
from .state import (COMPLETED, INF_I, QUEUED, REJECTED, RUNNING, BestFitKey,
                    SimState, UNSET_I, launch_bf_key)

SCHED_FIFO, SCHED_SJF, SCHED_LJF, SCHED_EBF = 0, 1, 2, 3
SCHED_NAMES = {SCHED_FIFO: "FIFO", SCHED_SJF: "SJF", SCHED_LJF: "LJF",
               SCHED_EBF: "EBF"}

ALLOC_FF, ALLOC_BF = 0, 1
ALLOC_NAMES = {ALLOC_FF: "FF", ALLOC_BF: "BF"}

# the loop's named scopes, in program order
PHASES = ("prologue", "next_event", "complete", "drain", "admit",
          "dispatch", "backfill", "record", "epilogue")


# ----------------------------------------------------------------------
# compilability contract
# ----------------------------------------------------------------------
def dispatch_code(scheduler) -> Optional[Tuple[int, int]]:
    """``(sched_code, alloc_code)`` for ``scheduler``, or None if it
    cannot be lowered onto the compiled loop.

    Compilable = exactly one of FIFO/SJF/LJF/EBF (subclasses may
    override ``plan`` arbitrarily, so only the exact types qualify) with
    exactly a ``FirstFit`` or ``BestFit`` allocator and no
    ``observe_completion`` hook (data-driven schedulers need the host
    callback stream).
    """
    from ..core.dispatchers.allocators import BestFit, FirstFit
    from ..core.dispatchers.schedulers import (EasyBackfilling,
                                               FirstInFirstOut,
                                               LongestJobFirst,
                                               ShortestJobFirst)

    scodes = {FirstInFirstOut: SCHED_FIFO, ShortestJobFirst: SCHED_SJF,
              LongestJobFirst: SCHED_LJF, EasyBackfilling: SCHED_EBF}
    acodes = {FirstFit: ALLOC_FF, BestFit: ALLOC_BF}
    sc = scodes.get(type(scheduler))
    if sc is None:
        return None
    ac = acodes.get(type(getattr(scheduler, "allocator", None)))
    if ac is None:
        return None
    if getattr(scheduler, "observe_completion", None) is not None:
        return None
    return sc, ac


def sched_code(scheduler) -> Optional[int]:
    """Engine scheduler code for a compilable ``scheduler`` (None if the
    dispatcher — scheduler OR allocator — cannot be lowered)."""
    pair = dispatch_code(scheduler)
    return None if pair is None else pair[0]


def alloc_code(scheduler) -> Optional[int]:
    """Engine allocator code for a compilable ``scheduler`` (None if the
    dispatcher cannot be lowered)."""
    pair = dispatch_code(scheduler)
    return None if pair is None else pair[1]


def compiles(scheduler) -> bool:
    """Whether ``scheduler`` can run on the compiled fleet engine."""
    return dispatch_code(scheduler) is not None


# ----------------------------------------------------------------------
# the compiled loop
# ----------------------------------------------------------------------
def _priority_order(s: SimState):
    """Static per-row priority positions for the active policy.

    The host's lexicographic keys — ``(est, queued_time, fifo_rank)``
    for SJF/LJF, ``fifo_rank`` for FIFO/EBF — are all *determined by
    static inputs*: estimates never change, ranks are handed out in the
    fixed ``pending`` order, and a row's ``queued_time`` always equals
    its submit time (a submission is always its own event).  So the
    whole lex order can be materialized ONCE per sim, and every
    candidate selection in the dispatch round collapses from a
    three-key lexicographic argmin (~6 masked ``[M]`` passes per trip)
    to a single masked argmin over these positions — the dominant cost
    of the hot greedy loop.

    Rows already admitted (resumed snapshots) keep their recorded
    ``fifo_rank``/``queued_time``; rows still pending get the rank the
    admit loop will hand them (``rank_ctr + position - ptr``) and their
    submit time.  Rows outside the pending window land on a trash slot.
    """
    m = s.submit.shape[0]
    pos = jnp.arange(m, dtype=jnp.int32)
    future = (pos >= s.ptr) & (pos < s.n_pending)
    tgt = jnp.where(future, s.pending, m)
    rank = jnp.zeros(m + 1, jnp.int32).at[tgt].set(
        s.rank_ctr + pos - s.ptr)[:m]
    rank = jnp.where(s.fifo_rank < INF_I, s.fifo_rank, rank)
    qt = jnp.where(s.queued_time >= 0, s.queued_time, s.submit)

    def lex(key):
        order = jnp.lexsort((rank, qt, key))
        return jnp.zeros(m, jnp.int32).at[order].set(pos)

    return lax.switch(
        jnp.clip(s.sched_id, 0, 3),
        [lambda: rank,
         lambda: lex(s.est),
         lambda: lex(-s.est),
         lambda: rank])                      # EBF runs FIFO priority


def _select_nodes(alloc_id, pool, capacity, bf, reqv, need, k_cap, elig=None):
    """Allocator probe against ``pool`` availability: FirstFit (node-id
    order) or BestFit (busiest-first stable order) via one shared sort
    and cumsum over the policy's node ordering.

    Returns ``(ok, sel [N] bool, nodes [K])``; ``bf`` is the machine's
    :class:`~repro.fleet.state.BestFitKey`; ``elig`` (bool[N], optional)
    ANDs the failure-aware node-eligibility mask — the compiled twin of
    the host's -1 availability floor on down/quarantined nodes
    (DESIGN.md §9).
    """
    n = pool.shape[0]
    node_ids = jnp.arange(n, dtype=jnp.int32)
    fitn = (pool >= reqv[None, :]).all(axis=1)
    if elig is not None:
        fitn = fitn & elig
    with jax.named_scope("select_nodes"):
        # BestFit key: the rank of the node's float64 load, fraction-in-use
        # summed over resource types, looked up at its usage vector — the
        # host's np.argsort order, float64 ties and all (DESIGN.md §8).
        # FirstFit's key is constant, so the stable sort keeps node order
        used = ((capacity - pool) * bf.stride).sum(axis=1)
        key = jnp.where(alloc_id == ALLOC_BF, -bf.rank[bf.base + used], 0)
        _, order, fit_o = lax.sort((key, node_ids, fitn.astype(jnp.int32)),
                                   num_keys=1, is_stable=True)
        csum = jnp.cumsum(fit_o)
        ok = csum[-1] >= need
        sel_o = (fit_o > 0) & (csum <= need)  # first `need` fitting in order
        # slot j holds the selected node whose running count is j + 1; the
        # node mask is read back from the slots: one-hot compares, where a
        # scatter or gather of the node ids runs element by element on TPU
        slot = jnp.arange(1, k_cap + 1, dtype=jnp.int32)
        hit = sel_o[None, :] & (csum[None, :] == slot[:, None])
        nodes = jnp.where(hit, order[None, :], n).min(axis=1)
        sel = (node_ids[:, None] == nodes[None, :]).any(axis=1)
    return ok, sel, nodes


def _dispatch_round(s: SimState, bf: BestFitKey, state, start, end, assigned,
                    avail, t, pri, q0, elig=None,
                    collect_stats=False):
    """One full dispatch round at event time ``t``, in three phases.

    **Greedy loop** — select the highest-priority queued job, probe the
    allocator against current availability, commit; stop on the first
    failure (all four policies start greedily until blocked).

    **Shadow + reservation** (straight-line, once per round, EBF only) —
    the job the greedy loop blocked on is the *head*: walk the estimated
    releases of running jobs to the first instant the head fits
    (``shadow_walk``, shared with the host scheduler), place the head's
    reservation there with the round's allocator, and derive the
    ``extra`` pool the reservation leaves free.

    **Backfill loop** (EBF with a feasible shadow only) — scan the queue
    past the head in FIFO rank order; a job may start iff it fits now
    AND (finishes by estimate before the shadow time, or fits inside
    ``min(avail, extra)``).  Misfits are skipped in BULK: each trip
    computes every job's fit count against its own pool (``[M, N]`` —
    nodes are few) and jumps straight to the first rank that passes, so
    the loop costs O(starts) trips, not O(queue) — trace-equivalent
    because a misfit probe has no side effects on the host either.

    The phase split keeps the hot greedy loop as lean as the blocking
    policies need (the shadow machinery and bulk fit counts priced only
    into rounds that block), which matters under vmap where every lane
    pays for the widest lane's body.  ``pri`` is the static priority
    order from :func:`_priority_order`; ``q0`` the number of queued
    rows at round entry (the round never re-queues, so the count just
    decrements per start); ``bf`` the machine's Best-Fit key.  Returns the
    updated job/node arrays and the number of jobs started this event.
    ``elig`` (bool[N] or None) is the failure-aware node-eligibility
    mask, threaded through every allocator probe, both bulk fit counts,
    and the shadow walk.

    ``collect_stats`` (STATIC — telemetry-off compiles it away) appends
    the per-event phase counters ``(dispatch_trips, shadow_trips,
    backfill_admits, misfit_skips)`` to the return tuple, all derived
    post-loop from carried scalars so the hot inner loops stay
    untouched; the host planners count the same quantities
    (DESIGN.md §10).
    """
    k_cap = assigned.shape[1]
    is_ebf = s.sched_id == SCHED_EBF

    def cond(c):
        return c[-1]

    # --- phase 1: greedy starts until the first blocked candidate -----
    def g_body(c):
        (state, start, end, assigned, avail, n_started, started_evt,
         q_cnt, _, _) = c
        queued = state == QUEUED
        idx = jnp.argmin(jnp.where(queued, pri, INF_I)).astype(jnp.int32)
        has_cand = q_cnt > 0
        reqv = s.req[idx]
        need = s.n_need[idx]
        ok_fit, sel, nodes = _select_nodes(
            s.alloc_id, avail, s.capacity, bf, reqv, need, k_cap, elig)
        ok = has_cand & ok_fit
        dec = sel[:, None].astype(jnp.int32) * reqv[None, :]
        avail = jnp.where(ok, avail - dec, avail)
        state = state.at[idx].set(jnp.where(ok, RUNNING, state[idx]))
        start = start.at[idx].set(jnp.where(ok, t, start[idx]))
        end = end.at[idx].set(jnp.where(ok, t + s.duration[idx], end[idx]))
        assigned = assigned.at[idx].set(jnp.where(ok, nodes, assigned[idx]))
        oki = ok.astype(jnp.int32)
        q_cnt = q_cnt - oki
        go = ok & (q_cnt > 0)
        return (state, start, end, assigned, avail,
                n_started + oki, started_evt + oki, q_cnt, idx, go)

    with jax.named_scope("dispatch"):
        (state, start, end, assigned, avail, n_started, started_evt, q_cnt,
         idx_h, _) = lax.while_loop(
            cond, g_body,
            (state, start, end, assigned, avail, s.n_started, jnp.int32(0),
             q0, jnp.int32(0), q0 > 0))

    # --- phase 2: EBF shadow walk + head reservation (once) -----------
    # The loop above exits with queued rows remaining exactly when its
    # last probe FAILED, so the candidate it carried out is the blocked
    # head (arbitrary when the queue drained — has_head masks that).
    with jax.named_scope("backfill"):
        queued = state == QUEUED
        has_head = is_ebf & (q_cnt > 0)
        head_req = s.req[idx_h]
        head_need = s.n_need[idx_h]
        # estimated releases of running rows (incl. this round's starts:
        # start == t); a job may overrun its estimate, so never before
        # t + 1.  All-INF when no EBF head is blocked, which makes the walk
        # a zero-trip no-op (vmap-safe).
        rel = jnp.where((state == RUNNING) & has_head,
                        jnp.maximum(start + s.est, t + 1), INF_I)
        found, shadow_t, sh_avail = shadow_walk(avail, rel, assigned, s.req,
                                                head_req, head_need,
                                                node_ok=elig)
        # head reservation at shadow time
        _, sel_h, _ = _select_nodes(
            s.alloc_id, sh_avail, s.capacity, bf, head_req, head_need, k_cap,
            elig)
        enter_bf = has_head & found
        extra = jnp.where(
            enter_bf,
            sh_avail - sel_h[:, None].astype(jnp.int32) * head_req[None, :],
            jnp.zeros_like(avail))

        # backfill pool per job: plain avail while the candidate finishes
        # (by estimate) before the shadow time, else it must not touch the
        # head's reservation -> min(avail, extra)
        before_all = t + s.est <= shadow_t                           # [M]
        cursor0 = s.fifo_rank[idx_h]
        go0 = enter_bf & (queued & (s.fifo_rank > cursor0)).any()

    # --- phase 3: backfill behind the reservation ---------------------
    def b_body(c):
        (state, start, end, assigned, avail, extra, n_started,
         started_evt, cursor, _) = c
        queued = state == QUEUED
        # bulk misfit skip: every job's fit count against its OWN pool
        # (avail for before-shadow candidates, min(avail, extra) past
        # it) — the count must honor the reservation or every
        # avail-fitting-but-reservation-blocked job burns a trip.  Full
        # [M] width on purpose: these workloads run overloaded with
        # queue depths in the hundreds, so any fixed row window leaks
        # one trip per uncovered row and loses far more than the
        # narrower tensor saves.
        pool_b = jnp.minimum(avail, extra)
        fit_a = (avail[None, :, :] >= s.req[:, None, :]).all(axis=2)
        fit_b = (pool_b[None, :, :] >= s.req[:, None, :]).all(axis=2)
        if elig is not None:
            fit_a = fit_a & elig[None, :]
            fit_b = fit_b & elig[None, :]
        cnt_a = fit_a.sum(axis=1, dtype=jnp.int32)               # [M]
        cnt_b = fit_b.sum(axis=1, dtype=jnp.int32)
        can_start = jnp.where(before_all, cnt_a, cnt_b) >= s.n_need
        bf_cand = queued & (s.fifo_rank > cursor) & can_start
        idx = jnp.argmin(
            jnp.where(bf_cand, s.fifo_rank, INF_I)).astype(jnp.int32)
        has_cand = bf_cand.any()

        reqv = s.req[idx]
        need = s.n_need[idx]
        before_shadow = before_all[idx]
        pool = jnp.where(before_shadow, avail, pool_b)
        ok_fit, sel, nodes = _select_nodes(
            s.alloc_id, pool, s.capacity, bf, reqv, need, k_cap, elig)
        ok = has_cand & ok_fit

        dec = sel[:, None].astype(jnp.int32) * reqv[None, :]
        avail = jnp.where(ok, avail - dec, avail)
        extra = jnp.where(ok & (~before_shadow), extra - dec, extra)
        state = state.at[idx].set(jnp.where(ok, RUNNING, state[idx]))
        start = start.at[idx].set(jnp.where(ok, t, start[idx]))
        end = end.at[idx].set(jnp.where(ok, t + s.duration[idx], end[idx]))
        assigned = assigned.at[idx].set(jnp.where(ok, nodes, assigned[idx]))
        oki = ok.astype(jnp.int32)

        cursor = jnp.where(has_cand, s.fifo_rank[idx], cursor)
        # a candidate whose real pool rejected it commits nothing and
        # the cursor skips past it — the has_cand gating keeps the
        # cursor-progress guarantee; can_start (vs post-commit avail,
        # a subset of pre-commit fits) trims the terminal no-fit trip
        more_bf = ((state == QUEUED) & (s.fifo_rank > cursor)
                   & can_start).any()
        go = has_cand & more_bf
        return (state, start, end, assigned, avail, extra,
                n_started + oki, started_evt + oki, cursor, go)

    with jax.named_scope("backfill"):
        out = lax.while_loop(
            cond, b_body,
            (state, start, end, assigned, avail, extra, n_started,
             started_evt, cursor0, go0))
    if not collect_stats:
        return out[:5] + out[6:8]
    # phase counters, all from already-carried scalars (DESIGN.md §10).
    # ``started_evt``/``q_cnt`` hold the PHASE-1 values here (the
    # backfill loop's totals live in ``out``):
    #   dispatch_trips  = greedy probes = starts + the one blocked probe
    #   shadow_trips    = releases consumed by the walk (every release at
    #                     or before the shadow instant; ALL of them when
    #                     the head never fits — the host's no-shadow case)
    #   backfill_admits = phase-3 starts
    #   misfit_skips    = backfill candidates behind the head that did
    #                     not start (no-fit + would-delay-head)
    with jax.named_scope("backfill"):
        disp_trips = started_evt + (q_cnt > 0).astype(jnp.int32)
        sh_trips = jnp.where(
            has_head,
            jnp.where(found,
                      ((rel <= shadow_t) & (rel < INF_I)).sum(dtype=jnp.int32),
                      (rel < INF_I).sum(dtype=jnp.int32)),
            0).astype(jnp.int32)
        bf_admits = out[7] - started_evt
        misfit = jnp.where(has_head, (q0 - started_evt - 1) - bf_admits,
                           0).astype(jnp.int32)
    return out[:5] + out[6:8] + ((disp_trips, sh_trips, bf_admits,
                                  misfit),)


def _advance_impl(s: SimState, bf: BestFitKey) -> SimState:
    m = s.submit.shape[0]
    n, r = s.avail.shape
    k_cap = s.assigned.shape[1]
    e = s.log_t.shape[0]
    f_cap = s.fail_ev.shape[0]
    # static switch: F == 0 compiles the exact pre-failure engine — all
    # failure machinery below vanishes at trace time
    has_fail = f_cap > 0
    # static switch: S == 0 compiles the exact pre-telemetry engine —
    # sampling, phase-counter accumulation and the dispatch round's
    # stats arm all vanish at trace time (DESIGN.md §10)
    tele_cap = s.tele_buf.shape[0]
    has_tele = tele_cap > 0
    # runaway guard: without failures every iteration admits or retires
    # one of <= 2M job events; a failure schedule adds F event times plus
    # at most one extra completion per (victim, FAIL event) requeue pair.
    # The log keeps its 2M + F + 8 slots and clamps on overflow.
    guard = 2 * m + 8 + (f_cap * (m + 1) if has_fail else 0)
    # the policy's priority order is static without failures (see
    # _priority_order) — one sort per sim replaces a lex argmin per
    # dispatch trip.  Requeues re-rank victims mid-run, so the order is
    # carried in the state and recomputed after each failure drain.
    with jax.named_scope("prologue"):
        s = s._replace(pri=_priority_order(s))

    def cond(s: SimState):
        with jax.named_scope("next_event"):
            go = (s.ptr < s.n_pending) | (s.state == RUNNING).any()
            if has_fail:
                # queued jobs may be waiting on a REPAIR / quarantine expiry
                # that only a later failure event can unblock
                queued = s.n_submitted - s.n_rejected - s.n_started
                go = go | ((queued > 0) & (s.fptr < s.n_fail))
            return (s.steps < guard) & go

    def body(s: SimState) -> SimState:
        # ---- next event time: min(submission, completion, failure) ---
        with jax.named_scope("next_event"):
            pidx = s.pending[jnp.clip(s.ptr, 0, m - 1)]
            t_sub = jnp.where(s.ptr < s.n_pending, s.submit[pidx], INF_I)
            running = s.state == RUNNING
            t_end = jnp.where(running, s.end, INF_I).min()
            t = jnp.minimum(t_sub, t_end)
            if has_fail:
                # a FAIL/REPAIR is a wake-up only while jobs are live
                # (running or queued) — mirrors EventManager.next_event_time;
                # events <= t set by a job event still drain below
                n_live = s.n_submitted - s.n_rejected - s.n_completed
                t_fail = jnp.where(
                    (s.fptr < s.n_fail) & (n_live > 0),
                    s.fail_ev[jnp.clip(s.fptr, 0, f_cap - 1), 0], INF_I)
                t = jnp.minimum(t, t_fail)

        # ---- completions first (as advance_to), retired ONE at a time:
        # a typical event completes a single job, so an O(1)-sized inner
        # loop beats the O(M*K) every-row release scatter by a wide
        # margin on the critical path (addition commutes, so the order
        # of same-time releases cannot change the resulting avail).
        def c_cond(c):
            state, _, _ = c
            emin = jnp.where(state == RUNNING, s.end, INF_I).min()
            # the emin < INF_I guard matters under vmap: a finished lane
            # still EXECUTES this body (masked afterwards) with t = INF_I,
            # and INF_I <= INF_I would spin forever
            return (emin <= t) & (emin < INF_I)

        def c_body(c):
            state, avail, n_completed = c
            idx = jnp.argmin(
                jnp.where(state == RUNNING, s.end, INF_I)).astype(jnp.int32)
            # release req[idx] on its K assigned nodes; pad entries point
            # at the trash row n of the padded buffer and drop out
            rel = jnp.zeros((n + 1, r), jnp.int32).at[s.assigned[idx]].add(
                jnp.broadcast_to(s.req[idx][None, :], (k_cap, r)))
            return (state.at[idx].set(COMPLETED), avail + rel[:n],
                    n_completed + 1)

        with jax.named_scope("complete"):
            state, avail, n_completed = lax.while_loop(
                c_cond, c_body, (s.state, s.avail, s.n_completed))

        # ---- failure drain: FAIL preempts + requeues, REPAIR restores -
        # (between completions and submissions, exactly advance_to's
        # order: a job completing at t escapes a failure at t; victims
        # re-rank ahead of same-t submissions).  One event per trip.
        pri = s.pri
        elig = None
        if has_fail:
            def f_cond(c):
                fptr = c[12]
                ev_t = s.fail_ev[jnp.clip(fptr, 0, f_cap - 1), 0]
                # t < INF_I: a finished vmap lane still executes this
                # body masked with t = INF_I and must not drain the tail
                # of its schedule (the host leaves trailing events
                # unprocessed too)
                return (fptr < s.n_fail) & (ev_t <= t) & (t < INF_I)

            def f_body(c):
                (state, start, end, assigned, avail, duration, fifo_rank,
                 rank_ctr, n_started, node_up, quar_until, down_since,
                 fptr, n_requeued, lost_work, downtime) = c
                ev = s.fail_ev[jnp.clip(fptr, 0, f_cap - 1)]
                ev_t, v, kind = ev[0], ev[1], ev[2]
                up_v = node_up[v] > 0
                do_fail = (kind == 1) & up_v        # FAIL on a down node
                do_rep = (kind == 0) & (~up_v)      # / REPAIR on an up
                                                    # node are no-ops
                # victims: running rows with the failed node in their
                # assignment (pad slots hold n and never match)
                vm = do_fail & (state == RUNNING) & \
                    (assigned == v).any(axis=1)
                # release every victim's full allocation in one scatter;
                # pad columns land on the trash row n and drop out
                contrib = jnp.where(
                    vm[:, None, None],
                    jnp.broadcast_to(s.req[:, None, :], (m, k_cap, r)), 0)
                add = jnp.zeros((n + 1, r), jnp.int32).at[assigned].add(
                    contrib)
                avail = avail + add[:n]
                nv = vm.sum(dtype=jnp.int32)
                # checkpoint/restart credit (CheckpointRestartPolicy):
                # a victim re-runs only the work since its last
                # checkpoint boundary; ck == 0 means full re-run
                ran = ev_t - start                  # masked by vm below
                ck = s.ckpt_every_s
                saved = jnp.where(ck > 0,
                                  (ran // jnp.maximum(ck, 1)) * ck, 0)
                saved = jnp.minimum(saved, jnp.maximum(duration - 1, 0))
                new_dur = jnp.maximum(duration - saved, 1)
                lost_work = lost_work + jnp.where(
                    vm, ran - (duration - new_dur), 0
                ).sum(dtype=jnp.int32)
                duration = jnp.where(vm, new_dur, duration)
                # victims rejoin the queue at the back, ordered by their
                # previous enqueue order (= current fifo_rank) — the
                # host requeues through the same ring in stamp order
                key = jnp.where(vm, fifo_rank, INF_I)
                order = jnp.argsort(key)
                pos = jnp.arange(m, dtype=jnp.int32)
                newr = jnp.where(pos < nv, rank_ctr + pos,
                                 fifo_rank[order])
                fifo_rank = fifo_rank.at[order].set(newr)
                rank_ctr = rank_ctr + nv
                state = jnp.where(vm, QUEUED, state).astype(jnp.int32)
                start = jnp.where(vm, UNSET_I, start)
                end = jnp.where(vm, INF_I, end)
                assigned = jnp.where(vm[:, None], n, assigned)
                n_started = n_started - nv
                n_requeued = n_requeued + nv
                downtime = downtime + jnp.where(
                    do_rep, ev_t - down_since[v], 0)
                node_up = node_up.at[v].set(
                    jnp.where(do_fail, 0,
                              jnp.where(do_rep, 1, node_up[v])))
                quar_until = quar_until.at[v].set(
                    jnp.where(do_fail, ev_t + s.quarantine_s,
                              quar_until[v]))
                down_since = down_since.at[v].set(
                    jnp.where(do_fail, ev_t,
                              jnp.where(do_rep, -1, down_since[v])))
                return (state, start, end, assigned, avail, duration,
                        fifo_rank, rank_ctr, n_started, node_up,
                        quar_until, down_since, fptr + 1, n_requeued,
                        lost_work, downtime)

            with jax.named_scope("drain"):
                (state, start_f, end_f, assigned_f, avail, duration_f,
                 fifo_rank_f, rank_ctr_f, n_started_f, node_up, quar_until,
                 down_since, fptr, n_requeued, lost_work,
                 downtime) = lax.while_loop(
                    f_cond, f_body,
                    (state, s.start, s.end, s.assigned, avail, s.duration,
                     s.fifo_rank, s.rank_ctr, s.n_started, s.node_up,
                     s.quar_until, s.down_since, s.fptr, s.n_requeued,
                     s.lost_work_s, s.node_downtime_s))
                s = s._replace(
                    start=start_f, end=end_f, assigned=assigned_f,
                    duration=duration_f, fifo_rank=fifo_rank_f,
                    rank_ctr=rank_ctr_f, n_started=n_started_f,
                    node_up=node_up, quar_until=quar_until,
                    down_since=down_since, fptr=fptr, n_requeued=n_requeued,
                    lost_work_s=lost_work, node_downtime_s=downtime)
                # requeues shifted ranks (victims re-ranked, pending rows'
                # future ranks moved by nv) -> refresh the carried order
                pri = _priority_order(s)
                s = s._replace(pri=pri)
                # dispatch-eligibility at this event: up and out of
                # quarantine — EventManager.node_eligibility(t)
                elig = (node_up > 0) & (quar_until <= t)

        # ---- submission batch: contiguous pending prefix with T_sb <= t,
        # admitted one row per trip in (T_sb, seq) order — ranks are
        # handed out in exactly the host's enqueue order, and unfit rows
        # consume a rank but land REJECTED with no queued_time.
        def s_cond(c):
            _, _, _, ptr = c[:4]
            row = s.pending[jnp.clip(ptr, 0, m - 1)]
            return (ptr < s.n_pending) & (s.submit[row] <= t)

        def s_body(c):
            state, queued_time, fifo_rank, ptr, rank_ctr, n_sub, n_rej = c
            row = s.pending[jnp.clip(ptr, 0, m - 1)]
            unfit = s.unfit[row] > 0
            state = state.at[row].set(
                jnp.where(unfit, REJECTED, QUEUED).astype(jnp.int32))
            queued_time = queued_time.at[row].set(
                jnp.where(unfit, queued_time[row], t))
            fifo_rank = fifo_rank.at[row].set(rank_ctr)
            return (state, queued_time, fifo_rank, ptr + 1, rank_ctr + 1,
                    n_sub + 1, n_rej + unfit.astype(jnp.int32))

        with jax.named_scope("admit"):
            (state, queued_time, fifo_rank, ptr, rank_ctr, n_submitted,
             n_rejected) = lax.while_loop(
                s_cond, s_body,
                (state, s.queued_time, s.fifo_rank, s.ptr, s.rank_ctr,
                 s.n_submitted, s.n_rejected))

            s1 = s._replace(state=state, queued_time=queued_time,
                            fifo_rank=fifo_rank)

        # ---- dispatch round -------------------------------------------
        # queued count from the admit/start/complete counters (a row is
        # QUEUED iff admitted and neither rejected nor started) — saves
        # an [M] reduction per event
        with jax.named_scope("dispatch"):
            q0 = n_submitted - n_rejected - s.n_started
            any_queued = q0 > 0
        res = _dispatch_round(
            s1, bf, state, s1.start, s1.end, s1.assigned, avail, t, pri, q0,
            elig, collect_stats=has_tele)
        (state, start, end, assigned, avail, n_started,
         started_evt) = res[:7]

        # ---- per-event log (host bench-line schema) and counters -------
        with jax.named_scope("record"):
            n_rounds = s.n_rounds + any_queued.astype(jnp.int32)
            i = jnp.clip(s.n_events, 0, e - 1)
            log_t = s.log_t.at[i].set(t)
            log_queue = s.log_queue.at[i].set(q0 - started_evt)
            log_running = s.log_running.at[i].set(n_started - n_completed)
            log_started = s.log_started.at[i].set(started_evt)

            new = s._replace(
                state=state, queued_time=queued_time, start=start, end=end,
                fifo_rank=fifo_rank, assigned=assigned, avail=avail,
                ptr=ptr, now=t, rank_ctr=rank_ctr,
                n_submitted=n_submitted, n_completed=n_completed,
                n_rejected=n_rejected, n_started=n_started,
                n_events=s.n_events + 1, n_rounds=n_rounds,
                steps=s.steps + 1,
                log_t=log_t, log_queue=log_queue, log_running=log_running,
                log_started=log_started)

            if has_tele:
                # ---- telemetry sample + phase counters (DESIGN.md §10) ----
                # 0-based event index % stride == 0 — the FIRST event is
                # always recorded, matching the host monitor.  stride == 0
                # keeps a telemetry-off sim inert inside a telemetry-on
                # batch; a full buffer stops writing (decoded as truncated).
                # ``s.n_requeued`` is post-failure-drain (s was rebound).
                disp, sh, admits, mis = res[7]
                stride = s.tele_stride
                do = (stride > 0) & (s.tele_n < tele_cap) & \
                    (s.n_events % jnp.maximum(stride, 1) == 0)
                row = jnp.concatenate([
                    jnp.stack([t, q0 - started_evt, n_started - n_completed,
                               n_started + s.n_requeued, s.n_requeued]),
                    avail.sum(axis=0)]).astype(jnp.int32)
                j = jnp.clip(s.tele_n, 0, tele_cap - 1)
                new = new._replace(
                    tele_buf=s.tele_buf.at[j].set(
                        jnp.where(do, row, s.tele_buf[j])),
                    tele_n=s.tele_n + do.astype(jnp.int32),
                    ct_disp_trips=s.ct_disp_trips + disp,
                    ct_shadow_trips=s.ct_shadow_trips + sh,
                    ct_backfill=s.ct_backfill + admits,
                    ct_misfit=s.ct_misfit + mis)
        return new

    out = lax.while_loop(cond, body, s)
    with jax.named_scope("epilogue"):
        if has_fail:
            # host livelock parity: queued jobs that outlast every event
            # (submissions, completions, the failure schedule) can never
            # start; the host simulator rejects them without another event
            # point, so no event is counted here either
            leftover = out.state == QUEUED
            out = out._replace(
                state=jnp.where(leftover, REJECTED,
                                out.state).astype(jnp.int32),
                n_rejected=out.n_rejected + leftover.sum(dtype=jnp.int32))
        if has_tele:
            # end-of-sim sample when the last event missed the stride —
            # AFTER the livelock rejection above, exactly where the host
            # monitor's finalize() runs, so both engines close the series
            # on the same post-rejection counts
            stride = out.tele_stride
            need = (stride > 0) & (out.n_events > 0) & \
                (out.tele_n < tele_cap) & \
                ((out.n_events - 1) % jnp.maximum(stride, 1) != 0)
            queue_now = out.n_submitted - out.n_rejected - out.n_started
            row = jnp.concatenate([
                jnp.stack([out.now, queue_now, out.n_started - out.n_completed,
                           out.n_started + out.n_requeued, out.n_requeued]),
                out.avail.sum(axis=0)]).astype(jnp.int32)
            j = jnp.clip(out.tele_n, 0, tele_cap - 1)
            out = out._replace(
                tele_buf=out.tele_buf.at[j].set(
                    jnp.where(need, row, out.tele_buf[j])),
                tele_n=out.tele_n + need.astype(jnp.int32))
    return out


_advance_jit = jax.jit(_advance_impl)


def advance(state: SimState) -> SimState:
    """Run one simulation to completion on device; returns the final
    state (all jobs COMPLETED/REJECTED, full event log), with the
    machine's Best-Fit key (:func:`~repro.fleet.state.launch_bf_key`)."""
    bf = launch_bf_key(np.asarray(state.capacity),
                       int(state.alloc_id) == ALLOC_BF)
    return _advance_jit(state, bf)


def advance_fn():
    """Unjitted single-sim advance ``(state, bf_key) -> state`` — the unit
    ``FleetRunner`` wraps in ``vmap`` (over the state only) and
    ``shard_map`` before jitting.  A lambda, so the compiled module keeps
    the name ``jit__lambda`` that the recorded device traces carry."""
    return lambda s, bf: _advance_impl(s, bf)
