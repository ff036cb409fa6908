"""Property-based tests of the dispatching invariants."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import EventManager, Job, ResourceManager
from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                    FirstInFirstOut, ShortestJobFirst)
from repro.core.dispatchers.base import Dispatcher

job_strategy = st.builds(
    lambda i, sub, dur, est, nodes, cores, mem: Job(
        id=str(i), user_id=0, submission_time=sub, duration=dur,
        expected_duration=est, requested_nodes=nodes,
        requested_resources={"core": cores, "mem": mem}),
    i=st.integers(0, 10**6), sub=st.integers(0, 5000),
    dur=st.integers(1, 400), est=st.integers(1, 500),
    nodes=st.integers(1, 4), cores=st.integers(1, 4),
    mem=st.integers(1, 512),
)


def run_audited(jobs, sched):
    """Run a simulation loop manually, auditing resource invariants at
    every event point."""
    rm = ResourceManager({"groups": {"g": {"core": 4, "mem": 512}},
                          "nodes": {"g": 6}})
    # unique ids
    for k, j in enumerate(jobs):
        j.id = f"{j.id}-{k}"
    em = EventManager(iter(sorted(jobs, key=lambda j: j.submission_time)), rm)
    disp = Dispatcher(sched)
    started_order = []
    while em.has_events():
        t = em.next_event_time()
        if t is None:
            break
        em.advance_to(t)
        for job in list(em.queue):
            if not rm.fits_system(job):
                em.reject_job(job)
        if em.queue:
            to_start, to_reject = disp.dispatch(t, em)
            for job, nodes in to_start:
                em.start_job(job, nodes)
                started_order.append(job)
            for job in to_reject:
                em.reject_job(job)
        # --- invariants ---
        assert np.all(rm.available >= 0), "over-allocation"
        assert np.all(rm.available <= rm.capacity), "release overflow"
    return em, started_order


@settings(max_examples=25, deadline=None)
@given(st.lists(job_strategy, min_size=1, max_size=40))
def test_no_overallocation_fifo(jobs):
    em, _ = run_audited(jobs, FirstInFirstOut(FirstFit()))
    assert em.n_completed + em.n_rejected == em.n_submitted


@settings(max_examples=25, deadline=None)
@given(st.lists(job_strategy, min_size=1, max_size=40))
def test_no_overallocation_ebf(jobs):
    em, _ = run_audited(jobs, EasyBackfilling(BestFit()))
    assert em.n_completed + em.n_rejected == em.n_submitted


@settings(max_examples=25, deadline=None)
@given(st.lists(job_strategy, min_size=1, max_size=40))
def test_jobs_run_exact_duration(jobs):
    em, started = run_audited(jobs, ShortestJobFirst(FirstFit()))
    for job in started:
        assert job.end_time - job.start_time == job.duration


@settings(max_examples=20, deadline=None)
@given(st.lists(job_strategy, min_size=2, max_size=30))
def test_fifo_is_nonskipping(jobs):
    """Under blocking FIFO, a job never starts before an earlier-queued
    job *queued at a different event point* starts (head-of-line)."""
    em, started = run_audited(jobs, FirstInFirstOut(FirstFit()))
    for a, b in zip(started, started[1:]):
        if a.start_time == b.start_time:
            continue  # same dispatch round: order within round is FIFO
        assert a.queued_time <= b.start_time


def test_ebf_backfill_does_not_delay_head():
    """A short backfilled job must not delay the blocked head job beyond
    its shadow time (estimates are exact here, so it is checkable)."""
    # node: 4 cores. Long job occupies all; head wants all; a short job
    # can backfill into the gap.
    jobs = [
        Job(id="long", user_id=0, submission_time=0, duration=100,
            expected_duration=100, requested_nodes=5,
            requested_resources={"core": 4, "mem": 1}),
        Job(id="head", user_id=0, submission_time=1, duration=50,
            expected_duration=50, requested_nodes=6,
            requested_resources={"core": 4, "mem": 1}),
        Job(id="short", user_id=0, submission_time=2, duration=20,
            expected_duration=20, requested_nodes=1,
            requested_resources={"core": 4, "mem": 1}),
    ]
    em, started = run_audited(jobs, EasyBackfilling(FirstFit()))
    by_id = {j.id.rsplit("-", 1)[0]: j for j in started}
    assert by_id["head"].start_time == 100     # exactly at shadow
    assert by_id["short"].start_time < 100     # backfilled
