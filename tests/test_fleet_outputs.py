"""The fleet's two JSONL streams, byte for byte.

``FleetResult.write_outputs`` fills line templates from the lanes' columns;
the oracle here is the plain writer it replaced: ``json.dumps`` of each
``records(i)`` entry for ``{name}-output.jsonl``, and ``json.dumps`` of each
event's dict plus the summary line for ``{name}-bench.jsonl``.  Cases cover
both dispatch cost classes, rejected jobs, ids that JSON escapes, a
failure schedule (the summary's ``failures`` block) and device telemetry.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.cluster import FailureInjector
from repro.core.job import JobFactory
from repro.fleet import (ALLOC_BF, ALLOC_FF, SCHED_EBF, SCHED_FIFO,
                         FleetRunner)
from repro.fleet import runner as runner_mod
from repro.workloads.synthetic import SyntheticWorkload

SYS = {"groups": {"a": {"core": 4, "mem": 1024}, "b": {"core": 8, "mem": 2048}},
       "nodes": {"a": 6, "b": 4}}
RSS_MB = 123.456789


def _workload(n=120, seed=11):
    return list(SyntheticWorkload(
        n, seed=seed, mean_interarrival_s=25.0, duration_median_s=900.0,
        duration_sigma=1.1, node_weights={1: 0.5, 2: 0.3, 4: 0.2},
        resources={"core": (1, 4), "mem": (64, 1024)}))


def _with_rejects():
    recs = _workload()
    # larger than any node, and more nodes than the machine has
    recs[5] = dict(recs[5], requested_resources={"core": 16, "mem": 64})
    recs[9] = dict(recs[9], requested_nodes=11)
    # a zero request, which ``resources`` leaves out
    recs[12] = dict(recs[12], requested_resources={"core": 2, "mem": 0})
    return recs


def _with_escaped_ids():
    recs = _workload()
    for k, jid in enumerate(['q"uote', "back\\slash", "café", "tab\t",
                             "漢字", "del\x7f"]):
        recs[k] = dict(recs[k], id=jid)
    return recs


def _injector():
    return FailureInjector(10, mtbf_s=4000.0, repair_s=900.0,
                           horizon_s=6000, seed=3)


CASES = {
    "fifo-ff": (_workload, SCHED_FIFO, ALLOC_FF, {}),
    "ebf-bf": (_workload, SCHED_EBF, ALLOC_BF, {}),
    "rejected": (_with_rejects, SCHED_EBF, ALLOC_FF, {}),
    "escaped-ids": (_with_escaped_ids, SCHED_FIFO, ALLOC_BF, {}),
    "failures": (_workload, SCHED_FIFO, ALLOC_FF,
                 dict(failures=_injector(), quarantine_s=1800,
                      ckpt_every_s=600)),
    "telemetry": (_workload, SCHED_EBF, ALLOC_FF,
                  dict(telemetry_stride=7)),
}


def _old_jobs_file(result, i):
    return b"".join(json.dumps(r).encode() + b"\n" for r in result.records(i))


def _old_bench_file(result, i):
    summ = result.summary(i)
    f = result.finals[i]
    n_events = int(f.n_events)
    dispatch_amort = summ["dispatch_time_s"] / max(n_events, 1)
    out = []
    for e in range(n_events):
        out.append(json.dumps({
            "t": int(np.asarray(f.log_t)[e]),
            "queue": int(np.asarray(f.log_queue)[e]),
            "running": int(np.asarray(f.log_running)[e]),
            "dispatch_s": dispatch_amort,
            "kernel_launches": 0,
            "rss_mb": RSS_MB,
        }).encode() + b"\n")
    out.append(json.dumps({"summary": summ}).encode() + b"\n")
    return b"".join(out)


@pytest.fixture(scope="module")
def results():
    """Every case, in one ``run``."""
    sims = [FleetRunner.build(name, make(), SYS, sc, alloc_id=ac,
                              job_factory=JobFactory(), **kw)
            for name, (make, sc, ac, kw) in CASES.items()]
    res = FleetRunner().run(sims)
    return {name: (res, i) for i, name in enumerate(CASES)}


@pytest.mark.parametrize("case", list(CASES))
def test_streams_are_byte_identical_to_json_dumps(results, case, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(runner_mod, "rss_mb", lambda: RSS_MB)
    result, i = results[case]
    out_path, bench_path = result.write_outputs(str(tmp_path), i)
    with open(out_path, "rb") as fh:
        assert fh.read() == _old_jobs_file(result, i)
    with open(bench_path, "rb") as fh:
        assert fh.read() == _old_bench_file(result, i)
    assert os.path.basename(out_path) == f"{case}-output.jsonl"


def test_cases_reach_what_they_name(results):
    recs = {k: results[k][0].records(results[k][1]) for k in CASES}
    assert any(r["state"] == "REJECTED" for r in recs["rejected"])
    assert any(r["resources"] == {"core": 2} for r in recs["rejected"])
    assert {'q"uote', "back\\slash", "café"} <= {
        r["id"] for r in recs["escaped-ids"]}
    assert "failures" in results["failures"][0].summary(results["failures"][1])
    assert "telemetry" in results["telemetry"][0].summary(
        results["telemetry"][1])


def test_kernel_launches_follow_the_queue_per_line(results, tmp_path,
                                                  monkeypatch):
    """The fleet launches no kernel, so every line reads 0, on a log with
    negative queue entries too, as the oracle does."""
    monkeypatch.setattr(runner_mod, "rss_mb", lambda: RSS_MB)
    result, i = results["ebf-bf"]
    f = result.finals[i]
    queue = np.asarray(f.log_queue).copy()
    queue[1:int(f.n_events):3] = -1
    result = dataclasses.replace(
        result, finals=[f._replace(log_queue=queue)], sims=[result.sims[i]])
    _, bench_path = result.write_outputs(str(tmp_path), 0)
    with open(bench_path, "rb") as fh:
        written = fh.read()
    assert written == _old_bench_file(result, 0)
    launches = [json.loads(ln).get("kernel_launches")
                for ln in written.splitlines()[:-1]]
    assert set(launches) == {0}


@pytest.mark.parametrize("case", ["fifo-ff", "rejected"])
def test_write_spans_count_the_lines(results, case, tmp_path):
    result, i = results[case]
    result.write_outputs(str(tmp_path), i)
    spans = result.spans.spans
    jobs = [s for s in spans if s.name == "results.jobs_file"][-1]
    events = [s for s in spans if s.name == "results.events_file"][-1]
    with open(tmp_path / f"{case}-output.jsonl") as fh:
        assert jobs.attrs["lines"] == len(fh.readlines()) == len(
            result.records(i))
    with open(tmp_path / f"{case}-bench.jsonl") as fh:
        assert events.attrs["lines"] == len(fh.readlines()) == int(
            result.finals[i].n_events) + 1
