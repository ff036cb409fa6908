"""Experimentation tools: Experiment automation, PlotFactory, metrics,
and the HLO cost analyzer's known-cost validation."""
import json
import os
import random

import pytest

from repro.core import Job
from repro.core.dispatchers import (BestFit, FirstFit, FirstInFirstOut,
                                    ShortestJobFirst)
from repro.experimentation import Experiment, PlotFactory, metrics
from repro.workloads.synthetic import SyntheticWorkload

SYS = {"groups": {"compute": {"core": 4, "mem": 1024}}, "nodes": {"compute": 8}}


def make_jobs(n=120, seed=2):
    rng = random.Random(seed)
    return [Job(id=str(i), user_id=1, submission_time=i * 11,
                duration=rng.randint(10, 400),
                expected_duration=rng.randint(10, 500),
                requested_nodes=rng.randint(1, 2),
                requested_resources={"core": rng.randint(1, 4),
                                     "mem": rng.randint(64, 512)})
            for i in range(n)]


def test_experiment_cross_product_and_plots(tmp_path):
    exp = Experiment("exp1", make_jobs(), SYS, output_dir=str(tmp_path))
    exp.gen_dispatchers([FirstInFirstOut, ShortestJobFirst],
                        [FirstFit, BestFit])
    assert len(exp.dispatchers) == 4
    results = exp.run_simulation(produce_plots=True)
    assert set(results) == {"FIFO-FF", "FIFO-BF", "SJF-FF", "SJF-BF"}
    for kind in ("slowdown", "queue_size", "dispatch_time"):
        assert os.path.exists(os.path.join(str(tmp_path), "exp1",
                                           f"plot_{kind}.png"))
    assert os.path.exists(os.path.join(str(tmp_path), "exp1",
                                       "summaries.json"))


def test_metrics_pipeline(tmp_path):
    exp = Experiment("exp2", make_jobs(80), SYS, output_dir=str(tmp_path))
    exp.gen_dispatchers([FirstInFirstOut], [FirstFit])
    res = exp.run_simulation(produce_plots=False)
    out = res["FIFO-FF"]["output"]
    bench = res["FIFO-FF"]["bench"]
    sl = metrics.slowdowns(out)
    assert len(sl) == 80 and all(s >= 1.0 for s in sl)
    series = metrics.bench_series(bench)
    assert series["summary"]["completed"] == 80
    pts = metrics.dispatch_time_by_queue_size(bench)
    assert pts and all(c > 0 for _, _, c in pts)
    pct = metrics.percentiles(sl)
    assert pct["p50"] <= pct["p95"] <= pct["max"]


def test_batch_planner_partitions_fleet_vs_host(tmp_path):
    """Compilable grid rows lower onto the fleet engine, the rest run on
    the host — every summary tagged with ``engine`` AND
    ``fallback_reason`` — and the per-repeat seeds are ``base_seed +
    rep`` for synthetic workloads."""

    class TweakedFIFO(FirstInFirstOut):
        """Subclass -> not exactly FirstInFirstOut -> host only."""
        name = "TFIFO"

    wl = SyntheticWorkload(60, seed=40, mean_interarrival_s=30.0,
                           duration_median_s=400.0,
                           resources={"core": (1, 4), "mem": (64, 512)})
    exp = Experiment("mix", wl, SYS, output_dir=str(tmp_path), repeats=2)
    exp.gen_dispatchers([FirstInFirstOut, ShortestJobFirst],
                        [FirstFit, BestFit])
    exp.add_dispatcher(TweakedFIFO(FirstFit()))
    res = exp.run_simulation(produce_plots=False)
    assert set(res) == {"FIFO-FF", "FIFO-BF", "SJF-FF", "SJF-BF",
                        "TFIFO-FF"}
    for name, entry in res.items():
        engines = {s["engine"] for s in entry["summaries"]}
        reasons = {s["fallback_reason"] for s in entry["summaries"]}
        # the full FIFO/SJF x FF/BF product is now compilable; only the
        # subclassed dispatcher falls back, with its reason recorded
        if name == "TFIFO-FF":
            assert engines == {"host"}, (name, engines)
            assert reasons == {"non-compilable-dispatcher"}
        else:
            assert engines == {"fleet"}, (name, engines)
            assert reasons == {None}
        assert [s["seed"] for s in entry["summaries"]] == [40, 41]
        assert os.path.exists(entry["output"])
        assert os.path.exists(entry["bench"])
    # reseeded repeats draw independent streams -> different end times
    ends = [s["sim_end_time"] for s in res["FIFO-FF"]["summaries"]]
    assert ends[0] != ends[1]
    with open(os.path.join(str(tmp_path), "mix", "summaries.json")) as fh:
        assert set(json.load(fh)) == set(res)


def test_fallback_reason_reports_host_only_knobs(tmp_path):
    """Host-only run knobs are named in ``fallback_reason`` instead of
    silently degrading the whole grid to the host engine."""
    wl = SyntheticWorkload(40, seed=11, mean_interarrival_s=30.0,
                           duration_median_s=300.0,
                           resources={"core": (1, 4), "mem": (64, 512)})
    exp = Experiment("knob", wl, SYS, output_dir=str(tmp_path),
                     use_fleet=False)
    exp.gen_dispatchers([FirstInFirstOut], [FirstFit])
    res = exp.run_simulation(produce_plots=False)
    s = res["FIFO-FF"]["summaries"][0]
    assert s["engine"] == "host"
    assert s["fallback_reason"] == "fleet-disabled"

    exp2 = Experiment("knob2", wl, SYS, output_dir=str(tmp_path))
    exp2.gen_dispatchers([FirstInFirstOut], [FirstFit])
    res2 = exp2.run_simulation(produce_plots=False,
                               start_kwargs={"max_events": 10 ** 9})
    s2 = res2["FIFO-FF"]["summaries"][0]
    assert s2["engine"] == "host"
    assert s2["fallback_reason"] == "custom-start-kwargs"


def test_batch_planner_fleet_and_host_agree(tmp_path):
    """Same grid row through both engines -> identical simulation
    outcome (counters + end time), so the planner's engine choice is
    invisible to experiment results."""
    wl = SyntheticWorkload(60, seed=40, mean_interarrival_s=30.0,
                           duration_median_s=400.0,
                           resources={"core": (1, 4), "mem": (64, 512)})
    out, launches = {}, {}
    for flag in (True, False):
        exp = Experiment(f"uf{flag}", wl, SYS, output_dir=str(tmp_path),
                         use_fleet=flag)
        exp.gen_dispatchers([ShortestJobFirst], [FirstFit])
        out[flag] = exp.run_simulation(produce_plots=False)[
            "SJF-FF"]["summaries"][0]
        launches[flag] = exp.fleet_launches
    # the fleet run reports its one launch; the host run launched nothing
    assert [ln["n_sims"] for ln in launches[True]] == [1]
    assert launches[False] == []
    assert out[True]["engine"] == "fleet"
    assert out[False]["engine"] == "host"
    for key in ("submitted", "completed", "rejected", "sim_end_time"):
        assert out[True][key] == out[False][key], key


def test_plot_factory_group_validation(tmp_path):
    pf = PlotFactory("decision", SYS)
    with pytest.raises(ValueError):
        pf.produce_plot("dispatch_time")   # performance plot, wrong group

