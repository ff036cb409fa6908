"""The plain reference and the copied generator against the program,
at sizes a CPU test can hold.  The benchmark's check never runs the
program's host engine; these tests show that the reference follows the
same semantics."""
import json
import os

import pytest

import run
from generators.daily_cycle import workload
from reference import simulate

ROWS = ["FIFO-FF", "FIFO-BF", "SJF-FF", "SJF-BF", "LJF-FF", "LJF-BF",
        "EBF-FF", "EBF-BF"]


# cells by their configuration and traffic files; ricc's files stay for
# the cell that comes back once Best-Fit's node order is exact
CELLS = {"seth.table2": ("seth", "seth_log_rate"),
         "ricc.table2": ("ricc", "ricc_log_rate")}


def _cell(name):
    config, traffic = CELLS[name]
    with open(os.path.join(run.BENCH, "configs", config + ".json")) as fh:
        machine = json.load(fh)
    with open(os.path.join(run.BENCH, "traffic", traffic + ".json")) as fh:
        mix = json.load(fh)
    return run.Cell({"name": name, "machine_config": machine,
                     "traffic_mix": mix})


def test_generator_is_a_copy_of_seth_jobs():
    """With seth_jobs' own constants the generator is seth_jobs."""
    from benchmarks.common import seth_jobs

    cell = _cell("seth.table2")
    params = {k: v for k, v in cell.params.items() if k != "mem_per_core_mb"}
    params.update(day_interarrival_s=55.0, night_interarrival_s=240.0,
                  mem_mb=[128, 256, 512, 1024])
    for seed in (0, 5, 2 ** 31 + 17):
        got = list(workload(500, seed, 4, params))
        want = list(seth_jobs(500, seed=seed))
        assert [(g["id"], g["submit"], g["duration"], g["expected_duration"],
                 g["requested_nodes"], g["requested_resources"], g["user"])
                for g in got] == \
            [(w.id, w.submission_time, w.duration, w.expected_duration,
              w.requested_nodes, w.requested_resources, w.user_id)
             for w in want]


@pytest.mark.parametrize("cell_name,n_jobs,log_jobs,log_days", [
    ("seth.table2", 8192, 202871, 1311), ("ricc.table2", 32768, 447794, 153)])
def test_arrivals_follow_the_logs_rate(cell_name, n_jobs, log_jobs, log_days):
    """Jobs a day within 3% of the log's mean, over lanes of some ten
    days each (a RICC lane of the cell's 2,048 jobs spans under a day,
    most of it by day)."""
    cell = _cell(cell_name)
    days = jobs = 0
    for seed in range(6):
        lane = list(workload(n_jobs, 2 ** 31 + seed, cell.cores, cell.params))
        days += lane[-1]["submit"] / 86400
        jobs += len(lane)
    assert jobs / days == pytest.approx(log_jobs / log_days, rel=0.03)


@pytest.mark.parametrize("cell_name", ["seth.table2", "ricc.table2"])
def test_memory_is_any_whole_mb_up_to_the_cores_share(cell_name):
    cell = _cell(cell_name)
    per_core = cell.params["mem_per_core_mb"]
    lane = list(workload(2048, 7, cell.cores, cell.params))
    mem = [j["requested_resources"]["mem"] for j in lane]
    assert all(1 <= j["requested_resources"]["mem"]
               <= per_core * j["requested_resources"]["core"] for j in lane)
    # not confined to a few round sizes
    assert len(set(mem)) > 500
    assert sum(m % 128 != 0 for m in mem) > 0.9 * len(mem)


def test_every_seed_draws_its_own_jobs():
    cell = _cell("seth.table2")
    a, b = (list(workload(2048, seed, cell.cores, cell.params))
            for seed in (1, 2 ** 31 + 3))
    assert sorted(j["duration"] for j in a) != sorted(j["duration"] for j in b)


@pytest.mark.parametrize("cell_name,jobs,seed", [
    ("seth.table2", 700, 3), ("ricc.table2", 400, 2 ** 31 + 9)])
@pytest.mark.parametrize("row", ROWS)
def test_reference_equals_host_simulator(tmp_path, cell_name, jobs, seed,
                                         row):
    from repro.core.simulator import Simulator

    cell = _cell(cell_name)
    lane_jobs = list(workload(jobs, seed, cell.cores, cell.params))
    sim = Simulator(lane_jobs, cell.machine, run.make_dispatcher(row),
                    output_dir=str(tmp_path), name=row)
    out = sim.start_simulation()
    want = simulate(lane_jobs, cell.machine, row)
    got_jobs = {r["id"]: r for r in run.read_jsonl(out)}
    bench = run.read_jsonl(out.replace("-output", "-bench"))
    got_log = [(e["t"], e["queue"], e["running"]) for e in bench[:-1]]
    got = run.compare(got_jobs, got_log, [sim.summary], want)
    assert got == {"jobs_wrong": 0, "events_wrong": 0, "summary_wrong": 0,
                   "jobs_compared": jobs}
    assert want[2]["events"] > jobs


def test_control_departs_from_reference():
    """The control (events at whole minutes) must read wrong at the
    seth cells' own size, on jobs, events and the summary."""
    import control

    cell = _cell("seth.table2")
    got = control.control_reading(cell, seed=4, control="quantum60")
    assert got["jobs_wrong"] > 0 and got["events_wrong"] > 0
    assert got["summary_wrong"] > 0
