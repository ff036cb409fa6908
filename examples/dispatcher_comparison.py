"""Paper §7 case study — 8 dispatchers (4 schedulers × 2 allocators) on a
Seth-like workload via the experimentation tool (Fig. 5), producing the
comparative plots of Figs. 10-13.

    PYTHONPATH=src python examples/dispatcher_comparison.py [n_jobs]

Pass ``--vectorized`` to additionally run the batched
DispatchContext/DispatchPlan engines (one ``alloc_score_batch`` Pallas
launch per event — see DESIGN.md §1-2) and report their kernel-launch
economy next to the numpy baselines.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core.dispatchers import (BestFit, EasyBackfilling, FirstFit,
                                    FirstInFirstOut, LongestJobFirst,
                                    ShortestJobFirst)
from repro.experimentation import Experiment
from benchmarks.common import SETH, seth_jobs


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    vectorized = "--vectorized" in sys.argv
    n = int(args[0]) if args else 4000
    exp = Experiment("dispatcher_comparison", list(seth_jobs(n, seed=7)),
                     SETH, output_dir="results")
    exp.gen_dispatchers(
        [FirstInFirstOut, ShortestJobFirst, LongestJobFirst, EasyBackfilling],
        [FirstFit, BestFit])
    if vectorized:
        from repro.core.dispatchers.vectorized import (
            VectorizedAllocator, VectorizedEasyBackfilling)
        exp.add_dispatcher(FirstInFirstOut(VectorizedAllocator("FF")))
        exp.add_dispatcher(FirstInFirstOut(VectorizedAllocator("BF")))
        exp.add_dispatcher(
            VectorizedEasyBackfilling(VectorizedAllocator("FF")))
    results = exp.run_simulation()
    table = {k: {"cpu_s": round(v["summaries"][0]["cpu_time_s"], 2),
                 "dispatch_s": round(v["summaries"][0]["dispatch_time_s"], 2),
                 "kernel_launches_per_event": round(
                     v["summaries"][0]["kernel_launches_per_event"], 2),
                 "makespan": v["summaries"][0]["sim_end_time"]}
             for k, v in results.items()}
    print(json.dumps(table, indent=1))
    print("plots under results/dispatcher_comparison/")


if __name__ == "__main__":
    main()
