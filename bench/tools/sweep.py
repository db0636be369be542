"""Find a serve cell's knee: the cell run at rising arrival rates.

    python bench/tools/sweep.py --workload serve.granite-3-2b.chat \
        --rates 0.5,1,1.5,2 --seconds 51 --seeds 1

In one process, for each rate and seed, one run of the cell exactly as
``bench/run.py`` makes it, with the rate of its traffic file replaced, and
one JSON line: requests, TTFT median and 95th percentile, the 95th
percentile gap, the mean and largest admission wait, and how many requests
waited longer than a second for a slot.  The knee is the highest rate
whose admission waits stay bounded; a cell's rate is set below it once,
from this sweep, and fixed in its traffic file.  A sweep of another pool
or mix is a traffic file of its own.  Run it on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def sweep(workload, rates, seeds, seconds, **run_kw):
    """One row per (rate, seed): the run's load and its waits."""
    from bench.harness import core, stats
    dirs = run_kw.get("dirs", (core.BENCH,))
    bench = core.load_json(run_kw.get("bench_file", ROOT / "BENCHMARK.json"))
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    tr = core.load_json(core.find(dirs, "traffic", f"{wl['traffic']}.json"))
    gaps = core.load_module(core.find(dirs, "metrics", "itl_p95_ms.py"),
                            "bench_metric_itl_p95_ms")
    for rate in rates:
        for seed in seeds:
            _, run = core.run_cell(workload, seed, seconds, False,
                                   t_start=time.perf_counter(),
                                   traffic=dict(tr, rate_per_s=rate),
                                   **run_kw)
            reqs = run.requests
            ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
            waits = [r.admit_s - r.due_s for r in reqs
                     if r.admit_s is not None]
            yield {"workload": workload, "rate_per_s": rate, "seed": seed,
                   "requests": len(reqs), "first_tokens": len(ttft),
                   "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                   "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
                   "itl_p95_ms": gaps.read(run),
                   "admit_wait_mean_ms": 1e3 * stats.mean(waits),
                   "admit_wait_max_ms": 1e3 * max(waits),
                   "waited_over_1s": sum(w > 1.0 for w in waits),
                   "correct": run.correct}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for row in sweep(args.workload,
                     [float(r) for r in args.rates.split(",")],
                     [int(s) for s in args.seeds.split(",")], args.seconds):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
