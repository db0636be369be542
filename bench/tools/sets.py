"""Measure a cell's spread: runs of ``bench/run.py``, one process each, as a
check makes them, and the spread of every metric.

    python bench/tools/sets.py --workload <name> --seeds 1,2,3,4,5,6 \
        [--seconds <s>] [--trace 0|1] [--out runs.jsonl]

Each run's result line goes to ``--out`` (one JSON object per line, with
its seed and the process's wall time).  At the end one line per metric
gives the median and the spread: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) over the median.  Run it
on the chip.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    rows = []
    for seed in args.seeds.split(","):
        t = time.perf_counter()
        p = subprocess.run(
            [sys.executable, str(ROOT / "bench" / "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", str(seconds), "--trace", str(args.trace)],
            cwd=ROOT, capture_output=True, text=True)
        lines = [x for x in p.stdout.splitlines() if x.startswith("{")]
        if p.returncode or not lines:
            print(json.dumps({"seed": seed, "rc": p.returncode,
                              "stderr": p.stderr[-2000:]}), flush=True)
            continue
        row = dict(json.loads(lines[-1]), seed=seed,
                   wall_s=time.perf_counter() - t)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    names = sorted({k for r in rows for k in r["metrics"]})
    for name in names:
        vals = [r["metrics"][name]["value"] for r in rows
                if name in r["metrics"]]
        if len(vals) >= 2:
            q1, q2, q3 = statistics.quantiles(vals, n=4)
            print(json.dumps({"metric": name, "n": len(vals),
                              "median": statistics.median(vals),
                              "spread": (q3 - q1) / q2 if q2 else None,
                              "values": vals}), flush=True)


if __name__ == "__main__":
    main()
