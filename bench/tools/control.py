"""Read a cell's check on sound runs and on its control, on the chip.

    python bench/tools/control.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 30]

In one process, for each seed: one run of the cell exactly as
``bench/run.py`` makes it (its check reads the sound program), and for
each of ``--control-seeds`` (default: every seed) the cell kind's
``control`` on that run's own work: the plain reference computed one
precision below the configuration's, put in the program's place, read by
the same number and checked at the configuration's limit.  Per seed, one
JSON line holds both verdicts in the result line's form; the control's
has to read ``"correct": false``.  A cell's limit lies above every sound
reading and below every control reading (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default=None)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import core

    bench = core.load_json(ROOT / "BENCHMARK.json")
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = (seeds if args.control_seeds is None else
                [int(s) for s in args.control_seeds.split(",")])
    for seed in seeds:
        out, run = core.run_cell(args.workload, seed, seconds, False,
                                 t_start=time.perf_counter())
        row = {"workload": args.workload, "seed": seed,
               "sound": {k: out[k] for k in ("correct", "attempted",
                                             "failed", "checks")},
               "metrics": out["metrics"],
               "checked_tokens": getattr(run, "checked_tokens", None)}
        if seed in controls:
            t = time.perf_counter()
            drv = core.load_kind(run.traffic["kind"])
            row["control"] = core.verdict(drv.control(run))
            row["control_s"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
