"""Run one cell with the program's own spans on the profiler's clock, and
read the metrics of those spans.

    python3 bench/tools/spans.py --workload <name> --seed <n> \
        [--seconds <s>] [--trace 0|1] [--records <out.jsonl>]

The run is ``bench/run.py``'s, with two additions: the program's ambient
tracer is ``repro.obs.profiler_tracer()`` from start to end, and a traced
run keeps the program's host spans (``PROGRAM_SPANS``) beside the cell
kind's own, so each gap in ``breakdown.idle_gaps`` is named by the
innermost program span around it.  The measured window's records are
handed to the readers as ``run.obs_records``.  The last line of standard
output is the result line, with every metric of the cell (end-to-end and
per-layer, whatever ``--trace``), plus:

  * ``span_metrics`` — the readers in ``SPAN_METRICS``;
  * ``spans`` — per span name in the window: count, total and mean seconds;
  * ``host_names`` (traced runs) — per program span name, its events on
    the traced host plane beside its records in the traced slice: more
    events than records means a runtime name equal to the program's.

``--records`` writes the window's records as JSONL.  Run it on the chip,
one process per run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]

PROGRAM_SPANS = {
    "serve": ("engine_tick", "admit", "prefill", "first_token_wait",
              "insert", "decode", "token_wait", "emit"),
    "plan": ("offload", "verify", "measure", "first_call", "repeats",
             "compare"),
}
SPAN_METRICS = {
    "serve": ("queue_wait_ms", "tick_host_ms"),
    "plan": ("compile_s_per_candidate", "planner_host_s_per_plan"),
}


def start_s(rec):
    return rec["t0"] if rec["type"] == "span" else rec["t"]


def span_table(recs):
    out = {}
    for r in recs:
        if r["type"] == "span":
            row = out.setdefault(r["name"], {"n": 0, "total_s": 0.0})
            row["n"] += 1
            row["total_s"] += r["t1"] - r["t0"]
    for row in out.values():
        row["mean_s"] = row["total_s"] / row["n"]
    return out


def run_with_spans(workload, seed, seconds, trace, *, t_start,
                   bench_file=None, dirs=None, **kw):
    """``core.run_cell`` under the program's profiler-clock tracer (see
    the module docstring); returns the result line and the run."""
    from bench.harness import core
    from bench.harness import trace as tr
    from repro.obs import profiler_tracer, use_tracer

    bench_file = bench_file or ROOT / "BENCHMARK.json"
    dirs = dirs or (core.BENCH,)
    bench = core.load_json(bench_file)
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    kind_name = core.load_json(
        core.find(dirs, "traffic", f"{wl['traffic']}.json"))["kind"]
    kind = core.load_kind(kind_name, dirs)
    program = PROGRAM_SPANS[kind_name]
    host_counts = collections.Counter()
    load_xplane, host_spans = tr.load_xplane, kind.HOST_SPANS

    def counting_load(trace_dir, host_names, **kw):
        import glob
        import os
        import jax
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if paths:
            data = jax.profiler.ProfileData.from_file(paths[-1])
            for plane in data.planes:
                if plane.name == tr.HOST_PLANE:
                    for line in plane.lines:
                        for e in line.events:
                            if e.name in program:
                                host_counts[e.name] += 1
        return load_xplane(trace_dir, host_names, **kw)

    tracer = profiler_tracer()
    tr.load_xplane, kind.HOST_SPANS = counting_load, host_spans + program
    try:
        with use_tracer(tracer):
            out, run = core.run_cell(workload, seed, seconds, trace,
                                     bench_file=bench_file, dirs=dirs,
                                     t_start=t_start, **kw)
    finally:
        tr.load_xplane, kind.HOST_SPANS = load_xplane, host_spans
    w0 = t_start + run.setup_s
    w1 = w0 + run.window_s
    run.obs_records = [r for r in tracer.records if w0 <= start_s(r) <= w1]

    def read(name):
        reader = core.load_module(
            core.find(dirs, "metrics", f"{name}.py"),
            "bench_metric_" + name.replace(".", "_"))
        return reader.read(run)

    for m in (core.metric_names(bench, workload, False)
              + core.metric_names(bench, workload, True)):
        v = None if m["name"] in out["metrics"] else read(m["name"])
        if v is not None:
            out["metrics"][m["name"]] = {"value": float(v),
                                         "unit": m["unit"]}
    out["span_metrics"] = {n: read(n) for n in SPAN_METRICS[kind_name]}
    out["spans"] = span_table(run.obs_records)
    if run.traced is not None:
        a, b = (w0 + s for s in run.traced)
        in_slice = collections.Counter(
            r["name"] for r in run.obs_records
            if r["type"] == "span" and a <= r["t0"] <= b)
        out["host_names"] = {n: [host_counts[n], in_slice[n]]
                             for n in program}
    out["checks"] = out.pop("checks")          # last in the line
    return out, run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--records", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import core

    seconds = args.seconds or core.load_json(
        ROOT / "BENCHMARK.json")["run_seconds"]
    out, run = run_with_spans(args.workload, args.seed, seconds,
                              bool(args.trace), t_start=T_START)
    if args.records:
        from repro.obs import write_jsonl
        write_jsonl(run.obs_records, args.records)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
