"""Metrics read from the program's own spans (``program_span``): each
reader on records made by hand through the program's tracer on a pinned
clock, each returning None for a run without records, and the span tool
on the CPU at a tiny size, its records read back through the same
readers."""
import time
import types
from pathlib import Path

import pytest

from bench.harness import core
from repro.obs import Tracer

DATA = Path(__file__).parent / "data"
SEED = 2 ** 40 + 777


def reader(name):
    return core.load_module(core.BENCH / "metrics" / f"{name}.py",
                            "bench_metric_" + name)


def at(tr, t):
    tr.set_time(t)
    return tr


def serve_records():
    """Two ticks: r0 submitted at 1.0 and admitted at 1.5 (its prefill's
    first token waited on for 2 ms, the step's tokens for 3 ms, of a 10 ms
    tick), r1 submitted at 1.2 and admitted at 1.6 (a 4 ms tick with a
    1 ms wait)."""
    tr = Tracer()
    at(tr, 1.0).event("submit", rid="r0")
    at(tr, 1.2).event("submit", rid="r1")
    tick = at(tr, 1.5).span("engine_tick")
    admit = tr.span("admit", rid="r0")
    wait = at(tr, 1.501).span("first_token_wait")
    at(tr, 1.503)
    wait.finish()
    admit.finish()
    with at(tr, 1.504).span("token_wait"):
        at(tr, 1.507)
    at(tr, 1.510)
    tick.finish()
    tick = at(tr, 1.6).span("engine_tick")
    with tr.span("admit", rid="r1"):
        pass
    with at(tr, 1.601).span("token_wait"):
        at(tr, 1.602)
    at(tr, 1.604)
    tick.finish()
    return tr.records


def plan_records():
    """Two plans: the first 1.0 s long with measures over 0.1-0.3 s (two
    compile stages of 0.1 s) and 0.5-0.6 s inside a verification (a cache
    load of 0.05 s inside a compile stage of 0.08 s); the second 0.5 s
    long with one 0.1 s measure and no compile.  A compile stage outside
    any measure is not the candidates'."""
    tr = Tracer()
    plan = at(tr, 0.0).span("offload")
    with at(tr, 0.1).span("measure"):
        with tr.span("first_call"):
            at(tr, 0.2).event("compile", stage="trace", seconds=0.1)
            at(tr, 0.3).event("compile", stage="compile", seconds=0.1)
    at(tr, 0.4).event("compile", stage="trace", seconds=0.05)
    verify = tr.span("verify")
    with at(tr, 0.5).span("measure"):
        at(tr, 0.55).event("compile", stage="cache_load", seconds=0.05)
        at(tr, 0.58).event("compile", stage="compile", seconds=0.08)
        at(tr, 0.6)
    verify.finish()
    at(tr, 1.0)
    plan.finish()
    plan = at(tr, 2.0).span("offload")
    with at(tr, 2.1).span("measure"):
        at(tr, 2.2)
    at(tr, 2.5)
    plan.finish()
    return tr.records


@pytest.mark.parametrize("name,records,want", [
    ("queue_wait_ms", serve_records, 450.0),
    ("tick_host_ms", serve_records, 4.0),
    ("compile_s_per_candidate", plan_records, (0.2 + 0.08) / 3),
    ("planner_host_s_per_plan", plan_records, (0.7 + 0.4) / 2),
])
def test_span_metric_reads_the_records(name, records, want):
    run = types.SimpleNamespace(obs_records=records())
    assert reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["queue_wait_ms", "tick_host_ms",
                                  "compile_s_per_candidate",
                                  "planner_host_s_per_plan"])
def test_span_metric_is_none_without_records(name):
    assert reader(name).read(types.SimpleNamespace()) is None
    assert reader(name).read(types.SimpleNamespace(obs_records=None)) \
        is None


@pytest.mark.parametrize("workload,names", [
    ("serve.tiny.chat", ("queue_wait_ms", "tick_host_ms")),
    ("plan.tdfir-small", ("compile_s_per_candidate",
                          "planner_host_s_per_plan")),
])
def test_the_span_tool_reads_the_window(workload, names, tmp_path):
    tool = core.load_module(core.BENCH / "tools" / "spans.py",
                            "bench_tool_spans")
    kind = core.load_kind("serve" if "serve" in workload else "plan")
    host_spans = kind.HOST_SPANS
    out, run = tool.run_with_spans(
        workload, SEED, 2.0, True, t_start=time.perf_counter(),
        bench_file=DATA / "BENCHMARK.json", dirs=(DATA, core.BENCH),
        require_chip=False, cache_dir=None, trace_dir=tmp_path)
    assert kind.HOST_SPANS == host_spans          # put back
    assert out["correct"] is True and list(out)[-1] == "checks"
    assert set(out["span_metrics"]) == set(names)
    assert all(v is not None and v >= 0
               for v in out["span_metrics"].values())
    if workload.startswith("plan"):
        # the offload span is the plan: within 2% of plan_s
        plan_s = out["metrics"]["plan_s"]["value"]
        assert out["spans"]["offload"]["mean_s"] == pytest.approx(
            plan_s, rel=0.02)
        assert out["spans"]["offload"]["n"] == len(run.plans)
    else:
        assert out["spans"]["engine_tick"]["n"] == len(run.tick_s)
