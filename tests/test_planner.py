"""Mixed-destination planner: six verifications, ordering, early stop,
residual rule (paper §II.C)."""
import pytest

from repro.apps import APPS
from repro.core.destinations import VERIFICATION_ORDER
from repro.core.ga import GAConfig
from repro.core.measure import TimedRunner
from repro.core.planner import UserTarget, plan_offload


@pytest.fixture(scope="module")
def tdfir_report():
    app = APPS["tdFIR"]()
    return plan_offload(
        app, UserTarget(),
        inputs=app.make_inputs(0, small=True),
        runner=TimedRunner(repeats=1),
        ga_cfg=GAConfig(population=3, generations=3, seed=0))


def test_verification_order_is_papers(tdfir_report):
    methods = [(r.paper_analogue, r.method) for r in tdfir_report.records]
    want = [(d.paper_analogue, m) for d, m in VERIFICATION_ORDER]
    assert methods == want[:len(methods)]
    # FB verifications strictly before loop verifications
    kinds = [r.method for r in tdfir_report.records]
    if "loop" in kinds:
        assert kinds.index("loop") >= kinds.count("function_block")


def test_all_six_run_without_target(tdfir_report):
    assert len(tdfir_report.records) == 6
    assert not tdfir_report.early_stopped
    assert tdfir_report.selected is not None


def test_early_stop_on_met_target():
    app = APPS["tdFIR"]()
    report = plan_offload(
        app, UserTarget(target_speedup=0.1),    # trivially met
        inputs=app.make_inputs(0, small=True),
        runner=TimedRunner(repeats=1),
        ga_cfg=GAConfig(population=3, generations=3, seed=0))
    assert report.early_stopped
    assert len(report.records) < 6


def test_price_constraint_blocks_early_stop():
    app = APPS["tdFIR"]()
    report = plan_offload(
        app, UserTarget(target_speedup=0.1, max_price=0.5),  # price never ok
        inputs=app.make_inputs(0, small=True),
        runner=TimedRunner(repeats=1),
        ga_cfg=GAConfig(population=3, generations=3, seed=0))
    assert not report.early_stopped
    assert len(report.records) == 6


def test_residual_rule_pins_fb_choice(tdfir_report):
    """After FB offload succeeds, loop searches keep the FB nest pinned."""
    fb = [r for r in tdfir_report.records if r.method == "function_block"
          and r.best_time_s < float("inf")]
    loops = [r for r in tdfir_report.records if r.method == "loop"]
    if fb and loops:
        best_fb = min(fb, key=lambda r: r.best_time_s)
        if best_fb.best_time_s < tdfir_report.ref_time_s:
            pinned = next(iter(best_fb.choice))
            for r in loops:
                assert r.choice.get(pinned) == best_fb.choice[pinned]


def test_selected_is_fastest(tdfir_report):
    finite = [r for r in tdfir_report.records
              if r.best_time_s < float("inf")]
    assert tdfir_report.selected.best_time_s == \
        min(r.best_time_s for r in finite)


def test_candidate_compile_error_stays_on_record():
    """A candidate whose build raises takes the paper's penalty, and its
    error stays visible on the VerificationRecord."""
    import jax.numpy as jnp

    from repro.core.offloadable import LoopNest, OffloadableApp

    def broken(state):
        raise ValueError("kernel refused by the compiler")

    def double(state):
        return dict(state, out=state["x"] * 2.0)

    app = OffloadableApp(
        name="broken-kernel",
        nests=[LoopNest("scale", {"seq": double, "dp": double,
                                  "pallas": broken})],
        make_inputs=lambda seed=0, small=False: {
            "x": jnp.arange(8 if small else 64, dtype=jnp.float32)})
    report = plan_offload(app, UserTarget(), runner=TimedRunner(repeats=1),
                          ga_cfg=GAConfig(population=2, generations=2,
                                          seed=0))
    fpga = [r for r in report.records
            if r.paper_analogue == "FPGA" and r.method == "loop"]
    assert len(fpga) == 1
    assert not fpga[0].correct
    assert "kernel refused by the compiler" in fpga[0].error
    # the working destinations carry no error and one of them is selected
    assert report.selected is not None and not report.selected.error
    assert all(not r.error for r in report.records
               if r.paper_analogue != "FPGA")


def test_plan_spans_cover_the_reference_and_split_each_measurement():
    """``offload`` spans the whole call, the reference measurement
    included; each ``measure`` splits into ``first_call``, ``repeats`` and
    (for a candidate) ``compare``; a candidate that fails to build stops
    in ``first_call``."""
    import jax.numpy as jnp

    from repro.core.offloadable import LoopNest, OffloadableApp
    from repro.obs import Tracer, use_tracer

    def broken(state):
        raise ValueError("kernel refused by the compiler")

    def double(state):
        return dict(state, out=state["x"] * 2.0)

    app = OffloadableApp(
        name="spans",
        nests=[LoopNest("scale", {"seq": double, "dp": double,
                                  "pallas": broken})],
        make_inputs=lambda seed=0, small=False: {
            "x": jnp.arange(8 if small else 64, dtype=jnp.float32)})
    tr = Tracer()
    with use_tracer(tr):
        report = plan_offload(app, UserTarget(),
                              runner=TimedRunner(repeats=2),
                              ga_cfg=GAConfig(population=2, generations=2,
                                              seed=0))
    spans = {r["id"]: r for r in tr.records if r["type"] == "span"}
    kids = {}
    for r in sorted(spans.values(), key=lambda r: r["id"]):
        kids.setdefault(r["parent"], []).append(r["name"])
    offload, = [s for s in spans.values() if s["name"] == "offload"]
    assert offload["parent"] is None
    assert offload["attrs"]["ref_time_s"] == report.ref_time_s
    assert offload["attrs"]["n_verifications"] == len(report.records)
    measures = [s for s in spans.values() if s["name"] == "measure"]
    ref, = [m for m in measures if m["attrs"]["reference"]]
    assert ref["parent"] == offload["id"]
    assert offload["t0"] <= ref["t0"] and ref["t1"] <= offload["t1"]
    assert kids[ref["id"]] == ["first_call", "repeats"]
    seen = set()
    for m in measures:
        assert m["t0"] >= offload["t0"] and m["t1"] <= offload["t1"]
        if m is ref:
            continue
        if m["attrs"]["correct"]:
            assert kids[m["id"]] == ["first_call", "repeats", "compare"]
            seen.add("ok")
        else:
            assert kids[m["id"]] == ["first_call"]
            seen.add("broken")
    assert seen == {"ok", "broken"}
