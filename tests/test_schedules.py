"""repro.dist.schedules: tick-plan structure, the closed-form cost-model
terms pinned to the built plans, schedule execution on a 1-rank pod mesh,
fallback paths, and the GA searching the pipeline genes.

Multi-device grad equivalence for all three schedules lives in
tests/test_distributed.py; everything here runs in-process on 1 device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cost_model
from repro.core.ga import Evaluation, GAConfig, run_ga
from jax.sharding import AxisType
from repro.dist.plan import Plan
from repro.dist.schedules import (SCHEDULES, Schedule, get_schedule,
                                  register_schedule)


# ---------------------------------------------------------------- structure
def test_gpipe_plan_shape():
    plan = SCHEDULES["gpipe"].build(n_stages=4, n_ranks=4, microbatches=8)
    assert plan is not None
    assert plan.total_ticks == 8 + 4 - 1
    assert plan.busy_ticks == 8
    assert plan.bubble_ticks == 3
    assert plan.in_flight == 8                      # all m held to backward
    # drain ticks feed nothing (the mb[m-1] re-feed bug)
    for t in range(8, plan.total_ticks):
        assert plan.ticks[t].feed_mb == -1
        assert plan.ticks[t].feed_buf == -1


def test_one_f_one_b_caps_in_flight():
    g = SCHEDULES["gpipe"].build(n_stages=4, n_ranks=4, microbatches=16)
    f = SCHEDULES["one_f_one_b"].build(n_stages=4, n_ranks=4,
                                       microbatches=16)
    # identical forward tick order; the cap is what changes
    assert [t.feed_mb for t in f.ticks] == [t.feed_mb for t in g.ticks]
    assert [t.capture_out for t in f.ticks] == \
        [t.capture_out for t in g.ticks]
    assert f.in_flight == 4 and g.in_flight == 16


def test_interleaved_bubble_shrinks():
    # S=4 stages on 2 ranks x V=2 chunks, m >= ranks: bubble = ranks-1
    plan = SCHEDULES["interleaved"].build(n_stages=4, n_ranks=2,
                                          microbatches=4, virtual_stages=2)
    assert plan is not None
    assert plan.busy_ticks == 8                     # V passes over m
    assert plan.bubble_ticks == plan.n_ranks - 1 == 1
    # every wrapped chunk output is stashed before (or at) the tick that
    # feeds it back
    stash_tick = {t.stash_buf: i for i, t in enumerate(plan.ticks)
                  if t.stash_buf >= 0}
    for i, t in enumerate(plan.ticks):
        if t.feed_buf >= 0:
            assert stash_tick[t.feed_buf] <= i


@pytest.mark.parametrize("name,v", [("gpipe", 1), ("one_f_one_b", 1),
                                    ("interleaved", 2), ("interleaved", 3)])
@pytest.mark.parametrize("m", [1, 2, 4, 8])
def test_tick_plan_invariants(name, v, m):
    ranks = 2
    plan = SCHEDULES[name].build(n_stages=ranks * v, n_ranks=ranks,
                                 microbatches=m, virtual_stages=v)
    assert plan is not None
    feeds = [t.feed_mb for t in plan.ticks if t.feed_mb >= 0]
    captures = [t.capture_out for t in plan.ticks if t.capture_out >= 0]
    assert sorted(feeds) == list(range(m))          # each mb fed once
    assert sorted(captures) == list(range(m))       # each out captured once
    for t in plan.ticks:                            # feeds are exclusive
        assert not (t.feed_mb >= 0 and t.feed_buf >= 0)
    assert sum(t.phase == "warmup" for t in plan.ticks) == ranks - 1
    assert sum(t.phase == "cooldown" for t in plan.ticks) == ranks - 1
    # the closed forms in cost_model match the built plan exactly
    assert cost_model.pipeline_bubble_fraction(name, ranks, m, v) == \
        pytest.approx(plan.bubble_fraction)
    assert cost_model.pipeline_in_flight(name, ranks, m, v) == plan.in_flight


def test_interleaved_v2_beats_gpipe_at_m_equals_s():
    """Acceptance: modeled bubble for interleaved(V=2) strictly below gpipe
    at m = S."""
    S = 4
    g = cost_model.pipeline_bubble_fraction("gpipe", S, S)
    i = cost_model.pipeline_bubble_fraction("interleaved", S, S,
                                            virtual_stages=2)
    assert 0.0 < i < g
    # and the same holds for the built tick plans
    gp = SCHEDULES["gpipe"].build(n_stages=S, n_ranks=S, microbatches=S)
    ip = SCHEDULES["interleaved"].build(n_stages=2 * S, n_ranks=S,
                                        microbatches=S, virtual_stages=2)
    assert ip.bubble_fraction < gp.bubble_fraction


def test_bubble_stretches_roofline_step_time():
    base = cost_model.roofline_terms(1e12, 1e9, 0.0, n_chips=4)
    bub = cost_model.roofline_terms(1e12, 1e9, 0.0, n_chips=4,
                                    bubble_fraction=0.5)
    assert bub.step_time_s == pytest.approx(2 * base.step_time_s)
    assert bub.pipeline_s == pytest.approx(base.step_time_s)
    assert base.bubble_fraction == 0.0 and bub.bubble_fraction == 0.5


def test_plan_bubble_fraction_reads_genes():
    assert cost_model.plan_bubble_fraction(Plan(), 1) == 0.0
    p = Plan(microbatches=8, pipeline_schedule="interleaved",
             virtual_stages=2)
    assert cost_model.plan_bubble_fraction(p, 4) == \
        cost_model.pipeline_bubble_fraction("interleaved", 4, 8, 2)
    # virtual_stages is ignored by non-interleaved schedules
    q = Plan(microbatches=8, pipeline_schedule="gpipe", virtual_stages=2)
    assert cost_model.plan_bubble_fraction(q, 4) == \
        cost_model.pipeline_bubble_fraction("gpipe", 4, 8)


# ---------------------------------------------------------------- registry
def test_get_schedule_and_register():
    assert get_schedule("gpipe") is SCHEDULES["gpipe"]
    assert get_schedule("nope") is None
    sched = SCHEDULES["interleaved"]
    assert get_schedule(sched) is sched             # instances pass through

    class Custom(Schedule):
        name = "custom-test"

        def build(self, *, n_stages, n_ranks, microbatches,
                  virtual_stages=1):
            return None

    register_schedule(Custom())
    try:
        assert get_schedule("custom-test") is not None
        with pytest.raises(ValueError):
            register_schedule(Custom())
    finally:
        del SCHEDULES["custom-test"]


# --------------------------------------------------------------- execution
def test_single_rank_pod_mesh_runs_every_schedule():
    """A 1-rank pod mesh exercises the real shard_map executor (including
    the interleaved recirculation buffer) in-process."""
    from repro.dist.pipeline import pipeline_apply, sequential_apply

    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    S, B, D = 3, 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    want = sequential_apply(stage_fn, ws, x)
    # interleaved hosts all 3 stages on the single rank (V = 3); gpipe and
    # 1F1B cannot (stages != ranks) and must fall back to sequential
    got = jax.jit(lambda ws, x: pipeline_apply(
        stage_fn, ws, x, mesh, microbatches=2, schedule="interleaved",
        virtual_stages=3))(ws, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for name in ("gpipe", "one_f_one_b"):
        got = pipeline_apply(stage_fn, ws, x, mesh, microbatches=2,
                             schedule=name)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_unknown_schedule_and_bad_shapes_fall_back():
    from repro.dist.pipeline import pipeline_apply, sequential_apply

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    ws = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    want = np.asarray(sequential_apply(stage_fn, ws, x))
    for kw in ({"schedule": "no-such-schedule"},
               {"schedule": "interleaved", "virtual_stages": 2},
               {"microbatches": 3}):              # 4 % 3 != 0
        got = pipeline_apply(stage_fn, ws, x, mesh,
                             **{"microbatches": 2, **kw})
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-6)


# ----------------------------------------------------------------- dryrun
def test_dryrun_default_plan_named_plus_schedule_override():
    """--plan <named> + --schedule must patch the named plan, not silently
    rebuild the auto baseline under the named plan's tag (subprocess: the
    dryrun module forces a 512-device XLA flag at import)."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = """
import sys
sys.path.insert(0, {src!r})
from repro.launch.dryrun import default_plan
from repro.configs import get_config, get_shape
cfg = get_config("granite-3-2b")
shape = get_shape("train_4k")
p = default_plan(cfg, shape, "train-tight-mem",
                 {{"pipeline_schedule": "interleaved", "virtual_stages": 2}})
assert p.remat == "full" and p.microbatches == 4, p   # named fields kept
assert p.pipeline_schedule == "interleaved" and p.virtual_stages == 2, p
q = default_plan(cfg, shape, "train-tight-mem", None)
assert q.remat == "full" and q.pipeline_schedule == "gpipe", q
print("ok")
""".format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ok" in proc.stdout


# ------------------------------------------------------------- GA search
def _modeled_evaluate(n_ranks, mem_weight):
    """Modeled step time from the pipeline genes alone: roofline busy time
    (constant across candidates) stretched by the schedule bubble, plus a
    memory term charging the schedule's in-flight activations."""

    def evaluate(genes):
        plan = Plan.from_genes(list(genes))
        bubble = cost_model.plan_bubble_fraction(plan, n_ranks)
        t = 1.0 / (1.0 - bubble)
        mem = cost_model.pipeline_in_flight(
            plan.pipeline_schedule, n_ranks,
            max(plan.microbatches, 1), plan.virtual_stages)
        return Evaluation(time_s=t + mem_weight * mem, correct=True)

    return evaluate


def _ga_best_plan(mem_weight):
    n = len(Plan.gene_cardinalities())
    cfg = GAConfig(population=16, generations=16, seed=3,
                   cardinalities=Plan.gene_cardinalities())
    res = run_ga(n, _modeled_evaluate(n_ranks=4, mem_weight=mem_weight), cfg)
    return Plan.from_genes(list(res.best_genes))


def test_ga_flips_schedule_gene_on_bubble_vs_memory():
    """The GA's all-zeros baseline is gpipe; when the bubble term dominates
    it must flip pipeline_schedule to interleaved, and when the memory term
    dominates to the 1F1B in-flight cap."""
    bubble_bound = _ga_best_plan(mem_weight=0.0)
    assert bubble_bound.pipeline_schedule == "interleaved"
    assert bubble_bound.virtual_stages == 2
    assert bubble_bound.microbatches == 8           # deepest overlap wins

    memory_bound = _ga_best_plan(mem_weight=0.5)
    assert memory_bound.pipeline_schedule == "one_f_one_b"
    assert memory_bound.microbatches == 8           # cap makes m=8 free
