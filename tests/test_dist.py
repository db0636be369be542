"""repro.dist subsystem on a single-device mesh: Plan genes, Rules specs,
tree_shardings, batch_axes, pipeline fallback, and the planner mesh bridge.

Multi-device behaviour (real (2,4)/(2,2,2) meshes) lives in
tests/test_distributed.py; everything here runs in-process on 1 device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.sharding import AxisType
from repro.dist.plan import Plan
from repro.dist.sharding import (NullRules, Rules, batch_axes,
                                 tree_shardings)


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# ------------------------------------------------------------------- plan
def test_plan_gene_space_matches_fields():
    p = Plan()
    for gene in Plan.GENE_SPACE:
        assert hasattr(p, gene.field), gene.field
        assert len(gene.choices) >= 2, gene.field
        assert isinstance(gene.structural, bool)


def test_plan_genes_roundtrip_all_fields():
    cards = Plan.gene_cardinalities()
    assert len(cards) == len(Plan.GENE_SPACE)
    # every gene value decodes to a plan that re-encodes to the same genes
    for i, gene in enumerate(Plan.GENE_SPACE):
        for g in range(len(gene.choices)):
            genes = [0] * len(cards)
            genes[i] = g
            q = Plan.from_genes(genes)
            assert getattr(q, gene.field) == gene.choices[g]
            assert q.to_genes()[i] == g


def test_named_plans_discoverable():
    # repro.launch.dryrun resolves --plan <name> by scanning module globals
    from repro.dist import plan as plan_mod
    named = {p.name: p for p in vars(plan_mod).values()
             if isinstance(p, Plan)}
    assert "serve-low-mem" in named
    assert named["serve-low-mem"].kv_cache_quant is True


# ------------------------------------------------------------------ rules
def test_rules_specs_on_single_device_mesh(mesh):
    rules = Rules(mesh, Plan())
    assert rules.spec(("embed", "ff"), dims=(64, 16)) == P(("data",),
                                                          "model")
    # unknown / None logical axes replicate; trailing Nones are trimmed
    assert rules.spec(("batch", "seq", None), dims=(8, 16, 4)) == \
        P(("data",))
    assert rules.spec((None, None)) == P()


def test_rules_divisibility_replicates(mesh):
    # 1-device mesh divides everything; fake a bigger axis via dims=odd
    # against a 2-wide axis on a (1,1) mesh is moot, so check the rule
    # directly: a dim not divisible by the axis product falls back
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}
    r = Rules(FakeMesh(), Plan())
    assert r.spec(("embed", "heads", None), dims=(64, 10, 7)) == P(("data",))
    assert r.spec(("embed", "ff"), dims=(64, 16)) == P(("data",), "model")


def test_rules_shard_largest_divisible_prefix():
    """batch % (pod*data) != 0 must degrade to sharding over the divisible
    prefix ("pod",), not fall all the way back to replicated."""
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 2}
    rules = Rules(FakeMesh(), Plan())
    # 4 % (2*4) != 0 but 4 % 2 == 0 -> shard over ("pod",) only
    assert rules.spec(("batch", None), dims=(4, 8)) == P(("pod",))
    # divisible by the full tuple -> unchanged behavior
    assert rules.spec(("batch", None), dims=(16, 8)) == P(("pod", "data"))
    # not even the first axis divides -> replicated
    assert rules.spec(("batch", None), dims=(3, 8)) == P()
    # the taken prefix is marked used: a later dim cannot reuse "pod",
    # while the untaken "data" stays free for dims that map to it
    spec = rules.spec(("batch", "embed"), dims=(4, 8))
    assert spec == P(("pod",), ("data",))


def test_rules_duplicate_axis_falls_back():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}
    rules = Rules(FakeMesh(), Plan(decode_kv_seq_shard=True))
    # kv_seq claims "model" first; kv_heads falls back to replicated
    assert rules.spec(("batch", "kv_seq", "kv_heads", None),
                      dims=(8, 32, 8, 4)) == P(("data",), "model")


def test_batch_axes(mesh):
    assert batch_axes(mesh) == ("data",)
    pod_mesh = jax.make_mesh((1, 1), ("pod", "data"))
    assert batch_axes(pod_mesh) == ("pod", "data")


def test_null_rules_are_identity():
    rules = NullRules()
    x = jnp.ones((2, 3))
    assert rules.constrain(x, ("batch", None)) is x
    assert rules.spec(("batch", None)) == P()
    assert rules.mesh is None


# --------------------------------------------------------- tree_shardings
def test_tree_shardings_produces_named_shardings(mesh):
    rules = Rules(mesh, Plan())
    axes = {"w": ("embed", "ff"), "b": ("ff",), "count": ()}
    sds = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
           "b": jax.ShapeDtypeStruct((4,), jnp.float32),
           "count": jax.ShapeDtypeStruct((), jnp.int32)}
    sh = tree_shardings(rules, axes, sds)
    assert set(sh) == {"w", "b", "count"}
    for v in sh.values():
        assert isinstance(v, NamedSharding)
    assert sh["w"].spec == P(("data",), "model")
    assert sh["count"].spec == P()


def test_plan_rules_tree_shardings_end_to_end(mesh):
    """Acceptance: Plan -> Rules -> tree_shardings yields valid shardings
    for a real model on a single-device mesh, and the constrained model
    still computes."""
    from repro.configs import get_config
    from repro.models.lm import Model, param_axes

    cfg = get_config("granite-3-2b").reduced()
    plan = Plan(vocab_chunk=8)
    rules = Rules(mesh, plan)
    model = Model(cfg, plan, rules)
    params = model.init(jax.random.PRNGKey(0))
    sds = jax.eval_shape(lambda: params)
    shardings = tree_shardings(rules, param_axes(cfg), sds)
    leaves = jax.tree.leaves(shardings,
                             is_leaf=lambda x: isinstance(x, NamedSharding))
    assert leaves and all(isinstance(s, NamedSharding) for s in leaves)

    params = jax.device_put(params, shardings)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32)}
    loss, metrics = jax.jit(model.train_loss)(params, batch)
    assert np.isfinite(float(loss))


# --------------------------------------------------------------- pipeline
def test_pipeline_falls_back_to_sequential_off_mesh(mesh):
    from repro.dist.pipeline import pipeline_apply, sequential_apply

    ws = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    want = sequential_apply(stage_fn, ws, x)
    # mesh has no "pod" axis of size 3 -> sequential schedule
    got = pipeline_apply(stage_fn, ws, x, mesh, microbatches=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------------- bridge
def test_bridge_mesh_verify_dp_tp_only(mesh):
    from repro.apps import APPS
    from repro.core.destinations import FPGA, GPU, MANY_CORE
    from repro.core.measure import CompiledCostRunner
    from repro.dist import bridge

    app = APPS["tdFIR"]()
    inputs = app.make_inputs(seed=0, small=True)
    runner = CompiledCostRunner(mesh)
    fn = app.build({})
    ev_dp = bridge.mesh_verify(runner, MANY_CORE, fn, inputs)
    ev_tp = bridge.mesh_verify(runner, GPU, fn, inputs)
    assert ev_dp is not None and ev_dp.correct and ev_dp.time_s > 0
    assert ev_tp is not None and ev_tp.correct and ev_tp.time_s > 0
    assert "roofline" in ev_dp.info
    # the FPGA analogue is a kernel substitution, not a sharding
    assert bridge.mesh_verify(runner, FPGA, fn, inputs) is None
    assert bridge.mesh_verify(None, MANY_CORE, fn, inputs) is None


def test_planner_records_mesh_time(mesh):
    from repro.apps import APPS
    from repro.core.ga import GAConfig
    from repro.core.measure import CompiledCostRunner, TimedRunner
    from repro.core.planner import UserTarget, plan_offload

    app = APPS["tdFIR"]()
    report = plan_offload(
        app, UserTarget(), inputs=app.make_inputs(0, small=True),
        runner=TimedRunner(repeats=1),
        ga_cfg=GAConfig(population=3, generations=3, seed=0),
        cost_runner=CompiledCostRunner(mesh))
    assert len(report.records) == 6
    by_method = {(r.paper_analogue, r.method): r for r in report.records}
    for analogue in ("many-core CPU", "GPU"):
        rec = by_method[(analogue, "loop")]
        assert rec.mesh_time_s is not None and rec.mesh_time_s > 0
        assert "roofline" in rec.mesh_info
    # FPGA verifications carry no mesh analogue
    assert by_method[("FPGA", "loop")].mesh_time_s is None
