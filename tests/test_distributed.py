"""Multi-device behaviour on a forced 8-device host (subprocess per test so
the main pytest process keeps exactly 1 device, per the task spec)."""
from helpers import run_multidevice


def test_sharded_train_step_runs_and_matches_single_device():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.dist.plan import Plan
from repro.dist.sharding import Rules, tree_shardings
from repro.launch.mesh import make_test_mesh
from repro.models.lm import Model, param_axes
from repro.train import optimizer, train_step as ts

cfg = get_config('granite-3-2b').reduced()
mesh = make_test_mesh((4, 2))
plan = Plan(vocab_chunk=8)
tcfg = TrainConfig(lr=1e-3, warmup_steps=1)
batch = {'tokens': jnp.ones((8, 16), jnp.int32),
         'labels': jnp.ones((8, 16), jnp.int32)}

def run(rules_mesh):
    rules = Rules(rules_mesh, plan) if rules_mesh is not None else None
    from repro.dist.sharding import NullRules
    model = Model(cfg, plan, rules or NullRules())
    params = model.init(jax.random.PRNGKey(0))
    opt = optimizer.init(params, tcfg)
    step = ts.make_train_step(model, tcfg)
    if rules_mesh is not None:
        p_sds = jax.eval_shape(lambda: params)
        p_sh = tree_shardings(rules, param_axes(cfg), p_sds)
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, jax.tree.map(
            lambda _: None, opt, is_leaf=lambda x: False) or opt)
        step = jax.jit(step)
    else:
        step = jax.jit(step)
    p2, o2, m = step(params, opt, batch, jnp.int32(0))
    return float(m['loss'])

l_multi = run(mesh)
l_single = run(None)
assert abs(l_multi - l_single) < 1e-3, ('FAIL', l_multi, l_single)
print('ok', l_multi, l_single)
""")


def test_rules_divisibility_fallback():
    run_multidevice("""
import jax
from jax.sharding import PartitionSpec as P
from repro.dist.plan import Plan
from repro.dist.sharding import Rules
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 4))
rules = Rules(mesh, Plan())
# heads=10 not divisible by model=4 -> replicated; ff=16 divisible -> sharded
spec = rules.spec(("embed", "heads", None), dims=(64, 10, 7))
assert spec == P(("data",)), ('FAIL', spec)
spec = rules.spec(("embed", "ff"), dims=(64, 16))
assert spec == P(("data",), "model"), ('FAIL', spec)
# duplicate axis: kv_seq takes model first, kv_heads falls back
plan = Plan(decode_kv_seq_shard=True)
rules = Rules(mesh, plan)
spec = rules.spec(("batch", "kv_seq", "kv_heads", None),
                  dims=(8, 32, 8, 4))
assert spec == P(("data",), "model"), ('FAIL', spec)
print('ok')
""")


def test_checkpoint_reshard_on_restore():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_test_mesh

mesh_a = make_test_mesh((4, 2))
mesh_b = make_test_mesh((2, 2))    # "after losing half the slice"
x = jnp.arange(64.0).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P('data', 'model')))
with tempfile.TemporaryDirectory() as d:
    ck = Checkpointer(d)
    ck.save(1, {'x': xa})
    got, _ = ck.restore(1, shardings={'x': NamedSharding(mesh_b,
                                                         P('data', None))})
    assert got['x'].sharding.spec == P('data', None), 'FAIL spec'
    np.testing.assert_array_equal(np.asarray(got['x']), np.asarray(x))
print('ok')
""")


def test_compressed_psum_close_to_plain():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.train.grad_compression import (compressed_psum, plain_psum,
                                          init_error_feedback)

mesh = make_test_mesh((8,), ('pod',))

def body(g, ef):
    out, new_ef = compressed_psum({'g': g}, {'g': ef}, 'pod')
    exact = plain_psum({'g': g}, 'pod')
    return out['g'], new_ef['g'], exact['g']

g = jax.random.normal(jax.random.PRNGKey(0), (8, 256)) * 0.1
ef = jnp.zeros((8, 256))
f = jax.shard_map(body, mesh=mesh, in_specs=(P('pod'), P('pod')),
                  out_specs=(P('pod'), P('pod'), P('pod')), check_vma=False)
out, new_ef, exact = f(g, ef)
rel = float(jnp.abs(out - exact).max() / (jnp.abs(exact).max() + 1e-9))
assert rel < 0.05, ('FAIL rel', rel)
# error feedback captures the residual: ef + deq == pre-quant grads
assert float(jnp.abs(new_ef).max()) > 0, 'FAIL ef empty'
# second step with error feedback reduces accumulated bias
out2, ef2, exact2 = f(g, new_ef)
print('ok', rel)
""")


def test_decode_kv_seq_sharding_lowers():
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.dist.plan import Plan
from repro.dist.sharding import Rules, tree_shardings
from repro.launch.mesh import make_test_mesh
from repro.models.lm import Model, param_axes, cache_axes, init_cache
from repro.train import train_step as ts

cfg = get_config('granite-3-2b').reduced()
mesh = make_test_mesh((2, 4))
plan = Plan(decode_kv_seq_shard=True, remat='none')
rules = Rules(mesh, plan)
model = Model(cfg, plan, rules)
params_sds = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
p_sh = tree_shardings(rules, param_axes(cfg), params_sds)
cache_sds = jax.eval_shape(lambda: init_cache(cfg, 8, 64))
c_sh = tree_shardings(rules, cache_axes(cfg), cache_sds)
fn = ts.make_serve_step(model)
jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, None, None))
comp = jitted.lower(params_sds, cache_sds,
                    jax.ShapeDtypeStruct((8, 1), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32)).compile()
txt = comp.as_text()
assert ('all-reduce' in txt) or ('all-gather' in txt), 'FAIL no collectives'
print('ok')
""")


def test_pod_parallel_train_step_with_compression():
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.dist.plan import Plan
from repro.dist.sharding import Rules
from repro.models.lm import Model
from repro.train import optimizer, train_step as ts
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                     axis_types=(AxisType.Auto,) * 3)
cfg = get_config('granite-3-2b').reduced()
plan = Plan(grad_compression=True, vocab_chunk=8)
tcfg = TrainConfig(lr=1e-3, warmup_steps=1)
model = Model(cfg, plan, Rules(mesh, plan))
params = model.init(jax.random.PRNGKey(0))
opt = optimizer.init(params, tcfg)
opt['ef'] = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
batch = {'tokens': jnp.ones((8, 16), jnp.int32),
         'labels': jnp.ones((8, 16), jnp.int32)}
step = ts.make_pod_parallel_train_step(model, tcfg, mesh)
with jax.set_mesh(mesh):
    p2, o2, m = jax.jit(step)(params, opt, batch, jnp.int32(0))
import math
assert math.isfinite(float(m['loss'])), 'FAIL loss'
print('ok', float(m['loss']))
""", n_devices=8)


def test_moe_ep_shardmap_matches_gspmd():
    run_multidevice("""
import jax, jax.numpy as jnp
from repro.configs import ARCHS
from repro.models import moe as moe_mod
from repro.dist.plan import Plan
from repro.dist.sharding import Rules
from repro.launch.mesh import make_test_mesh

cfg = ARCHS['moonshot-v1-16b-a3b'].reduced()
mesh = make_test_mesh((2, 4))
rules = Rules(mesh, Plan())
p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32)
y1, a1 = jax.jit(lambda p, x: moe_mod.apply_moe(p, cfg, x, rules))(p, x)
y2, a2 = jax.jit(lambda p, x: moe_mod.apply_moe_ep(p, cfg, x, rules))(p, x)
d = float(jnp.abs(y1 - y2).max())
assert d < 1e-4, ('FAIL ydiff', d)
# aux is a per-shard estimator: close but not identical
assert abs(float(a1) - float(a2)) < 0.05, ('FAIL aux', float(a1), float(a2))
# grads flow through the shard_map path
g = jax.grad(lambda p, x: moe_mod.apply_moe_ep(p, cfg, x, rules)[0].sum())(p, x)
gn = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
assert gn > 0, 'FAIL zero grads'
print('ok', d)
""")


def test_pipeline_schedules_grad_equivalence():
    """fwd + jax.grad of every schedule vs sequential_apply across
    m in {1, S, 4S}, plus the fallback path (batch not divisible)."""
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh
from repro.dist.pipeline import pipeline_apply, sequential_apply

S, B, D = 4, 16, 8
ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

def stage_fn(w, h):
    return jnp.tanh(h @ w)

want = sequential_apply(stage_fn, ws, x)
gwant = jax.grad(lambda ws: sequential_apply(stage_fn, ws, x).sum())(ws)
mesh4 = Mesh(np.asarray(jax.devices()[:4]), ('pod',),
             axis_types=(AxisType.Auto,))
mesh2 = Mesh(np.asarray(jax.devices()[:2]), ('pod',),
             axis_types=(AxisType.Auto,))
cases = [('gpipe', mesh4, 1), ('one_f_one_b', mesh4, 1),
         ('interleaved', mesh2, 2)]
for sched, mesh, v in cases:
    for m in (1, S, 4 * S):
        f = lambda ws, x: pipeline_apply(stage_fn, ws, x, mesh,
                                         microbatches=m, schedule=sched,
                                         virtual_stages=v)
        got = jax.jit(f)(ws, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f'{sched} fwd m={m}')
        g = jax.jit(jax.grad(lambda ws: f(ws, x).sum()))(ws)
        per_stage = np.asarray(jnp.abs(g).sum(axis=(1, 2)))
        assert (per_stage > 0).all(), ('FAIL grads', sched, m, per_stage)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gwant),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f'{sched} grad m={m}')
# fallback: B % m != 0 must still match (and differentiate) sequentially
f = lambda ws: pipeline_apply(stage_fn, ws, x, mesh4, microbatches=3,
                              schedule='one_f_one_b').sum()
g = jax.jit(jax.grad(f))(ws)
np.testing.assert_allclose(np.asarray(g), np.asarray(gwant), rtol=1e-4,
                           atol=1e-5)
print('ok')
""", n_devices=4, timeout=600)


def test_pipeline_train_step_consumes_plan_genes():
    """make_pipeline_train_step trains a stage-stacked model under each
    schedule and matches the sequential step's loss."""
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import TrainConfig
from jax.sharding import AxisType, Mesh
from repro.dist.pipeline import sequential_apply
from repro.dist.plan import Plan
from repro.train import optimizer, train_step as ts

mesh4 = Mesh(np.asarray(jax.devices()[:4]), ('pod',),
             axis_types=(AxisType.Auto,))
mesh2 = Mesh(np.asarray(jax.devices()[:2]), ('pod',),
             axis_types=(AxisType.Auto,))
S, B, D = 4, 8, 8
ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
y = jax.random.normal(jax.random.PRNGKey(2), (B, D))
tcfg = TrainConfig(lr=1e-2, warmup_steps=1)

def stage_fn(w, h):
    return jnp.tanh(h @ w)

def run(plan, mesh):
    step = ts.make_pipeline_train_step(stage_fn, tcfg, mesh, plan)
    opt = optimizer.init(ws, tcfg)
    p2, o2, m = jax.jit(step)(ws, opt, (x, y), jnp.int32(0))
    return float(m['loss']), p2

ref_loss = float(jnp.mean(
    (sequential_apply(stage_fn, ws, x) - y) ** 2))
losses = {}
params = {}
for sched, mesh, v in [('gpipe', mesh4, 1), ('one_f_one_b', mesh4, 1),
                       ('interleaved', mesh2, 2)]:
    plan = Plan(microbatches=4, pipeline_schedule=sched, virtual_stages=v)
    losses[sched], params[sched] = run(plan, mesh)
for sched, l in losses.items():
    assert abs(l - ref_loss) < 1e-5, ('FAIL loss', sched, l, ref_loss)
# all schedules take the same optimizer step (same grads)
for sched in ('one_f_one_b', 'interleaved'):
    d = float(np.abs(np.asarray(params[sched])
                     - np.asarray(params['gpipe'])).max())
    assert d < 1e-5, ('FAIL step', sched, d)
print('ok', ref_loss)
""", n_devices=4, timeout=600)


def test_pipeline_parallel_matches_sequential():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh
from repro.dist.pipeline import pipeline_apply, sequential_apply

mesh = Mesh(np.asarray(jax.devices()[:4]), ('pod',),
            axis_types=(AxisType.Auto,))
S, B, D = 4, 8, 16
ws = jax.random.normal(jax.random.PRNGKey(0), (S, D, D)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

def stage_fn(w, h):
    return jnp.tanh(h @ w)

want = sequential_apply(stage_fn, ws, x)
got = jax.jit(lambda ws, x: pipeline_apply(stage_fn, ws, x, mesh,
                                           microbatches=4))(ws, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-5, atol=1e-5)

# differentiable: grads flow to every stage's params
g = jax.jit(jax.grad(lambda ws: pipeline_apply(
    stage_fn, ws, x, mesh, microbatches=4).sum()))(ws)
per_stage = np.asarray(jnp.abs(g).sum(axis=(1, 2)))
assert (per_stage > 0).all(), ('FAIL grads', per_stage)
# matches sequential grads
g2 = jax.jit(jax.grad(lambda ws: sequential_apply(
    stage_fn, ws, x).sum()))(ws)
np.testing.assert_allclose(np.asarray(g), np.asarray(g2), rtol=1e-4,
                           atol=1e-5)
print('ok')
""", n_devices=4)
