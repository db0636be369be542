"""End-to-end training driver.

Runs the published config by default; ``--reduced`` opts into the tiny
same-family config for the CPU.  Production-structured: mesh + sharded jit
train step, deterministic data pipeline, fault-tolerant checkpointed loop,
straggler watchdog.  ``--pod-parallel --compress`` (int8 cross-pod
gradient compression) needs a mesh with a "pod" axis and raises on the
1-D host mesh this driver builds.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --reduced --steps 100 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def build_training(cfg, plan, tcfg, mesh):
    """Jitted train step and state initialiser for a sharded model.

    Parameters shard per the plan's :class:`~repro.dist.sharding.Rules`
    (FSDP: ``embed`` over ``data``) and the optimizer state mirrors them;
    both are created under ``jit`` with those output shardings, so no
    device ever holds the whole state.  Returns ``(jstep, init_state)``;
    ``jstep`` donates params and optimizer state.
    """
    from repro.dist.sharding import Rules, tree_shardings
    from repro.models.lm import Model, param_axes
    from repro.train import optimizer, train_step as ts

    rules = Rules(mesh, plan)
    model = Model(cfg, plan, rules)
    p_axes = param_axes(cfg)
    params_sds = jax.eval_shape(model.init,
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    params_sh = tree_shardings(rules, p_axes, params_sds)

    def opt_init(params):
        return optimizer.init(params, tcfg)

    opt_sh = tree_shardings(rules, optimizer.opt_state_axes(p_axes, tcfg),
                            jax.eval_shape(opt_init, params_sds))

    jstep = jax.jit(ts.make_train_step(model, tcfg), donate_argnums=(0, 1))

    def init_state():
        params = jax.jit(model.init, out_shardings=params_sh)(
            jax.random.PRNGKey(tcfg.seed))
        opt = jax.jit(opt_init, out_shardings=opt_sh)(params)
        return {"params": params, "opt": opt}

    return jstep, init_state


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--pod-parallel", action="store_true")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--remat", default="block",
                    choices=["none", "block", "full"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.pipeline import SyntheticTokens, data_config_for
    from repro.dist.plan import Plan
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.fault_tolerance import run_resilient

    mesh = make_host_mesh()
    if args.pod_parallel and "pod" not in mesh.axis_names:
        raise ValueError(
            f"--pod-parallel needs a mesh with a 'pod' axis; the host mesh "
            f"has axes {mesh.axis_names}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    plan = Plan(name="train-cli", remat=args.remat,
                microbatches=args.microbatches,
                grad_compression=args.compress,
                vocab_chunk=min(2048, args.seq))
    tcfg = TrainConfig(lr=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1),
                       microbatches=args.microbatches)

    jstep, init_state = build_training(cfg, plan, tcfg, mesh)
    data = SyntheticTokens(data_config_for(cfg, shape))
    ckpt = Checkpointer(args.ckpt_dir, keep=2)

    def body(state, step):
        batch = data.batch(step)
        t0 = time.perf_counter()
        params, opt, metrics = jstep(state["params"], state["opt"], batch,
                                     jnp.int32(step))
        metrics = jax.device_get(metrics)
        if step % args.log_every == 0:
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"dt={time.perf_counter()-t0:.3f}s", flush=True)
        return {"params": params, "opt": opt}, metrics

    res = run_resilient(total_steps=args.steps, checkpointer=ckpt,
                        init_state=init_state, step_fn=body,
                        save_every=args.save_every)
    losses = [h.get("loss") for h in res.metrics_history if "loss" in h]
    print(f"done: {res.last_step} steps, {res.restarts} restarts, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"{len(res.watchdog.flagged)} straggler flags")
    return res


if __name__ == "__main__":
    main()
