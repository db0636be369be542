"""Chip smoke run: the main paths once each on the TPU, in one process.

    python chip_smoke.py               # one chip: planner + granite-3-2b server
    python chip_smoke.py --four-chips  # four chips: granite-3-2b FSDP training

One chip, two phases:

  * planner — ``plan_offload`` over 3mm, NAS.BT and tdFIR at their
    non-small sizes with the GA settings of examples/quickstart.py.  Every
    app shows six verifications and a selected destination; no candidate
    carries a build / compile / run error; the Pallas records (3mm's
    loop->FPGA, tdFIR's FB->FPGA) are compiled, correct and finite.
  * serve — granite-3-2b at its published widths (40 layers, d_model 2048,
    32/8 heads, vocab 49155, bf16; random weights from a seed) built the way
    ``python -m repro.launch.serve`` builds it, behind the
    ``ContinuousBatcher``: 8 requests with prompt lengths drawn from a seed,
    4 slots, cache_len 1024, 32 generated tokens each.  Correctness compares
    logits, not greedy tokens (random weights give near-flat logits that
    bf16 reordering flips): one decode step on the engine's prefill cache
    must match a prefill over the prompt plus that token within
    ``LOGIT_TOL``, and the same step on another prompt's cache must not.

``--four-chips`` runs only the sharded-training phase: granite-3-2b at
published widths on the (4,) ``data`` mesh of ``make_host_mesh()`` with the
FSDP rules of ``repro.launch.train`` (parameters, f32 Adam moments: ~35 GB,
more than one chip holds).  It checks from ``addressable_shards`` that the
state sits on all four devices, takes a few steps, and compares the step-0
loss with a plain forward of the same parameters and batch on one device
within ``LOSS_RTOL``.

Exits non-zero on any failure, and at once when JAX finds no TPU; there is
no CPU fallback.  The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

APPS = ("3mm", "NAS.BT", "tdFIR")
# (app, method) of the records that must run a compiled Pallas kernel
PALLAS_RECORDS = {("3mm", "loop"), ("tdFIR", "function_block")}

ARCH = "granite-3-2b"
PUBLISHED = dict(n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
                 vocab_size=49155, param_dtype="bfloat16")
N_REQUESTS, N_SLOTS, CACHE_LEN, GEN = 8, 4, 1024, 32
PROMPT_LENS = (128, 256, 512)
# ||decode - prefill|| / ||prefill - mean|| over the real vocabulary.  bf16
# at 4-16 layers measures 0.007-0.014 on the CPU; another prompt's cache
# measures ~1.
LOGIT_TOL = 0.05

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 512, 3
LOSS_RTOL = 1e-2            # ~2.5 bf16 ulps (2**-8) of the loss


def require(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


@contextlib.contextmanager
def phase(name: str):
    """Print a phase's wall time, compilation included."""
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"== {name}: {time.perf_counter() - t0:.1f} s wall, compilation "
          "included", flush=True)


def peak_bytes(devices) -> str:
    return ", ".join(
        f"{d.id}:{(d.memory_stats() or {}).get('peak_bytes_in_use')}"
        for d in devices)


# ------------------------------------------------------------------ planner
def planner_phase(small: bool = False):
    from repro.apps import APPS as REGISTRY
    from repro.backends import FPGA
    from repro.core.ga import GAConfig
    from repro.core.measure import TimedRunner
    from repro.core.planner import UserTarget, plan_offload

    for name in APPS:
        app = REGISTRY[name]()
        ga_cfg = GAConfig.for_gene_length(min(app.gene_length, 6), seed=0)
        report = plan_offload(
            app, UserTarget(), inputs=app.make_inputs(seed=0, small=small),
            runner=TimedRunner(repeats=1), ga_cfg=ga_cfg)
        print(f"{name}: single-core {report.ref_time_s:.6f} s", flush=True)
        for r in report.records:
            mark = " <== selected" if r is report.selected else ""
            print(f"  {r.order}. {r.paper_analogue:14s} {r.method:15s} "
                  f"time_s={r.best_time_s!r} correct={r.correct} "
                  f"choice={ {k: v for k, v in r.choice.items() if v != 'seq'} }"
                  f"{mark}", flush=True)
        require(len(report.records) == 6,
                f"{name}: {len(report.records)} verifications, not 6")
        require(report.selected is not None, f"{name}: nothing selected")
        for r in report.records:
            require(not r.error,
                    f"{name} {r.paper_analogue} {r.method}: {r.error}")
            if (name, r.method) in PALLAS_RECORDS \
                    and r.destination == FPGA.name:
                # loop choices name the impl ("pallas"), function-block
                # choices the entry and impl ("fb_tdfir_pallas")
                ran = any(FPGA.key in v for v in r.choice.values())
                require(ran and r.correct
                        and r.best_time_s < ga_cfg.penalty_s,
                        f"{name} {r.method}->FPGA did not run a correct "
                        f"Pallas kernel: {r}")
        print(f"{name}: selected {report.selected.paper_analogue} "
              f"{report.selected.method}", flush=True)


# -------------------------------------------------------------------- serve
def _logit_error(got, want, vocab: int) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)[..., :vocab]
    want = np.asarray(want, np.float64)[..., :vocab]
    return float(np.linalg.norm(got - want)
                 / np.linalg.norm(want - want.mean()))


def serve_phase(cfg, *, n_requests=N_REQUESTS, n_slots=N_SLOTS,
                cache_len=CACHE_LEN, gen=GEN, prompt_lens=PROMPT_LENS,
                seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_engine
    from repro.serve import Request

    engine = build_engine(cfg, n_slots=n_slots, cache_len=cache_len,
                          seed=seed)
    rng = np.random.RandomState(seed)
    lens = rng.choice(prompt_lens, size=n_requests)
    reqs = [Request(rid=f"r{i}", arch=cfg.name, prompt_len=int(n),
                    max_gen=gen,
                    tokens=rng.randint(0, cfg.vocab_size, int(n)))
            for i, n in enumerate(lens)]
    print(f"serve: {cfg.name} prompts {sorted(set(lens.tolist()))}, "
          f"{n_requests} requests, {n_slots} slots, cache_len {cache_len}",
          flush=True)
    out = engine.run(reqs)
    for r in reqs:
        toks = out.get(r.rid)
        require(toks is not None and len(toks) == gen,
                f"request {r.rid} returned {toks}")
        require(bool((toks >= 0).all() and (toks < cfg.vocab_size).all()),
                f"request {r.rid}: token out of vocabulary {toks}")
    print(f"serve: {len(out)} requests x {gen} tokens returned; traces "
          f"{engine.traces}", flush=True)

    # logits check: decode one token on the engine's prefill cache against
    # a prefill over prompt + that token; the corrupted control decodes the
    # same token on another prompt's cache (a slot mix-up)
    params = engine.params
    step = jax.jit(engine.model.decode_step)
    n = reqs[0].prompt_len
    prompt = jnp.asarray(reqs[0].tokens, jnp.int32)[None]
    other = jnp.asarray(rng.randint(0, cfg.vocab_size, n), jnp.int32)[None]
    prefill = engine._prefill_fn(n)
    logits, cache = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    dec, _ = step(params, cache, tok, jnp.int32(n))
    want, _ = engine._prefill_fn(n + 1)(
        params, {"tokens": jnp.concatenate([prompt, tok], axis=1)})
    bad, _ = step(params, prefill(params, {"tokens": other})[1], tok,
                  jnp.int32(n))
    err = _logit_error(dec, want, cfg.vocab_size)
    err_bad = _logit_error(bad, want, cfg.vocab_size)
    print(f"serve: logits decode-vs-prefill error {err!r} (tolerance "
          f"{LOGIT_TOL}); on another prompt's cache {err_bad!r}", flush=True)
    require(np.isfinite(np.asarray(dec, np.float32)).all(),
            "decode logits not finite")
    require(err <= LOGIT_TOL, f"decode logits disagree with prefill: {err}")
    require(err_bad > LOGIT_TOL,
            f"a corrupted cache passes the logits check: {err_bad}")


# -------------------------------------------------------------------- train
def _bytes_per_device(tree) -> dict:
    import jax
    per = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def train_phase(cfg, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.pipeline import SyntheticTokens, data_config_for
    from repro.dist.plan import Plan
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_training
    from repro.models.lm import Model

    mesh = make_host_mesh()
    plan = Plan(name="train-cli", vocab_chunk=min(2048, seq))
    tcfg = TrainConfig(lr=3e-3, total_steps=steps, warmup_steps=1)
    jstep, init_state = build_training(cfg, plan, tcfg, mesh)
    data = SyntheticTokens(data_config_for(
        cfg, ShapeConfig("smoke", seq, batch, "train")))
    state = init_state()

    devices = list(mesh.devices.flat)
    for what, tree in (("params", state["params"]), ("opt", state["opt"])):
        total = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        per = _bytes_per_device(tree)
        print(f"train: {what} {total} bytes; per device {per}", flush=True)
        require(sorted(per) == sorted(d.id for d in devices),
                f"{what} not on every device: {per}")
        require(max(per.values()) <= 0.3 * total,
                f"{what} not sharded over {len(devices)} devices: {per}")

    # reference: the step-0 loss of a plain forward on one device
    one = devices[0]
    batch0 = data.batch(0)
    params1 = jax.device_put(state["params"], one)
    ref_loss = float(jax.jit(
        lambda p, b: Model(cfg, plan).train_loss(p, b)[1]["loss"])(
            params1, jax.device_put(batch0, one)))
    del params1

    losses = []
    for i in range(steps):
        params, opt, metrics = jstep(state["params"], state["opt"],
                                     data.batch(i), jnp.int32(i))
        state = {"params": params, "opt": opt}
        losses.append(float(metrics["loss"]))
        print(f"train: step {i} loss {losses[-1]!r}", flush=True)
    print(f"train: step-0 loss {losses[0]!r} vs one-device forward "
          f"{ref_loss!r} (rtol {LOSS_RTOL})", flush=True)
    require(bool(np.isfinite(losses).all()), f"loss not finite: {losses}")
    require(abs(losses[0] - ref_loss) <= LOSS_RTOL * abs(ref_loss),
            f"sharded step-0 loss {losses[0]} != one-device {ref_loss}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase on 4 chips")
    args = ap.parse_args(argv)

    require((SRC / "repro").is_dir(),
            f"{SRC / 'repro'} not found: run from a checkout of the repo")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    import jax
    devices = jax.devices()
    dev = devices[0]
    require(dev.platform == "tpu",
            f"no TPU: JAX found {dev.platform} devices")
    print(f"device: {dev.device_kind} x{len(devices)}", flush=True)

    from repro.configs import get_config
    cfg = get_config(ARCH)
    for field, value in PUBLISHED.items():
        require(getattr(cfg, field) == value,
                f"{ARCH}.{field} = {getattr(cfg, field)}, not {value}")

    if args.four_chips:
        require(len(devices) == 4, f"{len(devices)} devices, not 4")
        with phase("train (4 chips, FSDP over data)"):
            train_phase(cfg)
        print(f"peak bytes in use per device: {peak_bytes(devices)}")
    else:
        with phase("planner"):
            planner_phase()
        with phase("serve"):
            serve_phase(cfg)
        print(f"peak bytes in use: {peak_bytes(devices[:1])}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
