"""Serving driver: continuous-batching engine over one model replica.

Runs the published config by default; ``--reduced`` opts into the tiny
same-family config for the CPU.  ``generate`` remains the sequential
batch reference (prefill + greedy decode, jits memoized per model so
repeated calls never re-trace); the CLI routes through
:class:`repro.serve.ContinuousBatcher`, where requests join and leave the
running batch at decode-step granularity and the KV slot pool persists
across requests.  At pod scale the same ``decode_step`` is what the decode
dry-run cells lower — sharded per the destination's plan (e.g. the
``serve-low-mem`` serving genes), not pinned to any one mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --reduced --batch 4 --prompt-len 32 --gen 16
  # open-loop synthetic trace with staggered arrivals:
  PYTHONPATH=src python -m repro.launch.serve --reduced --trace 8
"""
from __future__ import annotations

import argparse
import time
import weakref

import jax
import jax.numpy as jnp

# per-model memo of the jitted prefill/step pair: repeated generate()
# calls (the benchmark's static baseline loops it) must not pay a fresh
# trace per call — jax.jit caches compiles per function object, so the
# function objects themselves must be reused
_GENERATE_JITS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _jits_for(model, cache_len: int):
    per_model = _GENERATE_JITS.setdefault(model, {})
    pair = per_model.get(cache_len)
    if pair is None:
        prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len))
        step = jax.jit(model.decode_step)
        pair = per_model[cache_len] = (prefill, step)
    return pair


def generate(model, params, batch, prompt_len: int, gen: int,
             cache_len: int):
    """Greedy decode `gen` tokens after prefilling `batch['tokens']`.

    The sequential reference the continuous engine's parity test compares
    against: whole batch prefilled together, decoded in lock-step."""
    prefill, step = _jits_for(model, cache_len)
    logits, cache = prefill(params, batch)
    toks = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    toks.append(tok)
    for i in range(gen - 1):
        logits, cache = step(params, cache, tok,
                             jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        toks.append(tok)
    return jnp.concatenate(toks, axis=1)


def _request_extras(cfg, key, n: int = 1) -> dict:
    """Modality context (vlm/audio) for one synthetic request batch."""
    extras = {}
    if cfg.family == "vlm":
        extras["img_embed"] = jax.random.normal(
            key, (n, cfg.n_img_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        extras["frames"] = jax.random.normal(
            key, (n, cfg.n_frames, cfg.d_model), jnp.float32)
    return extras


def synthetic_trace(cfg, n: int, prompt_len: int, gen: int, *,
                    gap_s: float = 0.02, seed: int = 1):
    """Open-loop arrival trace: ``n`` requests arriving ``gap_s`` apart
    (staggered — the shape continuous batching wins on)."""
    from repro.serve import Request
    key = jax.random.PRNGKey(seed)
    reqs = []
    for i in range(n):
        reqs.append(Request(
            rid=f"r{i}", arch=cfg.name, prompt_len=prompt_len, max_gen=gen,
            arrival_s=i * gap_s,
            extras=_request_extras(cfg, jax.random.fold_in(key, i))))
    return reqs


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU smoke runs)")
    ap.add_argument("--batch", type=int, default=4,
                    help="slot-pool width (concurrent requests)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--trace", type=int, default=0, metavar="N",
                    help="serve a synthetic open-loop trace of N staggered "
                         "arrivals instead of one gang batch")
    return ap.parse_args(argv)


def config_for(args):
    """The model config a parsed command line serves."""
    from repro.configs import get_config
    cfg = get_config(args.arch)
    return cfg.reduced() if args.reduced else cfg


def build_engine(cfg, *, n_slots: int, cache_len: int, seed: int = 0):
    """One replica: random parameters from ``seed``, initialised under
    ``jit`` (on the device, never materialised on the host), behind a
    :class:`repro.serve.ContinuousBatcher`."""
    from repro.models.lm import Model
    from repro.power import envelope_for
    from repro.serve import ContinuousBatcher

    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return ContinuousBatcher(model, params, n_slots=n_slots,
                             cache_len=cache_len,
                             envelope=envelope_for(None))


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = parse_args(argv)

    from repro.serve import Request

    cfg = config_for(args)
    engine = build_engine(cfg, n_slots=args.batch,
                          cache_len=args.prompt_len + args.gen)
    if args.trace:
        reqs = synthetic_trace(cfg, args.trace, args.prompt_len, args.gen)
    else:
        key = jax.random.PRNGKey(1)
        reqs = [Request(rid=f"r{i}", arch=cfg.name,
                        prompt_len=args.prompt_len, max_gen=args.gen,
                        extras=_request_extras(cfg,
                                               jax.random.fold_in(key, i)))
                for i in range(args.batch)]

    t0 = time.perf_counter()
    out = engine.run(reqs)
    dt = time.perf_counter() - t0
    s = engine.metrics.summary()
    n_tok = sum(len(v) for v in out.values())
    print(f"arch={cfg.name} served {len(out)} requests, {n_tok} tokens "
          f"in {dt:.2f}s wall ({n_tok / dt:.1f} tok/s incl. compile); "
          f"ttft_p50={s['ttft_p50_s']}s traces={engine.traces}")
    first = sorted(out)[0]
    print("sample tokens:", out[first][:12].tolist())
    return out


if __name__ == "__main__":
    main()
