"""The serve_hybrid kind of cell: a Mamba-2 + GQA hybrid (Granite 4.0-H)
behind the program's ``ContinuousBatcher``, fed an open-loop schedule on
the host clock.

It is the serve kind (``bench/kinds/serve.py``: its ``drive``,
``warm_up``, ``sample`` and host stamps) with the hybrid's weights, drawn
by ``hybrid_reference``'s law and placed by ``hybrid_params``, and the
hybrid's plain reference for the check: the widest gap by which a served
token's logit lies below the float32 reference's best at that position,
over a sample of finished requests drawn from the seed (the longest among
them), all in one batch once the engine is freed.

With ``--trace 1`` the program's ambient tracer is
``repro.obs.profiler_tracer()`` for the window: its spans land on the
profiler's host plane beside the kind's own (``HOST_SPANS``), so idle gaps
are named by them, and its records reach the readers as
``run.obs_records``.
"""
from __future__ import annotations

import contextlib
import gc

import numpy as np

from bench.harness import core, hybrid_params, hybrid_reference
from bench.harness import lm_reference, traffic as traffic_mod
from bench.harness.seeds import jax_key

serve = core.load_kind("serve")

PROGRAM_SPANS = ("engine_tick", "admit", "prefill", "first_token_wait",
                 "insert", "decode", "token_wait", "emit")
HOST_SPANS = tuple(serve.HOST_SPANS) + PROGRAM_SPANS


def model_config(config: dict):
    """The program's ``ModelConfig`` of a configuration file's ``model``
    (its ``hybrid`` and ``ssm`` groups nested)."""
    from repro.configs.base import HybridConfig, ModelConfig, SSMConfig
    m = dict(config["model"])
    m["hybrid"] = HybridConfig(**{**m["hybrid"],
                                  "pattern": tuple(m["hybrid"]["pattern"])})
    m["ssm"] = SSMConfig(**m["ssm"])
    return ModelConfig(**m)


def reference_config(config: dict) -> dict:
    """What the reference reads of a configuration file."""
    m = config["model"]
    return {**m, **m["ssm"], "pattern": list(m["hybrid"]["pattern"]),
            "embed_std": config["weights"]["embed_std"]}


def build(cell):
    """Weights, model and engine: what a deployment holds."""
    import jax
    from repro.models.lm import Model
    from repro.serve import ContinuousBatcher
    from repro.serve.metrics import ServeMetrics

    mcfg = model_config(cell.config)
    model = Model(mcfg)
    key = jax_key(cell.seed)
    params = hybrid_params.program_params(reference_config(cell.config),
                                          key,
                                          jax.eval_shape(model.init, key))
    stamps = serve._stamps(ServeMetrics)
    engine = ContinuousBatcher(model, params,
                               n_slots=int(cell.traffic["n_slots"]),
                               cache_len=int(cell.traffic["cache_len"]),
                               metrics=stamps)
    return mcfg, engine, stamps


def run(cell, devs):
    from repro.obs import get_tracer, profiler_tracer, use_tracer
    tr, run_ = cell.traffic, cell.run
    mcfg, engine, stamps = build(cell)
    sched = traffic_mod.schedule(tr, cell.seed, cell.seconds)
    toks = traffic_mod.prompt_tokens(cell.seed, sched, mcfg.vocab_size)
    reqs = [serve.Req(f"r{d.index}", d.due_s, d.prompt_len, d.out_len, t)
            for d, t in zip(sched, toks)]
    serve.warm_up(engine, mcfg, traffic_mod.prompt_lengths(tr), cell.seed)

    # a recording tracer already installed (bench/tools/spans.py) is kept
    tracer = profiler_tracer() if cell.trace and not get_tracer().enabled \
        else None
    scope = contextlib.nullcontext() if tracer is None else use_tracer(tracer)
    with cell.window() as t0, scope:
        run_.tick_s = serve.drive(engine, stamps, reqs, cell.seconds, t0,
                                  mcfg.name, cell.poll)
    if tracer is not None:
        run_.obs_records = tracer.records
    run_.memory_peak_bytes = core.memory_peak(devs)
    run_.requests = reqs
    run_.model = mcfg
    run_.attempted = len(reqs)
    run_.failed = sum(1 for r in reqs if not r.token_s)

    stamps.reqs = {}
    served = engine.run()
    del engine, stamps
    gc.collect()
    check = cell.config["check"]
    picked = serve.sample(reqs, cell.seed, int(check["sample_requests"]))
    if not picked:
        run_.check("served_logit_gap", float("inf"), check["logit_gap"])
        return
    run_.sample = {"prompts": [r.tokens for r in picked],
                   "served": [np.asarray(served[r.rid], np.int32)
                              for r in picked]}
    ref = hybrid_reference.Reference(reference_config(cell.config),
                                     int(tr["cache_len"]))
    gap = lm_reference.gaps(ref.read(jax_key(cell.seed), **run_.sample))
    run_.checked_tokens = int(gap.size)
    run_.check("served_logit_gap", float(gap.max()), check["logit_gap"])


def control(run):
    """The control: the reference in float8, put in the engine's place over
    the same prompts and served tokens; the widest gap of the token it puts
    first, in the float32 reference's logits, checked at the
    configuration's limit in a run of its own (returned)."""
    cfg = reference_config(run.config)
    length = int(run.traffic["cache_len"])
    key = jax_key(run.cell.seed)
    ctl = hybrid_reference.Reference(cfg, length, fp8=True).read(
        key, **run.sample)
    rows = hybrid_reference.Reference(cfg, length).read(
        key, **run.sample, query=[r["first"] for r in ctl])
    out = run.fresh()
    out.check("served_logit_gap", float(lm_reference.gaps(rows).max()),
              run.config["check"]["logit_gap"])
    return out
