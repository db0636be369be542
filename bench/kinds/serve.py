"""The serve kind of cell: a model behind the program's ``ContinuousBatcher``, fed an
open-loop schedule on the host clock.

Set-up draws the weights on the device in one jitted call from the seed
(the benchmark's own law, ``lm_reference``, placed in the program's tree
by ``lm_params``), builds the engine with the traffic file's slot pool,
and warms every shape the traffic uses by serving one two-token request
per prompt length of its grid through the engine's own ``submit`` /
``tick``.  The window then
submits each request of the schedule once it is due and drives ``tick``
whenever the engine holds work, sleeping only when it holds none.  The
host clock stamps each token as the engine hands it over (the engine calls
its metrics object right after it has read the token back from the
device).  After the close, ticks go on until every request due in the
window has its first token, so that TTFT counts every request; gaps
between tokens count up to the close.

The check: the engine's ``run`` finishes the requests still live, untimed,
and hands out every request's tokens.  A sample drawn from the seed of the
requests that finished in the window, with the longest among them, goes
through the plain reference (``bench/harness/lm_reference.py``) once the
engine is freed; the number compared is the widest gap by which a served
token's logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from bench.harness import lm_params, lm_reference, traffic as traffic_mod
from bench.harness.seeds import jax_key, rng_for

HOST_SPANS = ("tick", "idle_wait")
DRAIN_LIMIT_S = 60.0        # past the close, a first token that never comes


@dataclass
class Req:
    """One request of the window, on the host clock (seconds from the
    window's start)."""
    rid: str
    due_s: float
    prompt_len: int
    out_len: int
    tokens: np.ndarray
    admit_s: Optional[float] = None         # start of the admitting tick
    token_s: List[float] = field(default_factory=list)
    token_tick: List[int] = field(default_factory=list)

    @property
    def ttft_s(self) -> Optional[float]:
        return self.token_s[0] - self.due_s if self.token_s else None

    @property
    def finished(self) -> bool:
        return len(self.token_s) >= self.out_len


def _stamps(base):
    """The engine's metrics object, also stamping each token on the host
    clock as the engine hands it over."""
    class HostStamps(base):
        def __init__(self):
            super().__init__()
            self.t0 = time.perf_counter()
            self.tick = -1
            self.reqs = {}

        def on_token(self, rid, t):
            super().on_token(rid, t)
            r = self.reqs.get(rid)
            if r is not None:
                r.token_s.append(time.perf_counter() - self.t0)
                r.token_tick.append(self.tick)
    return HostStamps()


def build(cell):
    """Weights, model and engine: what a deployment holds."""
    import jax
    from repro.configs.base import ModelConfig
    from repro.models.lm import Model
    from repro.serve import ContinuousBatcher
    from repro.serve.metrics import ServeMetrics

    mcfg = ModelConfig(**cell.config["model"])
    model = Model(mcfg)
    key = jax_key(cell.seed)
    params = lm_params.program_params(reference_config(cell.config), key,
                                      jax.eval_shape(model.init, key))
    stamps = _stamps(ServeMetrics)
    engine = ContinuousBatcher(model, params,
                               n_slots=int(cell.traffic["n_slots"]),
                               cache_len=int(cell.traffic["cache_len"]),
                               metrics=stamps)
    return mcfg, engine, stamps


def warm_up(engine, mcfg, lengths, seed):
    """Serve one two-token request per prompt length through the engine's
    own path: compiles each prefill, the insert and the pool step."""
    from repro.serve import Request
    rng = rng_for(seed, 99)
    reqs = [Request(rid=f"warm{n}", arch=mcfg.name, prompt_len=n, max_gen=2,
                    tokens=rng.integers(0, mcfg.vocab_size, n,
                                        dtype=np.int32),
                    arrival_s=engine.now_s)
            for n in lengths]
    engine.run(reqs)


def drive(engine, stamps, reqs: List[Req], seconds: float, t0: float,
          arch: str, poll=lambda: None) -> List[float]:
    """The window: open-loop submission on the host clock; ``poll`` runs
    between ticks.  Returns the start of each tick, seconds from the
    window's start."""
    import jax
    from repro.serve import Request

    stamps.t0 = t0
    pending = sorted(reqs, key=lambda r: r.due_s)
    waiting: List[Req] = []         # submitted, no first token yet
    tick_s: List[float] = []
    i, busy = 0, False
    while True:
        poll()
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i].due_s <= now:
            r = pending[i]
            stamps.reqs[r.rid] = r
            engine.submit(Request(rid=r.rid, arch=arch,
                                  prompt_len=r.prompt_len,
                                  max_gen=r.out_len, tokens=r.tokens,
                                  arrival_s=engine.now_s))
            waiting.append(r)
            busy = True
            i += 1
        waiting = [r for r in waiting if not r.token_s]
        if now >= seconds and i == len(pending) and not waiting:
            break
        if now >= seconds + DRAIN_LIMIT_S:
            break
        if busy:
            stamps.tick = len(tick_s)
            tick_s.append(now)
            with jax.profiler.TraceAnnotation("tick"):
                busy = engine.tick()
        else:
            nxt = pending[i].due_s if i < len(pending) else seconds
            with jax.profiler.TraceAnnotation("idle_wait"):
                time.sleep(max(0.0, nxt - (time.perf_counter() - t0)))
    for r in reqs:
        if r.token_tick:
            r.admit_s = tick_s[r.token_tick[0]]
    return tick_s


def reference_config(config: dict) -> dict:
    """What the reference reads of a configuration file."""
    return {**config["model"], **config["reference"],
            "embed_std": config["weights"]["embed_std"]}


def sample(reqs: List[Req], seed: int, n: int) -> List[Req]:
    """Finished requests to check: the longest, and others drawn from the
    seed, ``n`` in all."""
    done = [r for r in reqs if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.out_len, r.rid))
    rest = [r for r in done if r is not longest]
    rng = rng_for(seed, 7)
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[k] for k in sorted(pick)]


def run(cell, devs):
    from bench.harness.core import memory_peak
    tr, run_ = cell.traffic, cell.run
    mcfg, engine, stamps = build(cell)
    sched = traffic_mod.schedule(tr, cell.seed, cell.seconds)
    toks = traffic_mod.prompt_tokens(cell.seed, sched, mcfg.vocab_size)
    reqs = [Req(f"r{d.index}", d.due_s, d.prompt_len, d.out_len, t)
            for d, t in zip(sched, toks)]
    warm_up(engine, mcfg, traffic_mod.prompt_lengths(tr), cell.seed)

    with cell.window() as t0:
        run_.tick_s = drive(engine, stamps, reqs, cell.seconds, t0,
                            mcfg.name, cell.poll)
    run_.memory_peak_bytes = memory_peak(devs)
    run_.requests = reqs
    run_.model = mcfg
    run_.attempted = len(reqs)
    run_.failed = sum(1 for r in reqs if not r.token_s)

    # the engine hands out what it served through ``run``, which first
    # finishes the requests still live; their tokens go unstamped
    stamps.reqs = {}
    served = engine.run()
    del engine, stamps
    gc.collect()
    check = cell.config["check"]
    picked = sample(reqs, cell.seed, int(check["sample_requests"]))
    if not picked:
        run_.check("served_logit_gap", float("inf"), check["logit_gap"])
        return
    run_.sample = {"prompts": [r.tokens for r in picked],
                   "served": [np.asarray(served[r.rid], np.int32)
                              for r in picked]}
    ref = lm_reference.Reference(reference_config(cell.config),
                                 int(tr["cache_len"]))
    gap = lm_reference.gaps(ref.read(jax_key(cell.seed),
                                     **run_.sample))
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
    ctl = lm_reference.Reference(cfg, length, fp8=True).read(
        key, **run.sample)
    rows = lm_reference.Reference(cfg, length).read(
        key, **run.sample, query=[r["first"] for r in ctl])
    out = run.fresh()
    out.check("served_logit_gap", float(lm_reference.gaps(rows).max()),
              run.config["check"]["logit_gap"])
    return out
