"""Continuous batching: slot-based decode over a fixed-shape pool.

The engine holds ``n_slots`` request slots in one decode cache and
advances them with **one** jitted step over the whole pool — requests join
and leave at decode-step granularity without ever changing the traced
shapes, so the step compiles exactly once per engine (pinned by
``ContinuousBatcher.traces`` and tests/test_serve_batching.py).

The pool takes one of two forms, chosen once from the model's layer type
(``ContinuousBatcher.pool_path``):

  * ``"inplace"`` — a dense model with a plain KV stack, or a hybrid of
    whole periods (Granite 4.0-H: Mamba-2 and attention blocks): the pool
    is ``init_cache(cfg, n_slots, cache_len)``, its batch axis the slot
    axis (dense ``[L, S, W, KV, Dh]``; a hybrid's leaves ``[groups, S,
    ...]``), and the step is ``Model.decode_step`` with a position per
    slot, which writes one K/V row per slot and attention layer and
    rewrites each recurrent layer's state;
  * ``"vmap"`` — ssm, other hybrids, MoE, vlm and audio models: one
    batch-1 cache per slot stacked on a new leading axis, stepped by
    ``vmap(decode_step)``.

Both jitted programs take the pool donated and hand it back updated.

Slot-pool invariants (the ROADMAP contract):

  * a slot's cache is replaced wholesale at admission (jitted
    ``dynamic_update_slice`` insert at the slot's index on the slot axis,
    traced index — one trace total), so stale state from a previous
    occupant can never leak;
  * inactive slots still run the decode step (fixed shapes beat masked
    compute at this scale); their outputs are discarded host-side and their
    cache garbage is overwritten by the next insert;
  * prefill runs at the **exact** prompt length, one jit per unique length
    — right-padding a prompt would poison recurrent (ssm/hybrid) state and
    window-ring caches, and a padded prefill is *not* token-identical to
    the sequential reference;
  * at most one prefill is interleaved per tick, so admissions never starve
    running decodes.

Time is a virtual tick clock (``tick_s`` per engine tick): arrivals,
TTFT/TPOT and the continuous-vs-static comparison all live on one
deterministic timeline, independent of host load.

Spans (:mod:`repro.obs`, on the ambient tracer's own clock; no-ops unless
a recording tracer is installed) name what the host does inside a tick:
``engine_tick`` holds ``admit`` (``prefill`` dispatch, ``first_token_wait``
on the first token's logits, ``insert`` dispatch), ``decode`` (staging and
dispatch of the pool step), ``token_wait`` (reading the step's tokens
back) and ``emit`` (per-slot bookkeeping, metrics callbacks, retirement).
``submit`` records a ``submit`` event with the request's ``rid``, which
its ``admit`` span carries too.  Counters from shapes, free on the device:
``prefill`` carries ``ssd_chunks`` and ``ssd_pad`` (positions padded in the
last SSD chunk) where the model has Mamba-2 layers; ``insert`` carries a
slot's ``state_bytes`` (recurrent) and ``kv_bytes``; ``decode`` the
``state_bytes`` of recurrent state the step rewrites, every slot's.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.obs import get_tracer
from repro.serve.metrics import ServeMetrics
from repro.serve.request import Request

DEFAULT_TICK_S = 0.01


KV_LEAVES = ("k", "v", "k_scale", "v_scale")


def slot_state_bytes(pool, n_slots: int) -> Tuple[int, int]:
    """One slot's share of the pool: (recurrent state bytes, K/V bytes).
    A leaf named as a K/V buffer is K/V; any other is recurrent state."""
    import jax
    state = kv = 0
    for path, x in jax.tree_util.tree_flatten_with_path(pool)[0]:
        n = math.prod(x.shape) * x.dtype.itemsize // n_slots
        if getattr(path[-1], "key", None) in KV_LEAVES:
            kv += n
        else:
            state += n
    return state, kv


def synth_tokens(rid: str, prompt_len: int, vocab: int) -> np.ndarray:
    """Deterministic synthetic prompt for a request without one (traces,
    benchmarks): seeded from the request id, stable across runs."""
    rng = np.random.RandomState(zlib.crc32(rid.encode()) & 0x7FFFFFFF)
    return rng.randint(0, vocab, size=(prompt_len,)).astype(np.int32)


class ContinuousBatcher:
    """Slot-pool continuous batching over one model replica.

    ``model`` / ``params`` are a :class:`repro.models.lm.Model` and its
    parameters; ``n_slots`` fixes the traced pool width and ``cache_len``
    the per-slot KV/state length.  ``envelope``
    (:class:`repro.power.PowerEnvelope`) prices each tick's energy into
    the metrics; ``eos_id`` stops a request early on that token.
    """

    def __init__(self, model, params, *, n_slots: int, cache_len: int,
                 metrics: Optional[ServeMetrics] = None,
                 envelope=None, eos_id: Optional[int] = None,
                 tick_s: float = DEFAULT_TICK_S):
        import jax
        import jax.numpy as jnp
        from repro.models.lm import init_cache, per_row_positions

        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1: {n_slots}")
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.n_slots = int(n_slots)
        self.cache_len = int(cache_len)
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.eos_id = eos_id
        self.tick_s = float(tick_s)
        self._track = f"engine:{self.cfg.name}"
        self.energy_model = None
        if envelope is not None:
            from repro.power import EnergyModel
            self.energy_model = EnergyModel(envelope)

        # trace counters: the counted bodies run only while jax is tracing,
        # so a steady-state tick leaves every counter flat — the engine-side
        # half of the zero-recompile guarantee
        self.traces = {"decode_step": 0, "insert": 0, "prefill": 0}

        # a plain KV stack, or a hybrid whose every cache leaf is stacked
        # by group, batches its slots: the pool is one decode cache whose
        # batch axis is the slot, stepped with a position per slot and
        # updated in place.  Batching a MoE model's slots changes what its
        # capacity drops; other families keep one batch-1 cache per slot
        # under vmap.
        inplace = self.cfg.moe is None and per_row_positions(self.cfg)
        self.pool_path = "inplace" if inplace else "vmap"
        quant = model.plan.kv_cache_quant
        if inplace:
            self._pool = init_cache(self.cfg, self.n_slots, self.cache_len,
                                    quant=quant)
        else:
            one = init_cache(self.cfg, 1, self.cache_len, quant=quant)
            self._pool = jax.tree.map(
                lambda x: jnp.zeros((self.n_slots,) + x.shape, x.dtype), one)

        def one_step(params, cache, tok, pos):
            logits, new_cache = model.decode_step(params, cache, tok, pos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B]
            return nxt, new_cache

        def pool_step(params, pool, toks, poss):
            self.traces["decode_step"] += 1
            if inplace:
                return one_step(params, pool, toks[:, 0], poss)
            return jax.vmap(one_step, in_axes=(None, 0, 0, 0))(
                params, pool, toks, poss)

        def pool_insert(pool, one_cache, idx):
            self.traces["insert"] += 1
            if inplace:     # the slot is the cache's batch axis
                def put(p, o):
                    return jax.lax.dynamic_update_slice_in_dim(p, o, idx, 1)
            else:           # the slot is a new leading axis
                def put(p, o):
                    return jax.lax.dynamic_update_index_in_dim(p, o, idx, 0)
            return jax.tree.map(lambda p, o: put(p, o.astype(p.dtype)),
                                pool, one_cache)

        self.slot_state_bytes, self.slot_kv_bytes = slot_state_bytes(
            self._pool, self.n_slots)
        ssm_layers = self.cfg.ssm is not None and (
            self.cfg.family == "ssm" or "mamba" in self.cfg.hybrid.pattern)
        self._ssd_chunk = model.plan.ssd_chunk if ssm_layers else None

        # the pool is donated to both: each returns it updated in place
        self._step = jax.jit(pool_step, donate_argnums=1)
        self._insert = jax.jit(pool_insert, donate_argnums=0)
        self._prefill_jits: Dict[int, object] = {}

        # host-side slot state (numpy: mutated at tick granularity)
        self._active = np.zeros(self.n_slots, dtype=bool)
        self._pos = np.zeros(self.n_slots, dtype=np.int32)
        self._last_tok = np.zeros(self.n_slots, dtype=np.int32)
        self._remaining = np.zeros(self.n_slots, dtype=np.int64)
        self._slot_req: List[Optional[Request]] = [None] * self.n_slots
        self._ticks = 0
        self._queue: List[Request] = []       # arrived, awaiting a slot
        self._pending: List[Request] = []     # on the trace, not yet arrived
        self._out: Dict[str, List[int]] = {}

    # ------------------------------------------------------------- intake
    @property
    def now_s(self) -> float:
        return self._ticks * self.tick_s

    @property
    def free_slots(self) -> int:
        return int((~self._active).sum())

    @property
    def live(self) -> int:
        return int(self._active.sum())

    def submit(self, req: Request):
        if req.arch and req.arch != self.cfg.name:
            raise ValueError(
                f"request {req.rid} wants arch {req.arch!r}, engine serves "
                f"{self.cfg.name!r} (route first: repro.serve.router)")
        self.metrics.on_submit(req.rid, req.arrival_s, arch=req.arch)
        get_tracer().event("submit", cat="engine", track=self._track,
                           rid=req.rid)
        self._pending.append(req)
        self._pending.sort(key=lambda r: (r.arrival_s, r.rid))

    # ------------------------------------------------------------ prefill
    def _prefill_fn(self, prompt_len: int):
        import jax
        fn = self._prefill_jits.get(prompt_len)
        if fn is None:
            def pf(params, batch):
                self.traces["prefill"] += 1
                return self.model.prefill(params, batch, self.cache_len)
            fn = self._prefill_jits[prompt_len] = jax.jit(pf)
        return fn

    def _admit(self, req: Request, slot: int, t_done: float):
        import jax.numpy as jnp
        toks = req.tokens
        if toks is None:
            toks = synth_tokens(req.rid, req.prompt_len,
                                self.cfg.vocab_size)
        toks = np.asarray(toks, dtype=np.int32).reshape(1, -1)
        if toks.shape[1] != req.prompt_len:
            raise ValueError(f"request {req.rid}: tokens length "
                             f"{toks.shape[1]} != prompt_len "
                             f"{req.prompt_len}")
        batch = {"tokens": jnp.asarray(toks)}
        for k, v in req.extras.items():
            batch[k] = v
        tracer, track = get_tracer(), self._track
        ssd = {}
        if self._ssd_chunk is not None:
            from repro.models.ssm import chunk_layout
            _, n, pad = chunk_layout(self.cfg, req.prompt_len,
                                     self._ssd_chunk)
            ssd = {"ssd_chunks": n, "ssd_pad": pad}
        with tracer.span("prefill", cat="engine", track=track, **ssd):
            logits, cache = self._prefill_fn(req.prompt_len)(self.params,
                                                             batch)
        with tracer.span("first_token_wait", cat="engine", track=track):
            first = int(np.asarray(logits).argmax(axis=-1)[0])
        with tracer.span("insert", cat="engine", track=track,
                         state_bytes=self.slot_state_bytes,
                         kv_bytes=self.slot_kv_bytes):
            self._pool = self._insert(self._pool, cache, slot)
        self._active[slot] = True
        self._pos[slot] = req.prompt_len
        self._last_tok[slot] = first
        self._remaining[slot] = req.max_gen - 1
        self._slot_req[slot] = req
        self._out[req.rid] = [first]

        self.metrics.on_admit(req.rid, t_done)
        self.metrics.on_token(req.rid, t_done)
        if self._remaining[slot] <= 0 or \
                (self.eos_id is not None and first == self.eos_id):
            self._retire(slot, t_done)

    def _retire(self, slot: int, t: float):
        req = self._slot_req[slot]
        self._active[slot] = False
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        if req is not None:
            self.metrics.on_finish(req.rid, t)

    # --------------------------------------------------------------- tick
    def tick(self) -> bool:
        """One engine tick: admit due arrivals (≤1 prefill), advance every
        active slot one decode step, retire finished requests.  Returns
        True while any work remains (live slots, queue, or future
        arrivals)."""
        tracer, track = get_tracer(), self._track
        with tracer.span("engine_tick", cat="engine", track=track,
                         tick=self._ticks) as tick_span:
            admitted, live, joules = self._tick(tracer, track)
            tick_span.set(live=live, queued=len(self._queue),
                          admitted=admitted, joules=joules)
        return bool(self._active.any() or self._queue or self._pending)

    def _tick(self, tracer, track):
        import jax.numpy as jnp

        now = self.now_s
        t_end = now + self.tick_s
        while self._pending and self._pending[0].arrival_s <= now:
            self._queue.append(self._pending.pop(0))

        # one interleaved prefill per tick: admissions must not starve the
        # decode cadence of the requests already running
        admitted = 0
        if self._queue and self.free_slots:
            slot = int(np.flatnonzero(~self._active)[0])
            req = self._queue.pop(0)
            with tracer.span("admit", cat="engine", track=track,
                             rid=req.rid, prompt_len=req.prompt_len,
                             slot=slot):
                self._admit(req, slot, t_end)
            admitted = 1

        live_before = [r.rid for r in self._slot_req if r is not None]
        if self._active.any():
            with tracer.span("decode", cat="engine", track=track,
                             path=self.pool_path,
                             state_bytes=self.slot_state_bytes
                             * self.n_slots):
                toks = jnp.asarray(
                    self._last_tok.reshape(self.n_slots, 1, 1))
                poss = jnp.asarray(self._pos)
                nxt, self._pool = self._step(self.params, self._pool, toks,
                                             poss)
            with tracer.span("token_wait", cat="engine", track=track):
                nxt = np.asarray(nxt).reshape(self.n_slots)
            with tracer.span("emit", cat="engine", track=track):
                for slot in np.flatnonzero(self._active):
                    req = self._slot_req[slot]
                    tok = int(nxt[slot])
                    self._out[req.rid].append(tok)
                    self._last_tok[slot] = tok
                    self._pos[slot] += 1
                    self._remaining[slot] -= 1
                    self.metrics.on_token(req.rid, t_end)
                    if self._remaining[slot] <= 0 or \
                            (self.eos_id is not None and tok == self.eos_id):
                        self._retire(slot, t_end)

        self._ticks += 1
        joules = 0.0
        if self.energy_model is not None:
            joules = self.energy_model.tick_joules(
                self.tick_s, len(live_before) / self.n_slots)
        self.metrics.charge_tick(joules, live_before)
        return admitted, len(live_before), joules

    # ---------------------------------------------------------------- run
    def run(self, requests: Optional[List[Request]] = None,
            max_ticks: int = 1_000_000) -> Dict[str, np.ndarray]:
        """Drive ticks until every submitted request completes; returns
        ``{rid: generated tokens [max_gen]}`` (greedy decode)."""
        for req in requests or ():
            self.submit(req)
        # fast-forward to the first arrival: an empty engine burning idle
        # ticks until the trace starts is not useful work
        if not self._active.any() and not self._queue and self._pending:
            first = self._pending[0].arrival_s
            if first > self.now_s:
                self._ticks = int(np.ceil(first / self.tick_s - 1e-9))
        for _ in range(max_ticks):
            if not self.tick():
                break
        else:
            raise RuntimeError(f"engine did not drain in {max_ticks} ticks")
        return {rid: np.asarray(toks, dtype=np.int32)
                for rid, toks in self._out.items()}
