"""repro.serve.batching: continuous batching parity + fixed-shape pool.

Pins the engine contract: greedy continuous-batched decode is
token-identical to the sequential ``generate`` reference for the same
request set — including requests that join mid-flight, finish early, and
recycle slots — and the jitted decode step / insert trace exactly once per
engine no matter how many requests flow through.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import generate
from repro.models.lm import Model
from repro.serve import ContinuousBatcher, Request

ARCH = "granite-3-2b"


def make_model(arch=ARCH, seed=0, plan=None):
    cfg = get_config(arch).reduced()
    model = Model(cfg, plan)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


def prompts(cfg, n, prompt_len, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n, prompt_len), 0, cfg.vocab_size),
        dtype=np.int32)


def sequential_reference(model, params, toks, prompt_len, gen, cache_len):
    """Per-request batch-1 greedy decode through the public reference."""
    out = {}
    for i in range(toks.shape[0]):
        ref = generate(model, params, {"tokens": toks[i:i + 1]},
                       prompt_len=prompt_len, gen=gen, cache_len=cache_len)
        out[f"r{i}"] = np.asarray(ref)[0]
    return out


@pytest.mark.parametrize("arch,cache_len,quant", [
    (ARCH, 32, False),
    ("h2o-danube-1.8b", 12, False),      # window ring: slot pos % 12 wraps
    (ARCH, 32, True),                    # kv_cache_quant: int8 + scales
    ("granite-4.0-h-micro", 32, False),  # Mamba-2 state + NoPE K/V stack
], ids=["dense", "windowed", "int8kv", "hybrid"])
def test_parity_with_midflight_joins_and_early_finishes(arch, cache_len,
                                                        quant):
    """The satellite pin: staggered arrivals (requests join while others
    decode), heterogeneous max_gen (early finishers free slots mid-run),
    and more requests than slots (slot recycling) — token-identical to the
    sequential reference throughout, on the in-place pool."""
    from repro.dist.plan import Plan
    cfg, model, params = make_model(arch, plan=Plan(kv_cache_quant=quant))
    prompt_len = 8
    gens = [6, 3, 9, 4, 7]                       # early finishes + stragglers
    toks = prompts(cfg, len(gens), prompt_len)
    engine = ContinuousBatcher(model, params, n_slots=2,
                               cache_len=cache_len)
    reqs = [Request(rid=f"r{i}", arch=cfg.name, prompt_len=prompt_len,
                    max_gen=gens[i], tokens=toks[i],
                    arrival_s=i * 1.5 * engine.tick_s)
            for i in range(len(gens))]
    out = engine.run(reqs)
    assert engine.pool_path == "inplace"

    for i, g in enumerate(gens):
        ref = np.asarray(generate(
            model, params, {"tokens": toks[i:i + 1]},
            prompt_len=prompt_len, gen=g, cache_len=cache_len))[0]
        assert np.array_equal(out[f"r{i}"], ref), f"r{i}"
        assert out[f"r{i}"].shape == (g,)
    assert engine.metrics.summary()["completed"] == len(gens)


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "recurrentgemma-2b"])
def test_parity_holds_for_recurrent_families(arch):
    """ssm/hybrid recurrent state survives the slot pool: exact-length
    prefill + wholesale slot insert keep the state identical to the
    sequential path (right-padding would corrupt it)."""
    cfg, model, params = make_model(arch)
    toks = prompts(cfg, 3, 8)
    engine = ContinuousBatcher(model, params, n_slots=2, cache_len=16)
    reqs = [Request(rid=f"r{i}", arch=cfg.name, prompt_len=8, max_gen=5,
                    tokens=toks[i], arrival_s=i * engine.tick_s)
            for i in range(3)]
    out = engine.run(reqs)
    ref = sequential_reference(model, params, toks, 8, 5, 16)
    for rid in ref:
        assert np.array_equal(out[rid], ref[rid]), rid


def _bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("arch,path", [
    (ARCH, "inplace"),
    ("granite-4.0-h-micro", "inplace"),
    ("mamba2-1.3b", "vmap"),
    ("recurrentgemma-2b", "vmap"),
    ("moonshot-v1-16b-a3b", "vmap"),
])
def test_pool_is_donated_and_never_copied(arch, path):
    """The step and the insert take the pool donated and hand it back in
    place: the compiled programs alias at least the pool's bytes and
    allocate no second pool.  Only a plain KV stack or a hybrid of whole
    periods takes the in-place pool; other recurrent state and MoE keep
    one cache per slot under vmap."""
    cfg, model, params = make_model(arch)
    engine = ContinuousBatcher(model, params, n_slots=4, cache_len=32)
    assert engine.pool_path == path
    pool = engine._pool
    n = _bytes(pool)
    toks = np.zeros((4, 1, 1), np.int32)
    poss = np.zeros((4,), np.int32)
    _, one = model.prefill(params, {"tokens": prompts(cfg, 1, 8)}, 32)
    for fn, args in ((engine._step, (params, pool, toks, poss)),
                     (engine._insert, (pool, one, 1))):
        ma = fn.lower(*args).compile().memory_analysis()
        assert ma.alias_size_in_bytes >= n
        assert ma.output_size_in_bytes - ma.alias_size_in_bytes < n
    if path == "inplace":       # the slot is every leaf's batch axis
        assert all(x.shape[1] == 4 for x in jax.tree.leaves(pool))
    if arch == ARCH:
        assert jax.tree.leaves(pool)[0].shape == (cfg.n_layers, 4, 32,
                                                  cfg.n_kv_heads,
                                                  cfg.head_dim)


def test_decode_step_traces_exactly_once():
    """Fixed-shape slot pool: the jitted step and the jitted insert are
    traced once per engine; a full run over joins/leaves/recycles adds no
    retrace, and prefill traces once per unique prompt length."""
    cfg, model, params = make_model()
    engine = ContinuousBatcher(model, params, n_slots=2, cache_len=32)
    toks8 = prompts(cfg, 4, 8)
    toks5 = prompts(cfg, 2, 5, seed=2)
    reqs = [Request(rid=f"a{i}", arch=cfg.name, prompt_len=8, max_gen=4,
                    tokens=toks8[i], arrival_s=i * engine.tick_s)
            for i in range(4)]
    reqs += [Request(rid=f"b{i}", arch=cfg.name, prompt_len=5, max_gen=3,
                     tokens=toks5[i], arrival_s=i * engine.tick_s)
             for i in range(2)]
    engine.run(reqs)
    assert engine.traces["decode_step"] == 1
    assert engine.traces["insert"] == 1
    assert engine.traces["prefill"] == 2         # one per unique length
    # a second wave through the same engine re-traces nothing
    more = [Request(rid=f"c{i}", arch=cfg.name, prompt_len=8, max_gen=4,
                    tokens=toks8[i]) for i in range(2)]
    engine.run(more)
    assert engine.traces == {"decode_step": 1, "insert": 1, "prefill": 2}


def test_metrics_ttft_energy_and_arrival_gating():
    from repro.power import GENERIC
    cfg, model, params = make_model()
    engine = ContinuousBatcher(model, params, n_slots=2, cache_len=32,
                               envelope=GENERIC)
    toks = prompts(cfg, 3, 8)
    # r2 arrives much later: its TTFT starts at its own arrival, and the
    # engine must not admit it early
    reqs = [Request(rid=f"r{i}", arch=cfg.name, prompt_len=8, max_gen=4,
                    tokens=toks[i],
                    arrival_s=[0.0, 0.0, 20 * engine.tick_s][i])
            for i in range(3)]
    engine.run(reqs)
    s = engine.metrics.summary()
    assert s["completed"] == 3 and s["rejected"] == 0
    assert s["tokens"] == 12
    assert s["ttft_p50_s"] is not None and s["ttft_p50_s"] > 0
    assert s["total_energy_j"] > 0 and s["joules_per_request"] > 0
    m2 = engine.metrics.requests["r2"]
    assert m2.admit_s >= 20 * engine.tick_s
    # per-request energy shares sum to the total charged on live ticks
    per_req = sum(m.energy_j for m in engine.metrics.requests.values())
    assert per_req <= s["total_energy_j"] + 1e-9


def test_eos_stops_a_request_early():
    cfg, model, params = make_model()
    toks = prompts(cfg, 1, 8)
    base = ContinuousBatcher(model, params, n_slots=1, cache_len=32)
    full = base.run([Request(rid="r0", arch=cfg.name, prompt_len=8,
                             max_gen=8, tokens=toks[0])])["r0"]
    # pick a mid-stream token whose first occurrence is that position, so
    # the stop point is unambiguous (greedy decode may repeat tokens)
    k = next(i for i in range(1, len(full))
             if int(full[i]) not in [int(t) for t in full[:i]])
    eos = int(full[k])
    engine = ContinuousBatcher(model, params, n_slots=1, cache_len=32,
                               eos_id=eos)
    out = engine.run([Request(rid="r0", arch=cfg.name, prompt_len=8,
                              max_gen=8, tokens=toks[0])])["r0"]
    assert len(out) == k + 1 and out[-1] == eos
    assert np.array_equal(out, full[:k + 1])


def test_engine_rejects_wrong_arch_and_bad_tokens():
    cfg, model, params = make_model()
    engine = ContinuousBatcher(model, params, n_slots=1, cache_len=32)
    with pytest.raises(ValueError, match="arch"):
        engine.submit(Request(rid="x", arch="other-arch", prompt_len=8,
                              max_gen=2))
    with pytest.raises(ValueError, match="prompt_len"):
        engine.run([Request(rid="y", arch=cfg.name, prompt_len=8,
                            max_gen=2, tokens=np.zeros(4, np.int32))])
    with pytest.raises(ValueError):
        Request(rid="z", arch=cfg.name, prompt_len=0, max_gen=2)


def test_generate_reference_does_not_retrace_across_calls():
    """Satellite pin for the launch.serve fix: repeated generate() calls
    reuse one jitted prefill/step pair instead of re-tracing per call."""
    cfg, model, params = make_model()
    toks = prompts(cfg, 2, 8)
    batch = {"tokens": toks[0:1]}
    generate(model, params, batch, prompt_len=8, gen=3, cache_len=32)
    from repro.launch.serve import _jits_for
    prefill, step = _jits_for(model, 32)
    # the memoized pair is stable and its jax cache shows exactly the
    # warm-up traces — further calls add none
    n0 = prefill._cache_size() + step._cache_size()
    generate(model, params, {"tokens": toks[1:2]}, prompt_len=8, gen=3,
             cache_len=32)
    generate(model, params, batch, prompt_len=8, gen=5, cache_len=32)
    assert (prefill, step) == _jits_for(model, 32)
    assert prefill._cache_size() + step._cache_size() == n0


def test_engine_spans_name_each_step_of_a_tick():
    """With a recording tracer each tick is an ``engine_tick`` span over
    ``admit`` (prefill, first-token wait, insert), ``decode``,
    ``token_wait`` and ``emit``; each request's ``submit`` event and its
    ``admit`` span share its rid; and tracing changes no token."""
    from repro.obs import Tracer, use_tracer
    cfg, model, params = make_model()
    toks = prompts(cfg, 3, 8)

    def serve(tracer):
        engine = ContinuousBatcher(model, params, n_slots=2, cache_len=32)
        reqs = [Request(rid=f"r{i}", arch=cfg.name, prompt_len=8,
                        max_gen=4, tokens=toks[i],
                        arrival_s=i * engine.tick_s) for i in range(3)]
        with use_tracer(tracer):
            return engine.run(reqs)

    tr = Tracer()
    traced, plain = serve(tr), serve(None)
    assert traced.keys() == plain.keys()
    assert all(np.array_equal(traced[k], plain[k]) for k in plain)

    spans = [r for r in tr.records if r["type"] == "span"]
    kids = {}
    for r in sorted(spans, key=lambda r: r["id"]):
        kids.setdefault(r["parent"], []).append(r)
    ticks = [r for r in spans if r["name"] == "engine_tick"]
    assert [t["attrs"]["tick"] for t in sorted(ticks, key=lambda r: r["id"])
            ] == list(range(len(ticks)))
    assert sum(t["attrs"]["admitted"] for t in ticks) == 3
    assert all(t["parent"] is None for t in ticks)
    for t in ticks:
        names = [c["name"] for c in kids.get(t["id"], [])]
        steps = ["admit"] * t["attrs"]["admitted"]
        if t["attrs"]["live"]:
            steps += ["decode", "token_wait", "emit"]
        assert names == steps
    decodes = [r for r in spans if r["name"] == "decode"]
    assert decodes and all(r["attrs"]["path"] == "inplace" for r in decodes)
    admits = [r for r in spans if r["name"] == "admit"]
    for a in admits:
        assert [c["name"] for c in kids[a["id"]]] == [
            "prefill", "first_token_wait", "insert"]
    submits = {e["attrs"]["rid"]: e for e in tr.records
               if e["type"] == "event" and e["name"] == "submit"}
    assert sorted(a["attrs"]["rid"] for a in admits) == sorted(submits) \
        == ["r0", "r1", "r2"]
    assert all(a["t0"] >= submits[a["attrs"]["rid"]]["t"] for a in admits)


def test_a_recycled_slot_keeps_no_state_of_its_last_occupant():
    """The hybrid's insert replaces a slot's Mamba state and K/V whole: a
    request served in a slot another request (longer, and ragged against
    the SSD chunk) has just left gives the tokens it gives in a fresh
    engine."""
    cfg, model, params = make_model("granite-4.0-h-micro")
    long_, short = prompts(cfg, 1, 21)[0], prompts(cfg, 1, 13, seed=2)[0]
    engine = ContinuousBatcher(model, params, n_slots=1, cache_len=32)
    engine.run([Request(rid="a", arch=cfg.name, prompt_len=21, max_gen=6,
                        tokens=long_)])
    out = engine.run([Request(rid="b", arch=cfg.name, prompt_len=13,
                              max_gen=6, tokens=short)])["b"]
    fresh = ContinuousBatcher(model, params, n_slots=1, cache_len=32)
    want = fresh.run([Request(rid="b", arch=cfg.name, prompt_len=13,
                              max_gen=6, tokens=short)])["b"]
    assert np.array_equal(out, want)


@pytest.mark.parametrize("arch", [ARCH, "granite-4.0-h-micro"])
def test_spans_count_the_state_each_step_moves(arch):
    """Counters from shapes: ``prefill`` carries the SSD's chunks and the
    positions padded in the last one (Mamba layers only), ``insert`` a
    slot's recurrent-state and K/V bytes, ``decode`` the recurrent state
    the step rewrites over every slot."""
    from repro.obs import Tracer, use_tracer
    cfg, model, params = make_model(arch)
    engine = ContinuousBatcher(model, params, n_slots=2, cache_len=32)
    reqs = [Request(rid=f"r{n}", arch=cfg.name, prompt_len=n, max_gen=3,
                    tokens=prompts(cfg, 1, n, seed=n)[0]) for n in (13, 21)]
    tr = Tracer()
    with use_tracer(tr):
        engine.run(reqs)
    spans = {n: [r for r in tr.records if r["type"] == "span"
                 and r["name"] == n] for n in ("prefill", "insert", "decode")}
    kv_row = 2 * cfg.n_kv_heads * cfg.head_dim * 4        # K and V, float32
    if arch == ARCH:
        assert all(r["attrs"] == {} for r in spans["prefill"])
        state, kv = 0, cfg.n_layers * 32 * kv_row
    else:
        s, di = cfg.ssm, cfg.ssm.d_inner(cfg.d_model)
        assert [(r["attrs"]["ssd_chunks"], r["attrs"]["ssd_pad"])
                for r in spans["prefill"]] == [(1, 0), (2, 11)]
        conv = (s.conv_kernel - 1) * (di + 2 * s.n_groups * s.d_state)
        state = 9 * 4 * (s.n_heads(cfg.d_model) * s.d_state * s.headdim
                         + conv)
        kv = 32 * kv_row
    assert (engine.slot_state_bytes, engine.slot_kv_bytes) == (state, kv)
    assert all(r["attrs"]["state_bytes"] == state
               and r["attrs"]["kv_bytes"] == kv for r in spans["insert"])
    assert spans["decode"] and all(r["attrs"]["state_bytes"] == 2 * state
                                   for r in spans["decode"])
