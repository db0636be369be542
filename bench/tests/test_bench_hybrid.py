"""The Granite 4.0-H hybrid's benchmark files: the plain recurrence
reference against the program, the weights' placement, the serve_hybrid
kind end to end on the CPU, the hybrid's counts and its metric readers."""
import functools
import gzip
import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import core, counts_hybrid as C, hybrid_params
from bench.harness import hybrid_reference as R, lm_reference, trace as tr

DATA = Path(__file__).parent / "data"
DIRS = (DATA, core.BENCH)
BENCH_FILE = DATA / "BENCHMARK.hybrid.json"
CELL = "serve.tiny-hybrid.ragchat"
SEED = 2 ** 40 + 4242
TINY = json.loads((DATA / "configs" / "tiny-hybrid.json").read_text())
GRANITE4 = json.loads(
    (core.BENCH / "configs" / "granite-4.0-h-micro.json").read_text())
M = GRANITE4["model"]
kind = core.load_kind("serve_hybrid")

# The program in float32 against the float32 reference differs by ~3e-8
# in these logits (the SSD's chunked sums against the token-by-token
# recurrence, in another order); the logits' spread is ~3e-3.  The same
# program in bfloat16 reads 3.7e-4 to 4.2e-4, and one dropped Granite
# multiplier 2.2e-4 (embedding times sqrt(d_model) for 12) to 7e-2
# (logits unscaled): a limit of 1e-5 holds the first with 300x of room
# and refuses each of the others by 20x or more.
LOGIT_TOL = 1e-5
KEY = jax.random.PRNGKey(3)


def tiny(dtype="float32", **model):
    """The tiny configuration file with its model in ``dtype``."""
    out = json.loads(json.dumps(TINY))
    out["model"].update(dtype=dtype, param_dtype=dtype, **model)
    return out


@functools.lru_cache(maxsize=None)
def weights(dtype):
    """The tiny model's weights at ``KEY``, placed in the program's tree
    (the multipliers do not enter them): drawn once per dtype."""
    from repro.models.lm import Model
    config = tiny(dtype)
    like = jax.eval_shape(Model(kind.model_config(config)).init, KEY)
    return hybrid_params.program_params(kind.reference_config(config), KEY,
                                        like)


def sequence(prompt_len):
    """A prompt of ``prompt_len`` tokens and the 7 tokens after it."""
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(prompt_len), (prompt_len + 7,), 0, 512), np.int32)


@functools.lru_cache(maxsize=None)
def reference_logits(prompt_len):
    """The float32 reference's logits at the positions that predict the
    tokens after the prompt (the last prompt position included)."""
    cfg = kind.reference_config(tiny())
    toks = sequence(prompt_len)
    h, emb = R.Reference(cfg, 64).hidden(KEY, [toks])
    positions = np.arange(prompt_len - 1, len(toks) - 1)
    x = lm_reference._rms(h[0, positions], cfg["norm_eps"])
    logits = jnp.einsum("pd,vd->pv", x, emb.astype(jnp.float32),
                        precision="highest") / cfg["logits_scaling"]
    return np.asarray(logits[:, :cfg["vocab_size"]])


def served_logits(config, toks, prompt_len):
    """The program's logits: the prompt's prefill, then one decode step
    through the cache for each later token."""
    from repro.models.lm import Model
    model = Model(kind.model_config(config))
    params = weights(config["model"]["dtype"])
    last, cache = jax.jit(lambda p, b: model.prefill(p, b, 64))(
        params, {"tokens": jnp.asarray(toks[None, :prompt_len])})
    step = jax.jit(model.decode_step)
    out = [last[0]]
    for t in range(prompt_len, len(toks) - 1):
        logits, cache = step(params, cache, jnp.asarray(toks[None, t:t + 1]),
                             jnp.int32(t))
        out.append(logits[0])
    vocab = config["model"]["vocab_size"]
    return np.stack([np.asarray(x, np.float32)[:vocab] for x in out])


@pytest.mark.parametrize("prompt_len", [13, 21])
def test_prefill_and_decode_match_the_recurrence(prompt_len):
    """Ragged prompts (13 and 21 against a chunk of 8: the SSD's last
    chunk padded), then 6 decode steps, against the token-by-token
    reference's full forward pass."""
    got = served_logits(tiny(), sequence(prompt_len), prompt_len)
    assert np.abs(got - reference_logits(prompt_len)).max() < LOGIT_TOL


@pytest.mark.parametrize("change", [
    {"dtype": "bfloat16"},
    {"residual_multiplier": 1.0},
    {"embed_multiplier": 0.0},
    {"attn_multiplier": 0.0},
    {"logits_scaling": 1.0},
], ids=["bf16", "residual", "embedding", "attention", "logits"])
def test_a_lower_precision_or_a_dropped_multiplier_fails(change):
    dtype = change.get("dtype", "float32")
    config = tiny(dtype, **{k: v for k, v in change.items()
                            if k != "dtype"})
    got = served_logits(config, sequence(13), 13)
    assert np.abs(got - reference_logits(13)).max() > 10 * LOGIT_TOL


def test_the_reference_draws_the_programs_weights():
    """Each leaf the program is handed is the reference's draw for that
    layer, at layer ``g * 10 + i`` for block ``i`` of group ``g`` (XLA may
    round an element one float32 step apart between the vmapped draw and
    this one, as lm_reference notes)."""
    key, params = KEY, weights("float32")
    cfg = kind.reference_config(tiny())
    names = {"in_proj": ("ssm", "w_in"), "out_proj": ("ssm", "w_out"),
             "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_in"),
             "w_down": ("ffn", "w_out")}
    for layer in (3, 5, 9):
        kind_ = R.kind_of(cfg, layer)
        block = params["blocks"][f"b{layer % 10}"]
        for name, want in R.layer_weights(cfg, key, layer, kind_).items():
            group, leaf = names.get(name, (
                "attn" if name in R.ATTN else "ssm", name))
            np.testing.assert_allclose(np.asarray(block[group][leaf][0]),
                                       np.asarray(want), rtol=2.0 ** -22,
                                       atol=1e-30)
    np.testing.assert_allclose(np.asarray(params["embed"]),
                               np.asarray(lm_reference.embedding(cfg, key)),
                               rtol=2.0 ** -22, atol=1e-30)


def test_a_changed_program_layout_is_refused():
    key = jax.random.PRNGKey(0)
    cfg = kind.reference_config(tiny())
    from repro.models.lm import Model
    like = jax.eval_shape(Model(kind.model_config(tiny())).init, key)
    like["blocks"]["b0"]["ssm"].pop("conv_b")
    with pytest.raises(ValueError, match="hybrid_params"):
        hybrid_params.program_params(cfg, key, like)


def test_the_bench_config_is_the_programs():
    """The benchmark serves the program's registered configuration, all
    40 layers at the published widths (nothing reduced)."""
    from repro.configs import get_config
    assert kind.model_config(GRANITE4) == get_config("granite-4.0-h-micro")
    assert GRANITE4["reduced"] == []
    assert GRANITE4["num_hidden_layers"] == M["n_layers"] == 40
    assert GRANITE4["layer_types"] == [
        M["hybrid"]["pattern"][i % 10] for i in range(40)]
    tr_ = json.loads((core.BENCH / "traffic" / "ragchat.json").read_text())
    assert max(tr_["prompt"]["grid"]) + tr_["output"]["max"] \
        <= tr_["cache_len"]


def run_cell(seed, trace=True, tmp=None):
    return core.run_cell(CELL, seed, 2.0, trace, bench_file=BENCH_FILE,
                         dirs=DIRS, require_chip=False, cache_dir=None,
                         trace_dir=tmp or core.TRACE_DIR)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return run_cell(SEED, tmp=tmp_path_factory.mktemp("trace"))


def test_sound_traced_run_is_correct_and_reads_its_spans(sound):
    out, run = sound
    assert out["correct"] is True and out["failed"] == 0
    # on the CPU there is no device trace: the host and span metrics
    assert set(out["metrics"]) == {"admit_wait_ms", "compiles_in_window",
                                   "ssd_pad_share"}
    # every prefill of the window carries its SSD padding: 13 and 21
    # tokens pad 3 positions each with a chunk of 8, 8 none
    prefills = [r for r in run.obs_records
                if r["type"] == "span" and r["name"] == "prefill"]
    admits = {r["id"]: r["attrs"]["prompt_len"] for r in run.obs_records
              if r["type"] == "span" and r["name"] == "admit"}
    lens = [admits[r["parent"]] for r in prefills]
    assert lens and all(r["attrs"]["ssd_pad"] == -n % 8
                        for r, n in zip(prefills, lens))
    want = 100.0 * sum(-n % 8 for n in lens) / sum(lens)
    assert out["metrics"]["ssd_pad_share"]["value"] == pytest.approx(want)


def test_the_control_is_refused(sound):
    out, run = sound
    ctl = core.verdict(kind.control(run))
    chk = out["checks"]["served_logit_gap"]
    assert chk["value"] <= chk["limit"] \
        < ctl["checks"]["served_logit_gap"]["value"]
    assert ctl["correct"] is False


def test_a_stale_decode_state_is_not_correct(monkeypatch):
    from repro.models.lm import Model
    orig = Model.decode_step

    def step(self, params, cache, tokens, pos):
        logits, _ = orig(self, params, cache, tokens, pos)
        return logits, cache
    monkeypatch.setattr(Model, "decode_step", step)
    out, _ = run_cell(SEED + 1, trace=False)
    assert out["correct"] is False


def test_granite4_counts_from_shapes():
    assert C.layer_counts(M) == {"mamba": 36, "attention": 4}
    # in_proj 2048 x (2*4096 + 2*128 + 64), out_proj 4096 x 2048, FFN
    mamba = 2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert C.matmul_params(M) == {"mamba": mamba, "attention": attn}
    # the program's own count: ModelConfig.n_params(), in bf16, plus the
    # final norm, with A_log, D, dt_bias at 4 bytes
    from repro.configs import get_config
    n = get_config("granite-4.0-h-micro").n_params()
    assert C.param_bytes(M) == 2 * (n + 2048) + 36 * 3 * 64 * 2
    # SSM state 64 x 128 x 64 float32, conv state 3 x 4352 bf16, 36 layers
    assert C.state_bytes_per_slot(M) == 36 * (64 * 128 * 64 * 4
                                              + 3 * 4352 * 2)
    assert C.kv_bytes_per_token(M) == 4 * 2 * 8 * 64 * 2
    assert C.decode_bytes(M, [100, 50]) == C.param_bytes(M) + 2 * 2 * \
        C.state_bytes_per_slot(M) + 8192 * 150


def test_ssd_and_prefill_flops_by_hand():
    # 300 tokens: a chunk of 256 and one of 44
    def chunk(q):
        pairs = q * (q + 1) // 2
        return 2 * pairs * 128 + 2 * pairs * 64 * 64 \
            + 4 * q * 128 * 64 * 64 + 2 * 64 * 128 * 64
    assert C.ssd_flops(M, 300) == chunk(256) + chunk(44)
    mp = C.matmul_params(M)
    n = 300
    want = 2 * n * (36 * mp["mamba"] + 4 * mp["attention"]) \
        + 36 * (n * 2 * 4 * 4352 + chunk(256) + chunk(44)) \
        + 4 * 4 * (n * (n + 1) // 2) * 32 * 64 + 2 * 2048 * 100352
    assert C.prefill_flops(M, n) == want


def test_device_readers_on_a_recorded_v5e_trace():
    """The hybrid readers on the chat cell's recorded slice (a prefill and
    a decode step of jit_pf / jit_pool_step), with the hybrid's counts."""
    with gzip.open(DATA / "chat-v5e.trace.json.gz", "rt") as f:
        t = tr.Trace.from_json(json.load(f))
    serve_kind = core.load_kind("serve")
    a = serve_kind.Req("a", 0.0, 640, 3, None)
    a.token_s, a.token_tick, a.admit_s = [0.01, 0.02, 0.03], [0, 1, 1], 0.0
    run = types.SimpleNamespace(
        trace=t, traced=(0.0, 1.0), tick_s=[0.0, 0.01], requests=[a],
        config=GRANITE4, peaks={"hbm_bytes_per_s": 819e9,
                                "bf16_flops": 197e12})
    secs, _ = tr.module_seconds(t, "jit_pool_step")
    dec = core.load_module(core.BENCH / "metrics"
                           / "hybrid_decode_hbm_share.py", "m_hd")
    assert dec.read(run) == pytest.approx(
        100 * C.decode_bytes(M, [641, 642]) / secs / 819e9)
    pf_secs, _ = tr.module_seconds(t, "jit_pf")
    mfu = core.load_module(core.BENCH / "metrics" / "hybrid_prefill_mfu.py",
                           "m_hp")
    assert mfu.read(run) == pytest.approx(
        100 * C.prefill_flops(M, 640) / pf_secs / 197e12)
    assert dec.read(types.SimpleNamespace(trace=None, peaks=None)) is None


def test_ssd_pad_share_reads_the_prefill_spans():
    pad = core.load_module(core.BENCH / "metrics" / "ssd_pad_share.py",
                           "m_sp")
    recs = [{"type": "span", "id": 1, "parent": None, "name": "admit",
             "attrs": {"prompt_len": 384}},
            {"type": "span", "id": 2, "parent": 1, "name": "prefill",
             "attrs": {"ssd_chunks": 2, "ssd_pad": 128}},
            {"type": "span", "id": 3, "parent": None, "name": "admit",
             "attrs": {"prompt_len": 200}},
            {"type": "span", "id": 4, "parent": 3, "name": "prefill",
             "attrs": {"ssd_chunks": 1, "ssd_pad": 0}}]
    run = types.SimpleNamespace(obs_records=recs)
    assert pad.read(run) == pytest.approx(100 * 128 / 584)
    assert pad.read(types.SimpleNamespace()) is None
