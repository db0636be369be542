"""Prefill/decode consistency: the vectorized prefill cache must produce the
same logits as building the cache token-by-token from position 0."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models.lm import Model

FAMS = ["granite-3-2b",          # dense GQA
        "h2o-danube-1.8b",       # SWA
        "moonshot-v1-16b-a3b",   # MoE
        "recurrentgemma-2b",     # hybrid
        "granite-4.0-h-micro",   # hybrid: Mamba-2 + NoPE attention
        "mamba2-1.3b",           # ssm
        "llama-3.2-vision-90b",  # vlm
        "seamless-m4t-medium"]   # audio enc-dec


def _batch(cfg, b, s, key):
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab_size),
             "labels": jnp.zeros((b, s), jnp.int32)}
    if cfg.family == "vlm":
        batch["img_embed"] = jax.random.normal(
            k2, (b, cfg.n_img_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            k3, (b, cfg.n_frames, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", FAMS)
def test_prefill_matches_incremental_decode(arch):
    cfg = ARCHS[arch].reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 12
    cache_len = s + 4
    batch = _batch(cfg, b, s, jax.random.PRNGKey(7))

    # path A: vectorized prefill
    last_a, cache_a = jax.jit(
        lambda p, bt: model.prefill(p, bt, cache_len))(params, batch)

    # path B: token-by-token decode from scratch (cross K/V precomputed —
    # they are a function of the modality context, not of decoded tokens)
    cache_b = jax.jit(
        lambda p, bt: model.init_context_cache(p, bt, b, cache_len))(
        params, batch)
    step = jax.jit(model.decode_step)
    for pos in range(s):
        last_b, cache_b = step(params, cache_b,
                               batch["tokens"][:, pos:pos + 1],
                               jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(last_a), np.asarray(last_b),
                               rtol=2e-3, atol=2e-3)

    # one more decode step from each cache must also agree
    tok = jnp.argmax(last_a, -1)[:, None].astype(jnp.int32)
    la, _ = step(params, cache_a, tok, jnp.int32(s))
    lb, _ = step(params, cache_b, tok, jnp.int32(s))
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch,cache_len,quant", [
    ("granite-3-2b", 16, False),         # linear slot min(pos, w - 1)
    ("h2o-danube-1.8b", 8, False),       # ring slot pos % w
    ("granite-3-2b", 16, True),          # int8 cache with scales
    ("granite-4.0-h-micro", 16, False),  # Mamba-2 state + NoPE K/V stack
])
def test_vector_pos_matches_scalar_pos_row_by_row(arch, cache_len, quant):
    """decode_step with a position per row gives each row the logits and
    the cache row that the scalar path gives that row alone."""
    from repro.dist.plan import Plan
    cfg = ARCHS[arch].reduced()
    model = Model(cfg, Plan(kv_cache_quant=quant))
    params = model.init(jax.random.PRNGKey(0))
    b, s = 3, 12
    batch = _batch(cfg, b, s, jax.random.PRNGKey(5))
    _, cache = jax.jit(lambda p, bt: model.prefill(p, bt, cache_len))(
        params, batch)
    toks = batch["tokens"][:, :1]
    pos = jnp.asarray([s, s - 3, s + 2], jnp.int32)
    step = jax.jit(model.decode_step)
    logits, new = step(params, cache, toks, pos)
    for r in range(b):
        row = jax.tree.map(lambda c: c[:, r:r + 1], cache)
        want_l, want_c = step(params, row, toks[r:r + 1], pos[r])
        np.testing.assert_allclose(np.asarray(logits[r]),
                                   np.asarray(want_l[0]), rtol=1e-5,
                                   atol=1e-5)
        for got, want in zip(jax.tree.leaves(new), jax.tree.leaves(want_c)):
            np.testing.assert_allclose(
                np.asarray(got[:, r], np.float32),
                np.asarray(want[:, 0], np.float32), rtol=1e-5, atol=1e-5)


def test_vector_pos_needs_a_plain_kv_stack():
    cfg = ARCHS["mamba2-1.3b"].reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, cache = model.prefill(params, _batch(cfg, 2, 4, jax.random.PRNGKey(1)),
                             8)
    with pytest.raises(ValueError, match="plain KV stack"):
        model.decode_step(params, cache, jnp.zeros((2, 1), jnp.int32),
                          jnp.asarray([4, 4], jnp.int32))


@pytest.mark.parametrize("seq", [13, 21])
def test_ssd_padded_tail_matches_an_unpadded_run(seq):
    """A prompt that is no multiple of the chunk (13 and 21 against 8)
    pads its last chunk with dt = 0 and x = 0: its outputs and its final
    conv and SSM states are those of one unpadded chunk of the whole
    prompt (float32; the two differ only in the order of their sums)."""
    import dataclasses
    from repro.dist.sharding import NullRules
    from repro.models import ssm
    cfg = ARCHS["granite-4.0-h-micro"].reduced()
    cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, chunk=8))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seq), 3)
    p = ssm.init_ssm(k1, cfg, jnp.float32)
    p["conv_b"] = jax.random.normal(k2, p["conv_b"].shape) * 0.1
    x = jax.random.normal(k3, (2, seq, cfg.d_model))
    assert ssm.chunk_layout(cfg, seq) == (8, -(-seq // 8), -seq % 8)
    y, st = ssm.apply_ssm(p, cfg, x, NullRules(), return_state=True)
    y1, st1 = ssm.apply_ssm(p, cfg, x, NullRules(), return_state=True,
                            chunk=seq)
    assert ssm.chunk_layout(cfg, seq, seq)[2] == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), rtol=1e-5,
                               atol=1e-5)
    for name in ("conv", "state"):
        np.testing.assert_allclose(np.asarray(st[name]),
                                   np.asarray(st1[name]), rtol=1e-5,
                                   atol=1e-5)


def test_chunked_loss_matches_full_loss():
    """Property: chunked-vocab xent == full-logit xent."""
    import dataclasses
    from repro.dist.plan import Plan
    cfg = ARCHS["granite-3-2b"].reduced()
    for chunk in (0, 4, 8, 16):
        plan = Plan(vocab_chunk=chunk,
                    blockwise_attn_threshold=10**9)
        model = Model(cfg, plan)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, 2, 16, jax.random.PRNGKey(1))
        loss, _ = jax.jit(model.train_loss)(params, batch)
        if chunk == 0:
            base = float(loss)
        else:
            assert float(loss) == pytest.approx(base, rel=1e-5), chunk


def test_blockwise_plan_matches_dense_plan():
    from repro.dist.plan import Plan
    cfg = ARCHS["granite-3-2b"].reduced()
    batch = _batch(cfg, 2, 32, jax.random.PRNGKey(2))
    dense = Model(cfg, Plan(blockwise_attn_threshold=10**9))
    block = Model(cfg, Plan(blockwise_attn_threshold=1, attn_block_q=16,
                            attn_block_kv=16))
    params = dense.init(jax.random.PRNGKey(0))
    l1, _ = jax.jit(dense.train_loss)(params, batch)
    l2, _ = jax.jit(block.train_loss)(params, batch)
    assert float(l1) == pytest.approx(float(l2), rel=2e-4)


def test_swa_window_actually_masks():
    """SWA logits must differ from full attention when S > window."""
    import dataclasses
    cfg = ARCHS["h2o-danube-1.8b"].reduced()
    cfg_full = dataclasses.replace(cfg, attn_kind="full", window=0)
    cfg_swa = dataclasses.replace(cfg, attn_kind="swa", window=8)
    m1, m2 = Model(cfg_full), Model(cfg_swa)
    params = m1.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, 1, 32, jax.random.PRNGKey(3))
    l1, _ = jax.jit(m1.train_loss)(params, batch)
    l2, _ = jax.jit(m2.train_loss)(params, batch)
    assert abs(float(l1) - float(l2)) > 1e-6


def test_int8_kv_cache_close_to_exact():
    """Quantized-cache decode matches exact decode within int8 tolerance."""
    from repro.dist.plan import Plan
    cfg = ARCHS["granite-3-2b"].reduced()
    b, s = 2, 12
    batch = _batch(cfg, b, s, jax.random.PRNGKey(9))
    exact = Model(cfg, Plan())
    quant = Model(cfg, Plan(kv_cache_quant=True))
    params = exact.init(jax.random.PRNGKey(0))
    la, ca = jax.jit(lambda p, bt: exact.prefill(p, bt, s + 4))(params,
                                                               batch)
    lq, cq = jax.jit(lambda p, bt: quant.prefill(p, bt, s + 4))(params,
                                                                batch)
    assert cq["attn"]["k"].dtype == jnp.int8
    pa = jax.nn.softmax(la, -1)
    pq = jax.nn.softmax(lq, -1)
    assert float(jnp.abs(pa - pq).max()) < 0.05
    # one decode step from each cache stays close
    tok = jnp.argmax(la, -1)[:, None].astype(jnp.int32)
    step_a = jax.jit(exact.decode_step)
    step_q = jax.jit(quant.decode_step)
    la2, _ = step_a(params, ca, tok, jnp.int32(s))
    lq2, _ = step_q(params, cq, tok, jnp.int32(s))
    assert float(jnp.abs(jax.nn.softmax(la2, -1)
                         - jax.nn.softmax(lq2, -1)).max()) < 0.05


def test_ring_place_preserves_last_tokens():
    """Property: ring placement keeps exactly the last W tokens, each at
    slot t % W."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.models.lm import _ring_place

    @settings(max_examples=30, deadline=None)
    @given(st.integers(1, 12), st.integers(1, 24))
    def check(w, s):
        k = jnp.arange(s, dtype=jnp.float32)[None, :, None, None]
        k = jnp.broadcast_to(k, (1, s, 2, 3))
        out = _ring_place(k, w, s, jnp.float32)
        assert out.shape[1] == w
        for t in range(max(0, s - w), s):
            np.testing.assert_array_equal(
                np.asarray(out[0, t % w, 0, 0]), float(t))

    check()


def test_quantize_kv_error_bound():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.models.layers import quantize_kv

    @settings(max_examples=30, deadline=None)
    @given(st.integers(0, 1000))
    def check(seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (4, 8, 16)) \
            * (seed % 5 + 0.1)
        q, scale = quantize_kv(x)
        err = jnp.abs(q.astype(jnp.float32) * scale - x)
        assert float((err <= scale * 0.51).all()), float(err.max())

    check()
