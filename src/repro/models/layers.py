"""Core layers: norms, RoPE, attention (dense / blockwise / decode), FFNs.

Pure-functional JAX; params are plain dicts.  Every layer has a matching
``*_axes`` helper returning the logical sharding axes for its params so the
distribution layer can build PartitionSpecs without touching array data.

Attention uses grouped-GQA einsums throughout: KV heads are never
materialized repeated-per-query-head (q is reshaped [B,S,KV,rep,Dh] instead),
which keeps the HBM bytes in §Roofline honest.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free
                 # when a row is fully masked (ring-buffer warmup, padding).

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(key, d, kind, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_axes(kind):
    p = {"scale": (None,)}
    if kind == "layernorm":
        p["bias"] = (None,)
    return p


def apply_norm(p, x, kind, eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta):
    """x [B, S, H, Dh]; positions [S] or [B, S] (int)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                        # [half]
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    angles = pos[..., :, None] * freqs                            # [B?,S,half]
    cos = jnp.cos(angles)[..., :, None, :]                        # [B?,S,1,half]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1.astype(x.dtype), y2.astype(x.dtype)], axis=-1)


# ---------------------------------------------------------------------------
# attention params
# ---------------------------------------------------------------------------

def init_attn(key, cfg, dtype, kv_d_model=None):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dk = kv_d_model or d
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), d, dtype),
        "wk": dense_init(ks[1], (dk, kv, hd), dk, dtype),
        "wv": dense_init(ks[2], (dk, kv, hd), dk, dtype),
        "wo": dense_init(ks[3], (h, hd, d), h * hd, dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def attn_axes(cfg):
    p = {
        "wq": ("embed", "heads", None),
        "wk": ("embed", "kv_heads", None),
        "wv": ("embed", "kv_heads", None),
        "wo": ("heads", None, "embed"),
    }
    if cfg.use_bias:
        p.update({"bq": ("heads", None), "bk": ("kv_heads", None),
                  "bv": ("kv_heads", None), "bo": (None,)})
    return p


def q_project(p, cfg, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.use_bias:
        q = q + p["bq"]
    return q


def kv_project(p, cfg, x):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.use_bias:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


def out_project(p, cfg, attn_out):
    y = jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"])
    if cfg.use_bias:
        y = y + p["bo"]
    return y


def _group_q(q, n_kv):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def repeat_kv(k, n_rep):
    """[B,S,KV,D] -> [B,S,KV*n_rep,D] (repeat each kv head n_rep times)."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d)) \
              .reshape(b, s, kv * n_rep, d)


# ---------------------------------------------------------------------------
# dense attention (small-seq path)
#
# grouped=True uses the grouped-GQA einsum (never materializes repeated KV —
# best single-device bytes); grouped=False repeats KV to the full head count
# first, which keeps the *query-head* dim shardable on the model axis (the
# grouped layout splits H into (KV, rep), neither of which may divide the
# axis — e.g. 8 kv heads on a 16-way axis replicate the S^2 score tensor).
# ---------------------------------------------------------------------------

def dense_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0, softcap: float = 0.0,
                    grouped: bool = True):
    """q [B,Sq,H,Dh], k/v [B,Skv,KV,Dh]."""
    kvh = k.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    if not grouped:
        k = repeat_kv(k, q.shape[2] // kvh)
        v = repeat_kv(v, q.shape[2] // kvh)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    else:
        qg = _group_q(q, kvh)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
    scores = scores * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    pad = (None,) * (scores.ndim - 2)
    scores = jnp.where(mask[pad], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if not grouped:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return out
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# blockwise (flash-style) attention: full compute, O(S*block) memory.
# Pure JAX, differentiable; the Pallas kernel in repro.kernels.flash_attention
# is the FPGA-analogue replacement for this block.
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_offset: int = 0, block_q: int = 512,
                        block_kv: int = 512, grouped: bool = True):
    if not grouped:                      # shardable-head layout (see above)
        k = repeat_kv(k, q.shape[2] // k.shape[2])
        v = repeat_kv(v, q.shape[2] // v.shape[2])
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    pq = (-sq) % block_q
    pk = (-sk) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // block_q, k.shape[1] // block_kv
    qb = q.reshape(b, nq, block_q, kvh, rep, dh)
    kb = k.reshape(b, nk, block_kv, kvh, dh)
    vb = v.reshape(b, nk, block_kv, kvh, dh)
    scale = 1.0 / math.sqrt(dh)

    def q_block(qi, qblk):                       # qblk [b,block_q,kvh,rep,dh]
        qpos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, inputs):
            m, lsum, acc = carry
            ki, kblk, vblk = inputs
            kpos = ki * block_kv + jnp.arange(block_kv)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qblk, kblk)
            s = s.astype(jnp.float32) * scale
            mask = jnp.ones((block_q, block_kv), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window:
                mask &= qpos[:, None] - kpos[None, :] < window
            mask &= (kpos < sk)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = lsum * corr + p.sum(axis=-1)
            pv = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(qblk.dtype), vblk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, rep, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, rep, block_q), jnp.float32)
        a0 = jnp.zeros((b, kvh, rep, block_q, dh), qblk.dtype)
        (m, lsum, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        lsum = jnp.maximum(lsum, 1e-20)
        out = acc / lsum[..., None].astype(acc.dtype)   # [b,g,r,q,dh]
        return jnp.moveaxis(out, 3, 1)               # [b,q,g,r,dh]

    out = jax.lax.map(lambda args: q_block(*args),
                      (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * block_q, h, dh)
    return out[:, :sq]


def attention(q, k, v, *, causal: bool, window: int = 0, q_offset: int = 0,
              softcap: float = 0.0, plan=None):
    """Dispatch dense vs blockwise based on the plan threshold."""
    grouped = plan.gqa_grouped if plan is not None else True
    if plan is not None and q.shape[1] >= plan.blockwise_attn_threshold:
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset,
                                   block_q=plan.attn_block_q,
                                   block_kv=plan.attn_block_kv,
                                   grouped=grouped)
    return dense_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, softcap=softcap,
                           grouped=grouped)


# ---------------------------------------------------------------------------
# int8 KV-cache quantization (per-token, per-head scales)
#
# The scales factor out of both attention einsums (scores ∝ k_scale[k];
# fold v_scale into probs), so the bf16 cache is never re-materialized —
# HBM reads stay int8.
# ---------------------------------------------------------------------------

def quantize_kv(x):
    """x [..., D] -> (int8 [..., D], scale [..., 1] f32)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127,
                 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _valid_keys(n_keys, cache_len):
    """Mask of the stored keys below ``cache_len`` (a scalar, or one per
    row [B]), broadcastable against scores [B,G,R,Q,S]."""
    return jnp.arange(n_keys) < jnp.reshape(cache_len, (-1, 1, 1, 1, 1))


def decode_attention_quant(q, k_q, k_scale, v_q, v_scale, cache_len, *,
                           softcap: float = 0.0):
    """q [B,1,H,Dh]; k_q/v_q int8 [B,S,KV,Dh]; scales [B,S,KV,1];
    cache_len a scalar or one per row [B]."""
    kvh = k_q.shape[2]
    qg = _group_q(q, kvh)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                        k_q.astype(q.dtype)).astype(jnp.float32)
    # fold per-(token, head) k scales into the scores
    ks = k_scale[..., 0]                                # [B,S,KV]
    scores = scores * jnp.transpose(ks, (0, 2, 1))[:, :, None, None, :]
    scores = scores * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(_valid_keys(k_q.shape[1], cache_len), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    vs = v_scale[..., 0]                                # [B,S,KV]
    probs = probs * jnp.transpose(vs, (0, 2, 1))[:, :, None, None, :]
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(q.dtype),
                     v_q.astype(q.dtype))
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# decode attention over a KV cache (one new token per call)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     softcap: float = 0.0):
    """q [B,1,H,Dh]; caches [B,S,KV,Dh]; cache_len = #valid entries, a
    scalar or one per row [B].

    For ring-buffer (windowed) caches every stored entry is valid once the
    ring wraps; validity is simply ``kpos < cache_len`` with cache_len capped
    at the buffer size by the caller.
    """
    kvh = k_cache.shape[2]
    qg = _group_q(q, kvh)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache).astype(jnp.float32)
    scores = scores * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(_valid_keys(k_cache.shape[1], cache_len), scores,
                       NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_cache)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def init_ffn(key, d, hidden, act, use_bias, dtype):
    gated = act in ("swiglu", "geglu")
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], (d, hidden), d, dtype),
         "w_out": dense_init(ks[1], (hidden, d), hidden, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d, hidden), d, dtype)
    if use_bias:
        p["b_in"] = jnp.zeros((hidden,), dtype)
        p["b_out"] = jnp.zeros((d,), dtype)
    return p


def ffn_axes(act, use_bias):
    gated = act in ("swiglu", "geglu")
    p = {"w_in": ("embed", "ff"), "w_out": ("ff", "embed")}
    if gated:
        p["w_gate"] = ("embed", "ff")
    if use_bias:
        p["b_in"] = ("ff",)
        p["b_out"] = (None,)
    return p


def apply_ffn(p, x, act, use_bias=False):
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    if use_bias:
        h = h + p["b_in"]
    if act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = jax.nn.silu(g) * h
    elif act == "geglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = jax.nn.gelu(g) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(f"unknown act {act}")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_out"])
    if use_bias:
        y = y + p["b_out"]
    return y
