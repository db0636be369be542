"""Mamba-2 (SSD — state-space duality) block, chunked matmul formulation.

TPU adaptation: the SSD algorithm is expressed as chunk-local masked matmuls
(MXU work) plus a sequential inter-chunk state recurrence (length S/chunk),
exactly the "matrix-form" duality from arXiv:2405.21060 — no per-token scan,
so the MXU does nearly all the FLOPs and the recurrence touches only the
[H, N, P] chunk states.

A prompt of any length runs: the last chunk is padded with ``dt = 0`` and
``x = 0``, which leaves the state as it was (``exp(0 * A) = 1`` and no
input), and the padded outputs are dropped (``chunk_layout``).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import layers


def init_ssm(key, cfg, dtype):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    ks = jax.random.split(key, 6)
    in_dim = 2 * di + 2 * gn + nh  # z, x, B, C, dt
    p = {
        "w_in": layers.dense_init(ks[0], (d, in_dim), d, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_kernel, di + 2 * gn),
                                     jnp.float32) * 0.1).astype(dtype),
        **({"conv_b": jnp.zeros((di + 2 * gn,), dtype)} if s.conv_bias
           else {}),
        "A_log": jnp.log(jnp.linspace(1.0, float(nh), nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "w_out": layers.dense_init(ks[2], (di, d), di, dtype),
        "norm_scale": jnp.ones((di,), dtype),
    }
    return p


def ssm_axes(cfg):
    return {
        "w_in": ("embed", "lru"),
        "conv_w": (None, "lru"),
        **({"conv_b": ("lru",)} if cfg.ssm.conv_bias else {}),
        "A_log": (None,),
        "D": (None,),
        "dt_bias": (None,),
        "w_out": ("lru", "embed"),
        "norm_scale": (None,),
    }


def _split_in(cfg, h):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    gn = s.n_groups * s.d_state
    nh = s.n_heads(cfg.d_model)
    z, x, bc, dt = jnp.split(h, [di, 2 * di, 2 * di + 2 * gn], axis=-1)
    b_, c_ = jnp.split(bc, 2, axis=-1)
    return z, x, b_, c_, dt, di, gn, nh


def _causal_conv(x, w, state=None, bias=None):
    """x [B,S,C], w [K,C] depthwise causal conv (``bias`` [C] or None).
    state [B,K-1,C] for decode."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    if bias is not None:
        out = out + bias
    new_state = xp[:, -(k - 1):] if k > 1 else pad
    return jax.nn.silu(out), new_state


def _gated_norm(p, cfg, y, z):
    """Mamba-2's gated RMSNorm: norm(y * silu(z)), in float32."""
    y = y * jax.nn.silu(z)
    yf = y.astype(jnp.float32)
    return (yf * jax.lax.rsqrt(jnp.mean(jnp.square(yf), -1, keepdims=True)
                               + cfg.norm_eps)
            * p["norm_scale"].astype(jnp.float32)).astype(y.dtype)


def chunk_layout(cfg, seq_len: int, chunk: int = 0):
    """How ``apply_ssm`` cuts ``seq_len`` positions: (chunk length, number
    of chunks, positions padded in the last chunk).  A sequence shorter
    than the chunk is one chunk of its own length."""
    q = min(chunk or cfg.ssm.chunk, seq_len)
    pad = -seq_len % q
    return q, (seq_len + pad) // q, pad


def apply_ssm(p, cfg, hidden, rules, return_state=False, chunk=0,
              bf16=False):
    """Training/prefill path. hidden [B,S,D] -> [B,S,D], any S."""
    s = cfg.ssm
    b, S, _ = hidden.shape
    q, nc, npad = chunk_layout(cfg, S, chunk)

    h = jnp.einsum("bsd,de->bse", hidden, p["w_in"])
    z, x, B_, C_, dt, di, gn, nh = _split_in(cfg, h)
    conv_in = jnp.concatenate([x, B_, C_], -1)
    # the conv runs over the real positions: its state is their last K-1
    xbc, conv_state = _causal_conv(conv_in, p["conv_w"],
                                   bias=p.get("conv_b"))
    x, B_, C_ = jnp.split(xbc, [di, di + gn], axis=-1)

    P = s.headdim
    N = s.d_state
    G = s.n_groups
    x = x.reshape(b, S, nh, P)
    B_ = B_.reshape(b, S, G, N)
    C_ = C_.reshape(b, S, G, N)
    # broadcast groups to heads
    rep = nh // G
    Bh = jnp.repeat(B_, rep, axis=2)         # [b,S,nh,N]
    Ch = jnp.repeat(C_, rep, axis=2)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # [b,S,nh]
    if npad:
        # padded positions: dt = 0 and x = 0 leave the state unchanged
        def tail(a):
            return jnp.pad(a, [(0, 0), (0, npad)] + [(0, 0)] * (a.ndim - 2))
        x, Bh, Ch, dt = tail(x), tail(Bh), tail(Ch), tail(dt)
    A = -jnp.exp(p["A_log"])                                      # [nh]
    dA = dt * A                                                   # [b,Sp,nh] (log-decay)

    # chunk
    xc = x.reshape(b, nc, q, nh, P)
    Bc = Bh.reshape(b, nc, q, nh, N)
    Cc = Ch.reshape(b, nc, q, nh, N)
    dtc = dt.reshape(b, nc, q, nh)
    dAc = dA.reshape(b, nc, q, nh)
    cum = jnp.cumsum(dAc, axis=2)                                 # [b,nc,q,nh]

    # intra-chunk (diagonal block): L[i,j] = exp(cum_i - cum_j) for i >= j
    # `ct` controls the big [b,nc,q,q,nh] intermediates: f32 for exactness,
    # bf16 (MXU-native, f32 accumulate) under Plan.ssd_bf16.
    ct = jnp.bfloat16 if bf16 else jnp.float32
    li = cum[:, :, :, None, :]                                    # i
    lj = cum[:, :, None, :, :]                                    # j
    mask = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(mask, li - lj, -jnp.inf)).astype(ct)
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cc.astype(ct), Bc.astype(ct),
                        preferred_element_type=ct) * decay
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(ct)
    y_diag = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt,
                        preferred_element_type=jnp.float32)

    # chunk states: S_c = sum_j exp(cum_last - cum_j) B_j (x_j dt_j)^T
    seg = jnp.exp(cum[:, :, -1:, :] - cum).astype(ct)             # [b,nc,q,nh]
    states = jnp.einsum("bcjhn,bcjh,bcjhp->bchnp",
                        Bc.astype(ct), seg, xdt,
                        preferred_element_type=jnp.float32)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                       # [b,nc,nh]

    # inter-chunk recurrence over nc chunk states
    def step(prev, inp):
        st, dec = inp
        new = st + dec[:, :, None, None] * prev
        return new, prev

    init = jnp.zeros((b, nh, N, P), jnp.float32)
    final_state, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)                 # [b,nc,h,N,P]

    y_inter = jnp.einsum("bcihn,bchnp->bcihp",
                         Cc.astype(jnp.float32) * jnp.exp(cum)[..., None],
                         prev_states)
    y = (y_diag + y_inter).reshape(b, nc * q, nh, P)
    y = y + x.astype(jnp.float32) * p["D"][None, None, :, None]
    if npad:
        y = y[:, :S]
    y = y.reshape(b, S, di).astype(hidden.dtype)

    y = _gated_norm(p, cfg, y, z)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    if return_state:
        return out, {"conv": conv_state.astype(hidden.dtype),
                     "state": final_state}
    return out


def init_ssm_cache(cfg, batch, dtype=jnp.float32):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    gn = s.n_groups * s.d_state
    nh = s.n_heads(cfg.d_model)
    return {
        "conv": jnp.zeros((batch, s.conv_kernel - 1, di + 2 * gn), dtype),
        "state": jnp.zeros((batch, nh, s.d_state, s.headdim), jnp.float32),
    }


def decode_ssm(p, cfg, hidden, cache, rules):
    """Single-token decode. hidden [B,1,D]."""
    s = cfg.ssm
    b = hidden.shape[0]
    h = jnp.einsum("bsd,de->bse", hidden, p["w_in"])
    z, x, B_, C_, dt, di, gn, nh = _split_in(cfg, h)
    xbc, conv_state = _causal_conv(
        jnp.concatenate([x, B_, C_], -1), p["conv_w"], cache["conv"],
        p.get("conv_b"))
    x, B_, C_ = jnp.split(xbc, [di, di + gn], axis=-1)
    P, N, G = s.headdim, s.d_state, s.n_groups
    rep = nh // G
    x = x.reshape(b, nh, P)
    Bh = jnp.repeat(B_.reshape(b, G, N), rep, axis=1)
    Ch = jnp.repeat(C_.reshape(b, G, N), rep, axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32).reshape(b, nh) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    da = jnp.exp(dt * A)                                          # [b,nh]
    st = cache["state"] * da[:, :, None, None] + jnp.einsum(
        "bhn,bh,bhp->bhnp", Bh.astype(jnp.float32), dt, x.astype(jnp.float32))
    y = jnp.einsum("bhn,bhnp->bhp", Ch.astype(jnp.float32), st)
    y = y + x.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(b, 1, di).astype(hidden.dtype)
    y = _gated_norm(p, cfg, y, z)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, {"conv": conv_state, "state": st}
