"""Plain reference of a Mamba-2 + GQA hybrid (Granite 4.0-H), for the
serve_hybrid cells' check, and the law of the weights the benchmark serves.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, with no
cache, no batching engine, no kernel and no chunked scan.  It imports
nothing of the program.  Layer ``l`` is of kind ``pattern[l % len]``:

* ``mamba``: x = rms(h); ``in_proj`` splits into the gate z, xBC and dt;
  xBC goes through a depthwise causal conv of ``conv_kernel`` taps with a
  bias, then silu, and splits into x [H, P], B and C [G, N];
  dt = softplus(dt + dt_bias), A = -exp(A_log).  Then the recurrence,
  token by token: h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T and
  y_t = C_t h_t + D x_t, per head (head i reads group i // (H / G)).
  y = rms(y * silu(z)) then ``out_proj``.
* ``attention``: NoPE causal GQA, scores q.k times ``attn_multiplier``.

Every layer is followed by a SwiGLU FFN; each branch (mixer or FFN) joins
the residual stream times ``residual_multiplier``; the embedding is
multiplied by ``embed_multiplier`` and the tied head's logits divided by
``logits_scaling``.  Norm scales are one, at eps ``norm_eps``.

The weights.  The tied embedding is normal at ``embed_std``
(``lm_reference.embedding``).  Layer ``l``'s draws use a key of their own
(the layer and then each name folded in, in the order listed).  A matrix
is normal at one over the root of its fan-in: attention ``wq``, ``wk``,
``wv``, ``wo`` as ``lm_reference``; Mamba ``in_proj`` [D, 2 D_in + 2 G N
+ H] (fan-in D) and ``out_proj`` [D_in, D] (fan-in D_in); FFN ``w_gate``,
``w_up`` [D, F] and ``w_down`` [F, D].  The conv's ``conv_w`` [K, C] and
``conv_b`` [C] are uniform in +-1/sqrt(K) (a depthwise conv's default);
``A_log`` is log of uniform [1, 16]; ``dt_bias`` is the inverse softplus
of a dt log-uniform in [0.001, 0.1] (the Mamba-2 initialization); ``D``
is one.  ``A_log``, ``dt_bias`` and ``D`` are float32; every other draw
is stored in ``param_dtype``.

``fp8=True`` is the control: every matrix product of the projections,
the attention and the head takes its two operands rounded to float8 e4m3,
the step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import lm_reference
from bench.harness.lm_reference import _mm, _normal, _rms

ATTN = ("wq", "wk", "wv", "wo")
F32 = ("A_log", "dt_bias", "D")


def kind_of(cfg: dict, layer: int) -> str:
    pat = cfg["pattern"]
    return pat[layer % len(pat)]


def mamba_dims(cfg: dict) -> Dict[str, int]:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    gn = cfg["n_groups"] * cfg["d_state"]
    nh = di // cfg["headdim"]
    return {"d_inner": di, "gn": gn, "heads": nh, "conv": di + 2 * gn,
            "in": 2 * di + 2 * gn + nh}


def _uniform(key, shape, bound, dtype):
    w = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return w.astype(dtype)


def _draws(cfg: dict, kind: str) -> Dict[str, tuple]:
    """One layer's draws in folding order: name -> (law, shape, scale)."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    ffn = {"w_gate": ("normal", (d, ff), d), "w_up": ("normal", (d, ff), d),
           "w_down": ("normal", (ff, d), ff)}
    if kind == "mamba":
        m = mamba_dims(cfg)
        k = cfg["conv_kernel"]
        mixer = {"in_proj": ("normal", (d, m["in"]), d),
                 "conv_w": ("uniform", (k, m["conv"]), k),
                 "conv_b": ("uniform", (m["conv"],), k),
                 "A_log": ("A_log", (m["heads"],), 0),
                 "dt_bias": ("dt_bias", (m["heads"],), 0),
                 "D": ("one", (m["heads"],), 0),
                 "out_proj": ("normal", (m["d_inner"], d), m["d_inner"])}
    else:
        h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
        mixer = {"wq": ("normal", (d, h, hd), d),
                 "wk": ("normal", (d, kv, hd), d),
                 "wv": ("normal", (d, kv, hd), d),
                 "wo": ("normal", (h, hd, d), h * hd)}
    return {**mixer, **ffn}


def _draw(key, law, shape, scale, dtype):
    if law == "normal":
        return _normal(key, shape, 1.0 / math.sqrt(scale), dtype)
    if law == "uniform":
        return _uniform(key, shape, 1.0 / math.sqrt(scale), dtype)
    if law == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if law == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))       # inverse of softplus
    return jnp.ones(shape, jnp.float32)


def layer_weights(cfg: dict, key, layer, kind: str) -> Dict[str, jax.Array]:
    """Layer ``layer``'s draws (norm scales are one and left out).
    ``layer`` may be traced; ``kind`` is its static block kind."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {name: _draw(jax.random.fold_in(lk, i), law, shape, scale,
                        jnp.float32 if name in F32 else cfg["param_dtype"])
            for i, (name, (law, shape, scale))
            in enumerate(_draws(cfg, kind).items())}


def _ffn(cfg, fp8, w, h):
    x = _rms(h, cfg["norm_eps"])
    gate = _mm("bsd,df->bsf", x, w["w_gate"], fp8)
    up = _mm("bsd,df->bsf", x, w["w_up"], fp8)
    y = _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"], fp8)
    return h + cfg["residual_multiplier"] * y


def _causal_conv(x, w, b):
    """x [B, S, C], w [K, C]: out_t = b + sum_i w[i] x_{t-K+1+i}."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))


def _mamba(cfg: dict, fp8: bool, key, layer, h):
    """One Mamba-2 layer and its FFN over h [B, S, D] (float32)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(cfg, key, layer, "mamba"))
    m = mamba_dims(cfg)
    di, gn, nh = m["d_inner"], m["gn"], m["heads"]
    g, n, p = cfg["n_groups"], cfg["d_state"], cfg["headdim"]
    b, s, _ = h.shape
    x = _rms(h, cfg["norm_eps"])
    zxbcdt = _mm("bsd,de->bse", x, w["in_proj"], fp8)
    z, xbc, dt = jnp.split(zxbcdt, [di, di + m["conv"]], axis=-1)
    xbc = jax.nn.silu(_causal_conv(xbc, w["conv_w"], w["conv_b"]))
    xs, bs, cs = jnp.split(xbc, [di, di + gn], axis=-1)
    head_group = jnp.arange(nh) // (nh // g)
    xs = xs.reshape(b, s, nh, p)
    bs = bs.reshape(b, s, g, n)
    cs = cs.reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])              # [B, S, H]
    a = -jnp.exp(w["A_log"])

    def step(state, inp):                                # state [B, H, N, P]
        x_t, b_t, c_t, dt_t = inp
        b_t, c_t = b_t[:, head_group], c_t[:, head_group]   # [B, H, N]
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None, None] * b_t[..., :, None]
               * x_t[..., None, :])
        y_t = jnp.einsum("bhn,bhnp->bhp", c_t, state,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t + w["D"][:, None] * x_t

    state0 = jnp.zeros((b, nh, n, p), jnp.float32)
    _, ys = jax.lax.scan(step, state0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (xs, bs, cs, dt)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, di)
    y = _rms(y * jax.nn.silu(z), cfg["norm_eps"])
    h = h + cfg["residual_multiplier"] * _mm("bse,ed->bsd", y,
                                             w["out_proj"], fp8)
    return _ffn(cfg, fp8, w, h)


def _attention(cfg: dict, fp8: bool, key, layer, h):
    """One NoPE GQA layer and its FFN over h [B, S, D] (float32); the
    scores one sequence at a time."""
    w = layer_weights(cfg, key, layer, "attention")
    kvh, rep = cfg["n_kv_heads"], cfg["n_heads"] // cfg["n_kv_heads"]
    hd = cfg["d_head"]

    def one(hs):                                          # [S, D]
        s = hs.shape[0]
        x = _rms(hs, cfg["norm_eps"])
        q = _mm("sd,dhk->shk", x, w["wq"], fp8).reshape(s, kvh, rep, hd)
        k = _mm("sd,dhk->shk", x, w["wk"], fp8)
        v = _mm("sd,dhk->shk", x, w["wv"], fp8)
        scores = _mm("qgrd,kgd->grqk", q, k, fp8) * cfg["attn_multiplier"]
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        att = _mm("grqk,kgd->qgrd", probs, v, fp8).reshape(s, kvh * rep, hd)
        return hs + cfg["residual_multiplier"] * _mm(
            "shk,hkd->sd", att, w["wo"], fp8)

    return _ffn(cfg, fp8, w, jax.lax.map(one, h))


def _scores(cfg: dict, fp8: bool, emb, h, pos, toks):
    """Reference best logit, logit of ``toks`` and the argmax at ``pos``."""
    x = _rms(h[pos], cfg["norm_eps"])
    logits = _mm("pd,vd->pv", x, emb, fp8)[:, :cfg["vocab_size"]] \
        / cfg["logits_scaling"]
    best = jnp.max(logits, axis=-1)
    chosen = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
    return best, chosen, jnp.argmax(logits, axis=-1)


class Reference(lm_reference.Reference):
    """Compiled pieces of the hybrid's reference for one configuration;
    ``read`` and ``gaps`` as ``lm_reference``.  Sequences are right-padded
    to the longest one rounded up to ``pad_to`` (at most ``length``): the
    recurrence's cost grows with the length it runs."""

    def __init__(self, cfg: dict, length: int, fp8: bool = False,
                 pad_to: int = 512):
        self.cfg, self.length, self.pad_to = cfg, int(length), int(pad_to)
        self._emb = jax.jit(lambda key: lm_reference.embedding(cfg, key))
        self._layers = {
            "mamba": jax.jit(lambda key, layer, h: _mamba(
                cfg, fp8, key, layer, h)),
            "attention": jax.jit(lambda key, layer, h: _attention(
                cfg, fp8, key, layer, h))}
        self._scores = jax.jit(lambda emb, h, pos, toks: _scores(
            cfg, fp8, emb, h, pos, toks))

    def hidden(self, key, seqs: Sequence[np.ndarray]):
        """Final hidden states [B, L, D] of the right-padded seqs."""
        cfg = self.cfg
        longest = max(len(s) for s in seqs)
        n = min(self.length, -(-longest // self.pad_to) * self.pad_to)
        toks = np.zeros((len(seqs), max(n, longest)), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        emb = self._emb(key)
        h = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
        h = h * cfg["embed_multiplier"]
        for layer in range(cfg["n_layers"]):
            h = self._layers[kind_of(cfg, layer)](key, jnp.int32(layer), h)
        return h, emb
