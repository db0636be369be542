"""Plain reference of a dense GQA decoder, for the serve cells' check, and
the law of the weights the benchmark serves.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, with no
cache, no batching engine and no kernel: the whole sequence (prompt and
served tokens) goes through every layer at once, under a causal mask.  It
imports nothing of the program.

The benchmark owns the weights.  Each matrix is drawn from the seed on a
key of its own (the layer and the matrix's name folded in), normal and
scaled by one over the root of its fan-in; the tied embedding table is
normal at the configuration's ``embed_std``; norm scales are one; all are
stored in the configuration's ``param_dtype``.  The program is handed
these same draws, put into its parameter tree by ``lm_params``; the
reference draws them again, one layer at a time, so that it holds one
layer's weights at most.  XLA may round a rare element one bfloat16 step
apart between the two draws (it fuses them differently); that is far below
what the check compares.

``fp8=True`` is the control: every matrix product takes its two
operands rounded to float8 e4m3 (per-tensor scale), the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.fp8 import round_f8


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def _matrices(cfg: dict) -> Dict[str, tuple]:
    """One layer's matrices, in the order their keys are folded in:
    name -> (shape, fan-in)."""
    d, h, kv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff = cfg["d_head"], cfg["d_ff"]
    return {"wq": ((d, h, hd), d), "wk": ((d, kv, hd), d),
            "wv": ((d, kv, hd), d), "wo": ((h, hd, d), h * hd),
            "w_gate": ((d, ff), d), "w_in": ((d, ff), d),
            "w_out": ((ff, d), ff)}


def _normal(key, shape, std, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * std
    return w.astype(dtype)


def embedding(cfg: dict, key) -> jax.Array:
    """The tied embedding table [padded vocab, d_model]."""
    return _normal(jax.random.fold_in(key, 0),
                   (padded_vocab(cfg), cfg["d_model"]), cfg["embed_std"],
                   cfg["param_dtype"])


def layer_weights(cfg: dict, key, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s matrices (norm scales are one and left out).
    ``layer`` may be traced, so that the layers can be drawn under
    ``vmap``."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    return {name: _normal(jax.random.fold_in(lk, i), shape,
                          1.0 / math.sqrt(fan_in), cfg["param_dtype"])
            for i, (name, (shape, fan_in))
            in enumerate(_matrices(cfg).items())}


def _mm(spec, a, b, fp8: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = round_f8(a), round_f8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [S, H, D]: rotate half-split pairs by position."""
    s, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: dict, fp8: bool, key, layer, h):
    """One decoder layer over one sequence h [S, D] (float32)."""
    w = layer_weights(cfg, key, layer)
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    kvh, rep = cfg["n_kv_heads"], cfg["n_heads"] // cfg["n_kv_heads"]
    s, hd = h.shape[0], cfg["d_head"]
    x = _rms(h, eps)
    q = _rope(_mm("sd,dhk->shk", x, w["wq"], fp8), theta)
    k = _rope(_mm("sd,dhk->shk", x, w["wk"], fp8), theta)
    v = _mm("sd,dhk->shk", x, w["wv"], fp8)
    q = q.reshape(s, kvh, rep, hd)
    scores = _mm("qgrd,kgd->grqk", q, k, fp8) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _mm("grqk,kgd->qgrd", probs, v, fp8).reshape(s, kvh * rep, hd)
    h = h + _mm("shk,hkd->sd", att, w["wo"], fp8)
    x = _rms(h, eps)
    gate = _mm("sd,df->sf", x, w["w_gate"], fp8)
    up = _mm("sd,df->sf", x, w["w_in"], fp8)
    return h + _mm("sf,fd->sd", jax.nn.silu(gate) * up, w["w_out"], fp8)


def _scores(cfg: dict, fp8: bool, emb, h, pos, toks):
    """Reference best logit, logit of ``toks`` and the argmax at ``pos``."""
    x = _rms(h[pos], cfg["norm_eps"])
    logits = _mm("pd,vd->pv", x, emb, fp8)[:, :cfg["vocab_size"]]
    best = jnp.max(logits, axis=-1)
    chosen = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
    return best, chosen, jnp.argmax(logits, axis=-1)


class Reference:
    """Compiled pieces of the reference for one configuration and length."""

    def __init__(self, cfg: dict, length: int, fp8: bool = False):
        self.cfg, self.length = cfg, int(length)
        self._emb = jax.jit(lambda key: embedding(cfg, key))
        self._layer = jax.jit(lambda key, layer, h: jax.lax.map(
            lambda x: _layer(cfg, fp8, key, layer, x), h))
        self._scores = jax.jit(lambda emb, h, pos, toks: _scores(
            cfg, fp8, emb, h, pos, toks))

    def hidden(self, key, seqs: Sequence[np.ndarray]) -> jax.Array:
        """Final hidden states [B, length, D] of the right-padded seqs."""
        cfg = self.cfg
        toks = np.zeros((len(seqs), self.length), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        emb = self._emb(key)
        h = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
        h = h * math.sqrt(cfg["d_model"])
        for layer in range(cfg["n_layers"]):
            h = self._layer(key, jnp.int32(layer), h)
        return h, emb

    def read(self, key, prompts: Sequence[np.ndarray],
             served: Sequence[np.ndarray], query=None, chunk: int = 256
             ) -> List[Dict[str, np.ndarray]]:
        """For each request, at each position that produced a served token:
        the best logit, the logit of the token ``query`` names there (the
        served token by default), and this precision's own first choice.
        The sequences are the prompts followed by the served tokens."""
        query = served if query is None else query
        seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
                for p, s in zip(prompts, served)]
        h, emb = self.hidden(key, seqs)
        out = []
        for i, (p, q) in enumerate(zip(prompts, query)):
            n = len(q)
            # whole chunks only, so that one program serves every request
            m = -(-n // chunk) * chunk
            pos = np.full(m, len(p) - 1, np.int32)
            pos[:n] = np.arange(len(p) - 1, len(p) - 1 + n)
            qq = np.zeros(m, np.int32)
            qq[:n] = q
            parts = [[], [], []]
            for a in range(0, m, chunk):
                res = self._scores(emb, h[i], jnp.asarray(pos[a:a + chunk]),
                                   jnp.asarray(qq[a:a + chunk]))
                for lst, r in zip(parts, res):
                    lst.append(np.asarray(r))
            best, chosen, first = (np.concatenate(x)[:n] for x in parts)
            out.append({"best": best, "chosen": chosen, "first": first})
        return out


def gaps(rows: List[Dict[str, np.ndarray]]) -> np.ndarray:
    """How far each queried token's logit lies below the best."""
    return np.concatenate([r["best"] - r["chosen"] for r in rows]) \
        if rows else np.zeros(0)
