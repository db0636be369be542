"""Unified language model covering all assigned architecture families.

families: dense (granite / danube / command-r+ / nemotron), moe (moonshot /
arctic), hybrid (recurrentgemma: RG-LRU + local attention; granite-4.0-h:
Mamba-2 + NoPE attention), ssm (mamba2), vlm (llama-3.2-vision
backbone, stub image frontend), audio (seamless enc-dec backbone, stub frame
frontend).

Layer stacks are ``jax.lax.scan`` over stacked params (keeps the HLO small —
essential for the 512-device dry-run), with per-block remat controlled by the
active :class:`~repro.dist.plan.Plan`.

Entry points, bound by :class:`Model`:
  * ``train_loss(params, batch)``               -> (loss, metrics)
  * ``prefill(params, batch, cache_len)``       -> (last_logits, cache)
  * ``decode_step(params, cache, tokens, pos)`` -> (logits, cache)

The prefill path collects every layer's K/V (and recurrent/SSM final states)
as ``scan`` outputs — one pass, no per-token loop — so it lowers cleanly at
32k tokens for the dry-run.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.plan import Plan
from repro.dist.sharding import NullRules
from repro.models import layers, moe as moe_mod, rglru, ssm as ssm_mod

Params = Dict[str, Any]


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _norm(cfg, p, x):
    return layers.apply_norm(p, x, cfg.norm, cfg.norm_eps)


def _res(cfg, y):
    """A residual branch's output as it joins the stream."""
    m = cfg.residual_multiplier     # in float32: 0.22 has no exact bf16
    return y if m == 1.0 else (y.astype(jnp.float32) * m).astype(y.dtype)


def _rope(cfg, x, positions):
    if cfg.pos_embedding == "nope":
        return x
    return layers.apply_rope(x, positions, cfg.rope_theta)


def _scale_q(cfg, q):
    """Queries scaled so that the attention kernels' 1/sqrt(d_head) gives
    the model's score multiplier."""
    m = cfg.attn_multiplier
    if not m:
        return q
    return q * jnp.asarray(m * cfg.head_dim ** 0.5, q.dtype)


# ===========================================================================
# block init / axes
# ===========================================================================

def _init_dense_block(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    ffn = (moe_mod.init_moe(ks[2], cfg, dtype) if cfg.moe is not None
           else layers.init_ffn(ks[2], cfg.d_model, cfg.d_ff, cfg.ffn_act,
                                cfg.use_bias, dtype))
    return {
        "attn_norm": layers.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "attn": layers.init_attn(ks[1], cfg, dtype),
        "ffn_norm": layers.init_norm(ks[3], cfg.d_model, cfg.norm, dtype),
        "ffn": ffn,
    }


def _dense_block_axes(cfg):
    ffn = (moe_mod.moe_axes(cfg) if cfg.moe is not None
           else layers.ffn_axes(cfg.ffn_act, cfg.use_bias))
    return {
        "attn_norm": layers.norm_axes(cfg.norm),
        "attn": layers.attn_axes(cfg),
        "ffn_norm": layers.norm_axes(cfg.norm),
        "ffn": ffn,
    }


def _init_cross_block(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "attn_norm": layers.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "attn": layers.init_attn(ks[1], cfg, dtype),
        "ffn_norm": layers.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "ffn": layers.init_ffn(ks[3], cfg.d_model, cfg.d_ff, cfg.ffn_act,
                               cfg.use_bias, dtype),
    }


def _cross_block_axes(cfg):
    return {
        "attn_norm": layers.norm_axes(cfg.norm),
        "attn": layers.attn_axes(cfg),
        "ffn_norm": layers.norm_axes(cfg.norm),
        "ffn": layers.ffn_axes(cfg.ffn_act, cfg.use_bias),
    }


def _init_recurrent_block(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "norm": layers.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "lru": rglru.init_rglru(ks[1], cfg, dtype),
        "ffn_norm": layers.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "ffn": layers.init_ffn(ks[3], cfg.d_model, cfg.d_ff, cfg.ffn_act,
                               cfg.use_bias, dtype),
    }


def _recurrent_block_axes(cfg):
    return {
        "norm": layers.norm_axes(cfg.norm),
        "lru": rglru.rglru_axes(cfg),
        "ffn_norm": layers.norm_axes(cfg.norm),
        "ffn": layers.ffn_axes(cfg.ffn_act, cfg.use_bias),
    }


def _init_ssm_block(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    return {
        "norm": layers.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ssm": ssm_mod.init_ssm(ks[1], cfg, dtype),
    }


def _ssm_block_axes(cfg):
    return {"norm": layers.norm_axes(cfg.norm),
            "ssm": ssm_mod.ssm_axes(cfg)}


def _init_mamba_block(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    return {
        "norm": layers.init_norm(ks[0], cfg.d_model, cfg.norm, dtype),
        "ssm": ssm_mod.init_ssm(ks[1], cfg, dtype),
        "ffn_norm": layers.init_norm(ks[2], cfg.d_model, cfg.norm, dtype),
        "ffn": layers.init_ffn(ks[3], cfg.d_model, cfg.d_ff, cfg.ffn_act,
                               cfg.use_bias, dtype),
    }


def _mamba_block_axes(cfg):
    return {
        "norm": layers.norm_axes(cfg.norm),
        "ssm": ssm_mod.ssm_axes(cfg),
        "ffn_norm": layers.norm_axes(cfg.norm),
        "ffn": layers.ffn_axes(cfg.ffn_act, cfg.use_bias),
    }


# a hybrid's block kinds; every other kind is an attention block
_HYBRID_INIT = {"recurrent": _init_recurrent_block,
                "mamba": _init_mamba_block}
_HYBRID_AXES = {"recurrent": _recurrent_block_axes,
                "mamba": _mamba_block_axes}


def _init_hybrid_block(kind, key, cfg, dtype):
    return _HYBRID_INIT.get(kind, _init_dense_block)(key, cfg, dtype)


def _hybrid_block_axes(kind, cfg):
    return _HYBRID_AXES.get(kind, _dense_block_axes)(cfg)


def _hybrid_window(cfg, kind) -> int:
    return cfg.window if kind == "local_attn" else 0


# ===========================================================================
# whole-model init / axes
# ===========================================================================

def _stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def vlm_groups(cfg) -> Tuple[int, int]:
    per = cfg.cross_attn_every
    return cfg.n_layers // (per + 1), per


def hybrid_groups(cfg) -> Tuple[int, int]:
    pat = cfg.hybrid.pattern
    groups = cfg.n_layers // len(pat)
    return groups, cfg.n_layers - groups * len(pat)


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = _pdt(cfg)
    ks = jax.random.split(key, 8)
    vp = cfg.padded_vocab
    p: Params = {
        "embed": layers.embed_init(ks[0], (vp, cfg.d_model), dtype),
        "final_norm": layers.init_norm(ks[1], cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = layers.dense_init(ks[2], (cfg.d_model, vp),
                                         cfg.d_model, dtype)
    fam = cfg.family
    if fam in ("dense", "moe"):
        p["blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, dtype), ks[3], cfg.n_layers)
    elif fam == "vlm":
        groups, per = vlm_groups(cfg)
        p["self_blocks"] = _stack_init(
            lambda k: _stack_init(
                lambda k2: _init_dense_block(k2, cfg, dtype), k, per),
            ks[3], groups)
        p["cross_blocks"] = _stack_init(
            lambda k: _init_cross_block(k, cfg, dtype), ks[4], groups)
    elif fam == "audio":
        p["enc_blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, dtype), ks[3],
            cfg.encoder_layers)
        p["enc_norm"] = layers.init_norm(ks[5], cfg.d_model, cfg.norm, dtype)
        p["dec_blocks"] = _stack_init(
            lambda k: {"self": _init_dense_block(k, cfg, dtype),
                       "cross": _init_cross_block(
                           jax.random.fold_in(k, 1), cfg, dtype)},
            ks[4], cfg.n_layers)
    elif fam == "hybrid":
        pat = cfg.hybrid.pattern
        groups, tail = hybrid_groups(cfg)

        def init_group(k):
            return {f"b{i}": _init_hybrid_block(
                kind, jax.random.fold_in(k, i), cfg, dtype)
                for i, kind in enumerate(pat)}

        p["blocks"] = _stack_init(init_group, ks[3], groups)
        if tail:
            p["tail"] = [
                _init_hybrid_block(pat[i % len(pat)],
                                   jax.random.fold_in(ks[6], i), cfg, dtype)
                for i in range(tail)]
    elif fam == "ssm":
        p["blocks"] = _stack_init(
            lambda k: _init_ssm_block(k, cfg, dtype), ks[3], cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return p


def param_axes(cfg: ModelConfig) -> Params:
    """Pytree of logical-axis tuples matching init_params' structure."""

    def stack(tree):
        return jax.tree.map(lambda ax: ("layers",) + ax, tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    p = {"embed": ("vocab", "embed"),
         "final_norm": layers.norm_axes(cfg.norm)}
    if not cfg.tie_embeddings:
        p["unembed"] = ("embed", "vocab")
    fam = cfg.family
    if fam in ("dense", "moe"):
        p["blocks"] = stack(_dense_block_axes(cfg))
    elif fam == "vlm":
        p["self_blocks"] = stack(stack(_dense_block_axes(cfg)))
        p["cross_blocks"] = stack(_cross_block_axes(cfg))
    elif fam == "audio":
        p["enc_blocks"] = stack(_dense_block_axes(cfg))
        p["enc_norm"] = layers.norm_axes(cfg.norm)
        p["dec_blocks"] = stack({"self": _dense_block_axes(cfg),
                                 "cross": _cross_block_axes(cfg)})
    elif fam == "hybrid":
        pat = cfg.hybrid.pattern
        group = {f"b{i}": _hybrid_block_axes(k, cfg)
                 for i, k in enumerate(pat)}
        p["blocks"] = stack(group)
        _, tail = hybrid_groups(cfg)
        if tail:
            p["tail"] = [_hybrid_block_axes(pat[i % len(pat)], cfg)
                         for i in range(tail)]
    elif fam == "ssm":
        p["blocks"] = stack(_ssm_block_axes(cfg))
    return p


# ===========================================================================
# forward blocks
# ===========================================================================

def _maybe_remat(fn, plan):
    if plan.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable
              if plan.remat == "full" else
              jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


def _window_of(cfg) -> int:
    return cfg.window if cfg.attn_kind == "swa" else 0


def _embed(cfg, rules, params, tokens):
    h = jnp.take(params["embed"], tokens, axis=0).astype(_dt(cfg))
    if cfg.embed_multiplier:
        scale = jnp.asarray(cfg.embed_multiplier, _dt(cfg))
    else:
        scale = jnp.sqrt(jnp.float32(cfg.d_model)).astype(_dt(cfg))
    return rules.constrain(h * scale, ("batch", "seq", None))


def _apply_dense_block(p, cfg, plan, rules, h, positions, window,
                       collect=False):
    x = _norm(cfg, p["attn_norm"], h)
    q = _scale_q(cfg, layers.q_project(p["attn"], cfg, x))
    k, v = layers.kv_project(p["attn"], cfg, x)
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)
    q = rules.constrain(q, ("batch", None, "heads", None))
    k = rules.constrain(k, ("batch", None, "kv_heads", None))
    attn_out = layers.attention(q, k, v, causal=True, window=window,
                                softcap=cfg.logit_softcap, plan=plan)
    h = h + rules.constrain(
        _res(cfg, layers.out_project(p["attn"], cfg, attn_out)),
        ("batch", None, None))
    x = _norm(cfg, p["ffn_norm"], h)
    if cfg.moe is not None:
        if plan.moe_impl == "shardmap_ep":
            y, aux = moe_mod.apply_moe_ep(p["ffn"], cfg, x, rules,
                                          plan.moe_capacity_factor)
        else:
            y, aux = moe_mod.apply_moe(p["ffn"], cfg, x, rules,
                                       plan.moe_capacity_factor,
                                       groups=plan.moe_groups)
    else:
        y, aux = layers.apply_ffn(p["ffn"], x, cfg.ffn_act, cfg.use_bias), 0.0
    h = h + rules.constrain(_res(cfg, y), ("batch", None, None))
    kv = (k, v) if collect else None
    return h, aux, kv


def _decode_dense_block(p, cfg, plan, rules, h, cache, pos, window,
                        layer=None):
    """h [B,1,D]; pos the absolute position: a scalar, or int32[B], one per
    row.

    Without ``layer`` the cache is this layer's {k,v: [B,W,KV,Dh]} and comes
    back with the new K/V written at the slot of ``pos``.  With ``layer``
    it is the whole stack {k,v: [L,B,W,KV,Dh]}: each row's new K/V is
    written at (layer, row, the row's slot) and the stack comes back, so
    a layer loop that carries it updates it in place.
    """
    x = _norm(cfg, p["attn_norm"], h)
    q = _scale_q(cfg, layers.q_project(p["attn"], cfg, x))
    k, v = layers.kv_project(p["attn"], cfg, x)
    posv = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (h.shape[0], 1))
    q = _rope(cfg, q, posv)
    k = _rope(cfg, k, posv)
    w = cache["k"].shape[-3]
    slot = (pos % w) if window else jnp.minimum(pos, w - 1)
    new = {"k": k, "v": v}
    if "k_scale" in cache:
        new["k"], new["k_scale"] = layers.quantize_kv(k)
        new["v"], new["v_scale"] = layers.quantize_kv(v)
    if layer is None:
        cache = {n: jax.lax.dynamic_update_slice(
            c, new[n].astype(c.dtype), (0, slot, 0, 0))
            for n, c in cache.items()}
        lc = cache
    else:
        # one update per row: a scatter would pin the stack to a layout
        # with the row's (KV, Dh) minor and relayout it at the jit boundary
        def put(c, x):
            for b in range(h.shape[0]):
                c = jax.lax.dynamic_update_slice(
                    c, x[None, b:b + 1].astype(c.dtype),
                    (layer, b, slot[b], 0, 0))
            return c
        cache = {n: put(c, new[n]) for n, c in cache.items()}
        lc = {n: c[layer] for n, c in cache.items()}
    k_cache = rules.constrain(lc["k"], ("batch", "kv_seq", "kv_heads", None))
    v_cache = rules.constrain(lc["v"], ("batch", "kv_seq", "kv_heads", None))
    cache_len = jnp.minimum(pos + 1, w)
    if "k_scale" in cache:
        attn_out = layers.decode_attention_quant(
            q, k_cache, lc["k_scale"], v_cache, lc["v_scale"], cache_len,
            softcap=cfg.logit_softcap)
    else:
        attn_out = layers.decode_attention(q, k_cache, v_cache, cache_len,
                                           softcap=cfg.logit_softcap)
    h = h + _res(cfg, layers.out_project(p["attn"], cfg, attn_out))
    x = _norm(cfg, p["ffn_norm"], h)
    if cfg.moe is not None:
        if plan.moe_impl == "shardmap_ep":
            y, _ = moe_mod.apply_moe_ep(p["ffn"], cfg, x, rules,
                                        plan.moe_capacity_factor)
        else:
            y, _ = moe_mod.apply_moe(p["ffn"], cfg, x, rules,
                                     plan.moe_capacity_factor,
                                     groups=plan.moe_groups)
    else:
        y = layers.apply_ffn(p["ffn"], x, cfg.ffn_act, cfg.use_bias)
    if layer is None:
        cache = dict(cache, k=k_cache, v=v_cache)
    return h + _res(cfg, y), cache


def _apply_cross_block(p, cfg, plan, rules, h, ctx, collect=False):
    x = _norm(cfg, p["attn_norm"], h)
    q = layers.q_project(p["attn"], cfg, x)
    k, v = layers.kv_project(p["attn"], cfg, ctx)
    attn_out = layers.dense_attention(q, k, v, causal=False)
    h = h + layers.out_project(p["attn"], cfg, attn_out)
    x = _norm(cfg, p["ffn_norm"], h)
    h = h + layers.apply_ffn(p["ffn"], x, cfg.ffn_act, cfg.use_bias)
    return (h, (k, v)) if collect else (h, None)


def _apply_cross_block_cached(p, cfg, rules, h, kc, vc):
    x = _norm(cfg, p["attn_norm"], h)
    q = layers.q_project(p["attn"], cfg, x)
    attn_out = layers.decode_attention(q, kc, vc, jnp.int32(kc.shape[1]))
    h = h + layers.out_project(p["attn"], cfg, attn_out)
    x = _norm(cfg, p["ffn_norm"], h)
    return h + layers.apply_ffn(p["ffn"], x, cfg.ffn_act, cfg.use_bias)


def _apply_recurrent_block(bp, cfg, plan, rules, h, collect=False):
    x = _norm(cfg, bp["norm"], h)
    if collect:
        y, st = rglru.apply_rglru(bp["lru"], cfg, x, rules, return_state=True)
    else:
        y, st = rglru.apply_rglru(bp["lru"], cfg, x, rules), None
    h = h + y
    x = _norm(cfg, bp["ffn_norm"], h)
    h = h + layers.apply_ffn(bp["ffn"], x, cfg.ffn_act, cfg.use_bias)
    return h, st


def _apply_mamba_block(bp, cfg, plan, rules, h, collect=False):
    x = _norm(cfg, bp["norm"], h)
    kw = dict(chunk=plan.ssd_chunk, bf16=plan.ssd_bf16)
    if collect:
        y, st = ssm_mod.apply_ssm(bp["ssm"], cfg, x, rules,
                                  return_state=True, **kw)
    else:
        y, st = ssm_mod.apply_ssm(bp["ssm"], cfg, x, rules, **kw), None
    h = h + _res(cfg, y)
    x = _norm(cfg, bp["ffn_norm"], h)
    h = h + _res(cfg, layers.apply_ffn(bp["ffn"], x, cfg.ffn_act,
                                       cfg.use_bias))
    return h, st


def _apply_hybrid_block(kind, bp, cfg, plan, rules, h, positions,
                        collect=False):
    """One block of a hybrid; returns (h, its collected state or K/V)."""
    if kind == "recurrent":
        return _apply_recurrent_block(bp, cfg, plan, rules, h, collect)
    if kind == "mamba":
        return _apply_mamba_block(bp, cfg, plan, rules, h, collect)
    h, _, kv = _apply_dense_block(bp, cfg, plan, rules, h, positions,
                                  _hybrid_window(cfg, kind), collect)
    return h, kv


# ===========================================================================
# backbone (training + prefill share this; prefill collects caches)
# ===========================================================================

def _backbone(cfg, plan, rules, params, h, positions, batch, collect=False):
    """Run the layer stack. Returns (hidden, aux_loss, collected)."""
    fam = cfg.family
    window = _window_of(cfg)
    aux0 = jnp.float32(0.0)

    if fam in ("dense", "moe"):
        def body(carry, layer_p):
            hh, aux = carry
            hh, a, kv = _apply_dense_block(layer_p, cfg, plan, rules, hh,
                                           positions, window, collect)
            return (hh, aux + a), kv

        (h, aux), kvs = jax.lax.scan(_maybe_remat(body, plan), (h, aux0),
                                     params["blocks"])
        return h, aux, {"attn": kvs}

    if fam == "vlm":
        ctx = batch["img_embed"].astype(_dt(cfg))

        def group_body(carry, gp):
            hh, aux = carry

            def self_body(h2, lp):
                h2, _, kv = _apply_dense_block(lp, cfg, plan, rules, h2,
                                               positions, window, collect)
                return h2, kv

            hh, kvs = jax.lax.scan(_maybe_remat(self_body, plan), hh,
                                   gp["self"])
            hh, ckv = _apply_cross_block(gp["cross"], cfg, plan, rules, hh,
                                         ctx, collect)
            return (hh, aux), (kvs, ckv)

        (h, aux), (kvs, ckvs) = jax.lax.scan(
            group_body, (h, aux0),
            {"self": params["self_blocks"], "cross": params["cross_blocks"]})
        return h, aux, {"attn": kvs, "cross": ckvs}

    if fam == "audio":
        enc = encode_audio(cfg, plan, rules, params, batch)

        def dec_body(carry, lp):
            hh, aux = carry
            hh, a, kv = _apply_dense_block(lp["self"], cfg, plan, rules, hh,
                                           positions, window, collect)
            hh, ckv = _apply_cross_block(lp["cross"], cfg, plan, rules, hh,
                                         enc, collect)
            return (hh, aux + a), (kv, ckv)

        (h, aux), (kvs, ckvs) = jax.lax.scan(_maybe_remat(dec_body, plan),
                                             (h, aux0), params["dec_blocks"])
        return h, aux, {"attn": kvs, "cross": ckvs}

    if fam == "hybrid":
        pat = cfg.hybrid.pattern

        def group_body(carry, gp):
            hh, aux = carry
            out = {}
            for i, kind in enumerate(pat):
                hh, out[f"b{i}"] = _apply_hybrid_block(
                    kind, gp[f"b{i}"], cfg, plan, rules, hh, positions,
                    collect)
            return (hh, aux), out

        (h, aux), collected = jax.lax.scan(_maybe_remat(group_body, plan),
                                           (h, aux0), params["blocks"])
        tail_out = []
        for i, bp in enumerate(params.get("tail", [])):
            h, st = _apply_hybrid_block(pat[i % len(pat)], bp, cfg, plan,
                                        rules, h, positions, collect)
            tail_out.append(st)
        return h, aux, {"groups": collected, "tail": tail_out}

    if fam == "ssm":
        def body(carry, lp):
            hh, aux = carry
            x = _norm(cfg, lp["norm"], hh)
            if collect:
                y, st = ssm_mod.apply_ssm(lp["ssm"], cfg, x, rules,
                                          return_state=True,
                                          chunk=plan.ssd_chunk,
                                          bf16=plan.ssd_bf16)
            else:
                y, st = ssm_mod.apply_ssm(lp["ssm"], cfg, x, rules,
                                          chunk=plan.ssd_chunk,
                                          bf16=plan.ssd_bf16), None
            return (hh + y, aux), st

        (h, aux), states = jax.lax.scan(_maybe_remat(body, plan), (h, aux0),
                                        params["blocks"])
        return h, aux, {"blocks": states}

    raise ValueError(fam)


def encode_audio(cfg, plan, rules, params, batch):
    enc = batch["frames"].astype(_dt(cfg))
    pos = jnp.arange(enc.shape[1])

    def body(hh, lp):
        x = _norm(cfg, lp["attn_norm"], hh)
        q = layers.q_project(lp["attn"], cfg, x)
        k, v = layers.kv_project(lp["attn"], cfg, x)
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
        hh = hh + layers.out_project(
            lp["attn"], cfg, layers.dense_attention(q, k, v, causal=False))
        x = _norm(cfg, lp["ffn_norm"], hh)
        return hh + layers.apply_ffn(lp["ffn"], x, cfg.ffn_act,
                                     cfg.use_bias), None

    enc, _ = jax.lax.scan(_maybe_remat(body, plan), enc,
                          params["enc_blocks"])
    return _norm(cfg, params["enc_norm"], enc)


# ---------------------------------------------------------------------------
# losses / logits
# ---------------------------------------------------------------------------

def _unembed_matrix(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["unembed"]


def _scale_logits(cfg, logits):
    m = cfg.logits_scaling
    return logits if m == 1.0 else logits / m


def chunked_softmax_xent(cfg, plan, rules, params, hidden, labels):
    """Cross-entropy; the [B,S,V] logits are never fully materialized."""
    w = _unembed_matrix(cfg, params)
    b, s, d = hidden.shape
    chunk = plan.vocab_chunk or s
    chunk = min(chunk, s)
    if s % chunk != 0:
        chunk = s
    nc = s // chunk
    hc = hidden.reshape(b, nc, chunk, d)
    lc = labels.reshape(b, nc, chunk)

    @jax.checkpoint
    def step(acc, inp):
        hh, ll = inp                            # [b,chunk,d], [b,chunk]
        logits = _scale_logits(
            cfg, jnp.einsum("bcd,dv->bcv", hh, w).astype(jnp.float32))
        logits = rules.constrain(logits, ("batch", None, "vocab"))
        if cfg.padded_vocab != cfg.vocab_size:
            pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad[None, None, :], layers.NEG_INF, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(step, jnp.float32(0.0),
                            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)))
    return total / (b * s)


def logits_for(cfg, rules, params, hidden):
    """Full logits for a short hidden slice (decode / last position)."""
    w = _unembed_matrix(cfg, params)
    logits = _scale_logits(
        cfg, jnp.einsum("bsd,dv->bsv", hidden, w).astype(jnp.float32))
    logits = rules.constrain(logits, ("batch", None, "vocab"))
    if cfg.padded_vocab != cfg.vocab_size:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad[None, None, :], layers.NEG_INF, logits)
    return logits


# ===========================================================================
# decode caches
# ===========================================================================

def _kv_cache_len(cfg, seq_len):
    w = _window_of(cfg) or (cfg.window if cfg.family == "hybrid" else 0)
    return min(seq_len, w) if w else seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=None, quant: bool = False) -> Params:
    dtype = dtype or _dt(cfg)
    kvl = _kv_cache_len(cfg, seq_len)
    kv, hd = cfg.n_kv_heads, cfg.head_dim

    def kv_buf(length, *lead):
        shape = tuple(lead) + (batch, length, kv, hd)
        if quant:
            sshape = tuple(lead) + (batch, length, kv, 1)
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_scale": jnp.zeros(sshape, jnp.float32),
                    "v_scale": jnp.zeros(sshape, jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    fam = cfg.family
    if fam in ("dense", "moe"):
        return {"attn": kv_buf(kvl, cfg.n_layers)}
    def kv_buf_plain(length, *lead):
        shape = tuple(lead) + (batch, length, kv, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    if fam == "vlm":
        groups, per = vlm_groups(cfg)
        return {"attn": kv_buf(kvl, groups, per),
                "cross": kv_buf_plain(cfg.n_img_tokens, groups)}
    if fam == "audio":
        return {"attn": kv_buf(kvl, cfg.n_layers),
                "cross": kv_buf_plain(cfg.n_frames, cfg.n_layers)}
    if fam == "hybrid":
        pat = cfg.hybrid.pattern
        groups, tail = hybrid_groups(cfg)

        def block_cache(kind, *lead):
            if kind == "recurrent":
                base = rglru.init_rglru_cache(cfg, batch, dtype)
            elif kind == "mamba":
                base = ssm_mod.init_ssm_cache(cfg, batch, dtype)
            else:
                w = _hybrid_window(cfg, kind)
                return kv_buf_plain(min(seq_len, w) if w else seq_len,
                                    *lead)
            return jax.tree.map(
                lambda x: jnp.zeros(tuple(lead) + x.shape, x.dtype), base)

        out = {"groups": {f"b{i}": block_cache(kind, groups)
                          for i, kind in enumerate(pat)}}
        if tail:
            out["tail"] = [block_cache(pat[i % len(pat)])
                           for i in range(tail)]
        return out
    if fam == "ssm":
        base = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return {"blocks": jax.tree.map(
            lambda x: jnp.zeros((cfg.n_layers,) + x.shape, x.dtype), base)}
    raise ValueError(fam)


def cache_axes(cfg: ModelConfig, quant: bool = False) -> Params:
    def kvbuf(*lead):
        out = {"k": tuple(lead) + ("batch", "kv_seq", "kv_heads", None),
               "v": tuple(lead) + ("batch", "kv_seq", "kv_heads", None)}
        if quant:
            out["k_scale"] = tuple(lead) + ("batch", "kv_seq", "kv_heads",
                                            None)
            out["v_scale"] = tuple(lead) + ("batch", "kv_seq", "kv_heads",
                                            None)
        return out
    def kvbuf_plain(*lead):
        return {"k": tuple(lead) + ("batch", "kv_seq", "kv_heads", None),
                "v": tuple(lead) + ("batch", "kv_seq", "kv_heads", None)}
    def rec_axes(*lead):
        return {"conv": tuple(lead) + ("batch", None, "lru"),
                "h": tuple(lead) + ("batch", None, "lru")}

    def ssm_axes(*lead):
        return {"conv": tuple(lead) + ("batch", None, "lru"),
                "state": tuple(lead) + ("batch", "heads", None, None)}

    def hybrid_axes(kind, *lead):
        if kind == "recurrent":
            return rec_axes(*lead)
        return ssm_axes(*lead) if kind == "mamba" else kvbuf(*lead)
    fam = cfg.family
    if fam in ("dense", "moe"):
        return {"attn": kvbuf("layers")}
    if fam == "vlm":
        return {"attn": kvbuf("layers", None),
                "cross": kvbuf_plain("layers")}
    if fam == "audio":
        return {"attn": kvbuf("layers"), "cross": kvbuf_plain("layers")}
    if fam == "hybrid":
        pat = cfg.hybrid.pattern
        out = {"groups": {f"b{i}": hybrid_axes(kind, "layers")
                          for i, kind in enumerate(pat)}}
        _, tail = hybrid_groups(cfg)
        if tail:
            out["tail"] = [hybrid_axes(pat[i % len(pat)])
                           for i in range(tail)]
        return out
    if fam == "ssm":
        return {"blocks": ssm_axes("layers")}
    raise ValueError(fam)


def _ring_place(k_seq, buf_len, seq_len, dtype):
    """Place collected K/V [.., B, S, KV, D] into a ring buffer of buf_len.

    Token t lives at slot t % buf_len; only the last buf_len tokens are kept.
    Works for the full-cache case too (buf_len >= S: identity placement with
    zero padding at the end).
    """
    s = k_seq.shape[-3]
    if buf_len >= s:
        pad = [(0, 0)] * k_seq.ndim
        pad[-3] = (0, buf_len - s)
        return jnp.pad(k_seq.astype(dtype), pad)
    kept = k_seq[..., s - buf_len:, :, :]
    positions = jnp.arange(buf_len) + (s - buf_len)
    slots = positions % buf_len                      # a permutation
    inv = jnp.argsort(slots)
    return jnp.take(kept, inv, axis=-3).astype(dtype)


def assemble_cache(cfg, collected, batch_size, seq_len, cache_len,
                   dtype=None, quant: bool = False):
    """Turn _backbone(collect=True) outputs into a decode cache at position
    seq_len with buffer size cache_len."""
    dtype = dtype or _dt(cfg)
    kvl = _kv_cache_len(cfg, cache_len)

    def place(kv):
        k, v = kv
        if quant:
            kq, ks = layers.quantize_kv(k)
            vq, vs = layers.quantize_kv(v)
            return {"k": _ring_place(kq, kvl, seq_len, jnp.int8),
                    "v": _ring_place(vq, kvl, seq_len, jnp.int8),
                    "k_scale": _ring_place(ks, kvl, seq_len, jnp.float32),
                    "v_scale": _ring_place(vs, kvl, seq_len, jnp.float32)}
        return {"k": _ring_place(k, kvl, seq_len, dtype),
                "v": _ring_place(v, kvl, seq_len, dtype)}

    def place_block(kind, st):
        if kind in ("recurrent", "mamba"):
            return st
        k, v = st
        w = _hybrid_window(cfg, kind)
        w = min(cache_len, w) if w else cache_len
        return {"k": _ring_place(k, w, seq_len, dtype),
                "v": _ring_place(v, w, seq_len, dtype)}

    def cross(kv):
        k, v = kv
        return {"k": k.astype(dtype), "v": v.astype(dtype)}

    fam = cfg.family
    if fam in ("dense", "moe"):
        return {"attn": place(collected["attn"])}
    if fam == "vlm":
        return {"attn": place(collected["attn"]),
                "cross": cross(collected["cross"])}
    if fam == "audio":
        return {"attn": place(collected["attn"]),
                "cross": cross(collected["cross"])}
    if fam == "hybrid":
        pat = cfg.hybrid.pattern
        out = {"groups": {f"b{i}": place_block(kind,
                                               collected["groups"][f"b{i}"])
                          for i, kind in enumerate(pat)}}
        if collected.get("tail"):
            out["tail"] = [place_block(pat[i % len(pat)], st)
                           for i, st in enumerate(collected["tail"])]
        return out
    if fam == "ssm":
        return {"blocks": collected["blocks"]}
    raise ValueError(fam)


def init_cache_with_context(cfg, plan, rules, params, batch, batch_size,
                            cache_len):
    """Fresh decode cache with cross-attention K/V precomputed from the
    modality context (vlm: image embeddings; audio: encoder output).

    Token-by-token decoding without a text prompt still needs these — the
    cross K/V are a function of the context only, not of decoded tokens.
    """
    cache = init_cache(cfg, batch_size, cache_len,
                       quant=plan.kv_cache_quant)
    dtype = _dt(cfg)
    if cfg.family == "vlm":
        ctx = batch["img_embed"].astype(dtype)
        ks, vs = jax.vmap(lambda p: layers.kv_project(p, cfg, ctx))(
            params["cross_blocks"]["attn"])
        cache["cross"] = {"k": ks.astype(dtype), "v": vs.astype(dtype)}
    elif cfg.family == "audio":
        enc = encode_audio(cfg, plan, rules, params, batch)
        ks, vs = jax.vmap(lambda p: layers.kv_project(p, cfg, enc))(
            params["dec_blocks"]["cross"]["attn"])
        cache["cross"] = {"k": ks.astype(dtype), "v": vs.astype(dtype)}
    return cache


# ===========================================================================
# decode
# ===========================================================================

def _decode_recurrent_block(bp, cfg, rules, h, cache):
    x = _norm(cfg, bp["norm"], h)
    y, nc = rglru.decode_rglru(bp["lru"], cfg, x, cache, rules)
    h = h + y
    x = _norm(cfg, bp["ffn_norm"], h)
    return h + layers.apply_ffn(bp["ffn"], x, cfg.ffn_act, cfg.use_bias), nc


def _decode_mamba_block(bp, cfg, rules, h, cache):
    x = _norm(cfg, bp["norm"], h)
    y, nc = ssm_mod.decode_ssm(bp["ssm"], cfg, x, cache, rules)
    h = h + _res(cfg, y)
    x = _norm(cfg, bp["ffn_norm"], h)
    return h + _res(cfg, layers.apply_ffn(bp["ffn"], x, cfg.ffn_act,
                                          cfg.use_bias)), nc


def _decode_hybrid_block(kind, bp, cfg, plan, rules, h, cache, pos,
                         layer=None):
    """One hybrid block's decode step.  A recurrent or Mamba block's cache
    is its own state; an attention block's is its K/V, or with ``layer``
    the whole stack, as in ``_decode_dense_block``."""
    if kind == "recurrent":
        return _decode_recurrent_block(bp, cfg, rules, h, cache)
    if kind == "mamba":
        return _decode_mamba_block(bp, cfg, rules, h, cache)
    return _decode_dense_block(bp, cfg, plan, rules, h, cache, pos,
                               _hybrid_window(cfg, kind), layer=layer)


def per_row_positions(cfg) -> bool:
    """Whether ``decode_forward`` takes a position per row: a plain KV
    stack, or a hybrid of whole periods (every cache leaf stacked by
    group, the batch on axis 1)."""
    if cfg.family in ("dense", "moe"):
        return True
    return cfg.family == "hybrid" and hybrid_groups(cfg)[1] == 0


def decode_forward(cfg, plan, rules, params, cache, tokens, pos):
    """One decode step. tokens [B,1] int32; pos the absolute position, a
    scalar for the whole batch or int32[B], one per row (where
    ``per_row_positions``: each row attends to and writes at its own
    position)."""
    h = _embed(cfg, rules, params, tokens)
    fam = cfg.family
    window = _window_of(cfg)

    if jnp.ndim(pos) and not per_row_positions(cfg):
        raise ValueError(f"a position per row needs a plain KV stack "
                         f"(dense or moe) or a hybrid of whole periods, "
                         f"not {cfg.name!r} (family {fam!r})")

    if fam in ("dense", "moe") and jnp.ndim(pos):
        # the stack rides in the carry, not in xs/ys: each layer writes its
        # B new rows into it and reads its own layer back, so XLA updates
        # the buffer in place instead of restacking it
        def body(carry, xs):
            hh, kv = carry
            lp, i = xs
            return _decode_dense_block(lp, cfg, plan, rules, hh, kv, pos,
                                       window, layer=i), None

        (h, new_attn), _ = jax.lax.scan(
            body, (h, cache["attn"]),
            (params["blocks"], jnp.arange(cfg.n_layers)))
        new_cache = {"attn": new_attn}
    elif fam in ("dense", "moe"):
        def body(hh, xs):
            lp, lc = xs
            return _decode_dense_block(lp, cfg, plan, rules, hh, lc, pos,
                                       window)

        h, new_attn = jax.lax.scan(body, h, (params["blocks"],
                                             cache["attn"]))
        new_cache = {"attn": new_attn}
    elif fam == "vlm":
        def group_body(hh, xs):
            gp, gc_attn, gc_cross = xs

            def self_body(h2, xs2):
                lp, lc = xs2
                return _decode_dense_block(lp, cfg, plan, rules, h2, lc,
                                           pos, window)

            hh, new_self = jax.lax.scan(self_body, hh, (gp["self"], gc_attn))
            hh = _apply_cross_block_cached(gp["cross"], cfg, rules, hh,
                                           gc_cross["k"], gc_cross["v"])
            return hh, (new_self, gc_cross)

        h, (new_self, new_cross) = jax.lax.scan(
            group_body, h,
            ({"self": params["self_blocks"],
              "cross": params["cross_blocks"]},
             cache["attn"], cache["cross"]))
        new_cache = {"attn": new_self, "cross": new_cross}
    elif fam == "audio":
        def body(hh, xs):
            lp, lc_attn, lc_cross = xs
            hh, nc = _decode_dense_block(lp["self"], cfg, plan, rules, hh,
                                         lc_attn, pos, window)
            hh = _apply_cross_block_cached(lp["cross"], cfg, rules, hh,
                                           lc_cross["k"], lc_cross["v"])
            return hh, (nc, lc_cross)

        h, (new_attn, new_cross) = jax.lax.scan(
            body, h, (params["dec_blocks"], cache["attn"], cache["cross"]))
        new_cache = {"attn": new_attn, "cross": new_cross}
    elif fam == "hybrid" and jnp.ndim(pos):
        pat = cfg.hybrid.pattern

        # as for dense: the caches ride in the carry, each block writing
        # its group's entry in place — an attention block one K/V row per
        # slot, a recurrent or Mamba block its whole state
        def group_body(carry, xs):
            hh, gc = carry
            gp, g = xs
            gc = dict(gc)
            for i, kind in enumerate(pat):
                name = f"b{i}"
                if kind in ("recurrent", "mamba"):
                    st = jax.tree.map(lambda c: c[g], gc[name])
                    hh, st = _decode_hybrid_block(kind, gp[name], cfg, plan,
                                                  rules, hh, st, pos)
                    gc[name] = jax.tree.map(
                        lambda c, n: jax.lax.dynamic_update_index_in_dim(
                            c, n.astype(c.dtype), g, 0), gc[name], st)
                else:
                    hh, gc[name] = _decode_hybrid_block(
                        kind, gp[name], cfg, plan, rules, hh, gc[name], pos,
                        layer=g)
            return (hh, gc), None

        (h, new_groups), _ = jax.lax.scan(
            group_body, (h, cache["groups"]),
            (params["blocks"], jnp.arange(hybrid_groups(cfg)[0])))
        new_cache = {"groups": new_groups}
    elif fam == "hybrid":
        pat = cfg.hybrid.pattern

        def group_body(hh, xs):
            gp, gc = xs
            new_gc = {}
            for i, kind in enumerate(pat):
                hh, new_gc[f"b{i}"] = _decode_hybrid_block(
                    kind, gp[f"b{i}"], cfg, plan, rules, hh, gc[f"b{i}"],
                    pos)
            return hh, new_gc

        h, new_groups = jax.lax.scan(group_body, h,
                                     (params["blocks"], cache["groups"]))
        new_cache = {"groups": new_groups}
        if "tail" in params:
            new_tail = []
            for i, bp in enumerate(params["tail"]):
                h, nc = _decode_hybrid_block(pat[i % len(pat)], bp, cfg,
                                             plan, rules, h,
                                             cache["tail"][i], pos)
                new_tail.append(nc)
            new_cache["tail"] = new_tail
    elif fam == "ssm":
        def body(hh, xs):
            lp, lc = xs
            x = _norm(cfg, lp["norm"], hh)
            y, nc = ssm_mod.decode_ssm(lp["ssm"], cfg, x, lc, rules)
            return hh + y, nc

        h, new_blocks = jax.lax.scan(body, h, (params["blocks"],
                                               cache["blocks"]))
        new_cache = {"blocks": new_blocks}
    else:
        raise ValueError(fam)

    h = _norm(cfg, params["final_norm"], h)
    logits = logits_for(cfg, rules, params, h)[:, 0]
    return logits, new_cache


# ===========================================================================
# public API
# ===========================================================================

class Model:
    """Binds (cfg, plan, rules) into callable train/serve functions."""

    def __init__(self, cfg: ModelConfig, plan: Optional[Plan] = None,
                 rules=None):
        self.cfg = cfg
        self.plan = plan or Plan()
        self.rules = rules or NullRules()

    def init(self, key) -> Params:
        return init_params(key, self.cfg)

    def param_axes(self) -> Params:
        return param_axes(self.cfg)

    def train_loss(self, params, batch) -> Tuple[jax.Array, Dict]:
        cfg, plan, rules = self.cfg, self.plan, self.rules
        tokens = batch["tokens"]
        h = _embed(cfg, rules, params, tokens)
        positions = jnp.arange(tokens.shape[1])
        h, aux, _ = _backbone(cfg, plan, rules, params, h, positions, batch)
        h = _norm(cfg, params["final_norm"], h)
        loss = chunked_softmax_xent(cfg, plan, rules, params, h,
                                    batch["labels"])
        total = loss + 0.01 * aux
        return total, {"loss": loss, "aux_loss": aux}

    def prefill(self, params, batch, cache_len: int):
        """Full-prompt pass; returns (last_logits, decode cache)."""
        cfg, plan, rules = self.cfg, self.plan, self.rules
        tokens = batch["tokens"]
        b, s = tokens.shape
        h = _embed(cfg, rules, params, tokens)
        positions = jnp.arange(s)
        h, _, collected = _backbone(cfg, plan, rules, params, h, positions,
                                    batch, collect=True)
        h = _norm(cfg, params["final_norm"], h)
        last = logits_for(cfg, rules, params, h[:, -1:])[:, 0]
        cache = assemble_cache(cfg, collected, b, s, cache_len,
                               quant=plan.kv_cache_quant)
        return last, cache

    def decode_step(self, params, cache, tokens, pos):
        return decode_forward(self.cfg, self.plan, self.rules, params, cache,
                              tokens, pos)

    def init_context_cache(self, params, batch, batch_size, cache_len):
        return init_cache_with_context(self.cfg, self.plan, self.rules,
                                       params, batch, batch_size, cache_len)
