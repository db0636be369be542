"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Sort-based (not dense one-hot) dispatch keeps the dispatch buffer at
[E, C, D] instead of [T, E, C]: tokens are ordered by expert id, position-
within-expert is computed from segment offsets, and tokens beyond the
per-expert capacity are dropped (standard GShard semantics).  Experts are
sharded over the ``model`` axis (EP); the scatter from token-sharded to
expert-sharded layout is where GSPMD emits the all-to-all that §Roofline
tracks.

Supports Moonlight-style shared experts and Arctic-style dense-residual FFN.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def init_moe(key, cfg, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    ek = jax.random.split(ks[1], m.n_experts)
    experts = jax.vmap(
        lambda k: layers.init_ffn(k, d, m.d_expert, cfg.ffn_act, False, dtype)
    )(ek)
    p = {"router": layers.dense_init(ks[0], (d, m.n_experts), d, dtype),
         "experts": experts}
    if m.shared_experts:
        p["shared"] = layers.init_ffn(
            ks[2], d, m.d_expert * m.shared_experts, cfg.ffn_act, False, dtype)
    if m.dense_residual:
        p["dense"] = layers.init_ffn(
            ks[3], d, m.dense_d_ff or cfg.d_ff, cfg.ffn_act, False, dtype)
    return p


def moe_axes(cfg):
    m = cfg.moe
    gated = cfg.ffn_act in ("swiglu", "geglu")
    expert_axes = {"w_in": ("experts", "embed", "ff"),
                   "w_out": ("experts", "ff", "embed")}
    if gated:
        expert_axes["w_gate"] = ("experts", "embed", "ff")
    p = {"router": ("embed", None), "experts": expert_axes}
    if m.shared_experts:
        p["shared"] = layers.ffn_axes(cfg.ffn_act, False)
    if m.dense_residual:
        p["dense"] = layers.ffn_axes(cfg.ffn_act, False)
    return p


def _expert_ffn(p, x, act):
    """x [E, C, D] with per-expert weights stacked on dim 0."""
    h = jnp.einsum("ecd,edf->ecf", x, p["w_in"])
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"])) * h
    elif act == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"])) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"])


def _expert_ffn_grouped(p, x, act):
    """x [G, E, C, D] with per-expert weights stacked on dim 1."""
    h = jnp.einsum("gecd,edf->gecf", x, p["w_in"])
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", x, p["w_gate"])) * h
    elif act == "geglu":
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", x, p["w_gate"])) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    return jnp.einsum("gecf,efd->gecd", h, p["w_out"])


def apply_moe(p, cfg, x, rules, capacity_factor=None, groups: int = 1):
    """x [B,S,D] -> [B,S,D].

    GShard-style grouped dispatch: tokens are split into `groups` groups
    (aligned with the data shards), capacity is per-group, and the dispatch
    buffer is [G, E, C_g, D] with G on the data axes and E on the expert
    axis — the G<->E re-sharding boundary is where GSPMD emits the MoE
    all-to-all.  groups=1 degenerates to a single global group.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    g = max(int(groups), 1)
    if t % g != 0:
        g = 1
    tg = t // g
    cf = capacity_factor or m.capacity_factor
    capacity = max(int(tg * m.top_k * cf / m.n_experts), m.top_k)

    tokens = rules.constrain(x.reshape(g, tg, d), ("batch", None, None))
    logits = jnp.einsum("gtd,de->gte", tokens,
                        p["router"]).astype(jnp.float32)
    gates, expert_ids = jax.lax.top_k(logits, m.top_k)         # [g,tg,k]
    gates = jax.nn.softmax(gates, axis=-1)

    # per-group (token, k) pairs sorted by expert id
    fe = expert_ids.reshape(g, tg * m.top_k)
    order = jnp.argsort(fe, axis=1)                             # stable
    se = jnp.take_along_axis(fe, order, axis=1)                 # [g, tg*k]
    st = order // m.top_k
    sg = jnp.take_along_axis(gates.reshape(g, tg * m.top_k), order, axis=1)

    counts = jax.vmap(lambda v: jnp.bincount(v, length=m.n_experts))(se)
    starts = jnp.cumsum(counts, axis=1) - counts                # [g, E]
    pos = jnp.arange(tg * m.top_k)[None, :] \
        - jnp.take_along_axis(starts, se, axis=1)
    keep = pos < capacity
    pos_c = jnp.where(keep, pos, 0)

    # dispatch: [G, E, C, D]; G on data axes, E on the expert axis
    vals = jnp.where(keep[..., None],
                     jnp.take_along_axis(tokens, st[..., None], axis=1),
                     0).astype(x.dtype)
    gi = jnp.broadcast_to(jnp.arange(g)[:, None], se.shape)
    buf = jnp.zeros((g, m.n_experts, capacity, d), x.dtype)
    buf = buf.at[gi, se, pos_c].add(vals)
    buf = rules.constrain(buf, ("batch", "experts", None, None))

    out_buf = _expert_ffn_grouped(p["experts"], buf, cfg.ffn_act)
    out_buf = rules.constrain(out_buf, ("batch", "experts", None, None))

    # combine: gather back to token layout, weight by gate
    gathered = out_buf[gi, se, pos_c]                           # [g,tg*k,D]
    gathered = jnp.where(keep[..., None], gathered, 0)
    combined = jnp.zeros((g, tg, d), x.dtype).at[
        gi, st].add((gathered.astype(jnp.float32)
                     * sg[..., None]).astype(x.dtype))
    combined = rules.constrain(combined, ("batch", None, None))
    y = combined.reshape(b, s, d)

    if m.shared_experts:
        y = y + layers.apply_ffn(p["shared"], x, cfg.ffn_act)
    if m.dense_residual:
        y = y + layers.apply_ffn(p["dense"], x, cfg.ffn_act)

    # aux: load-balance loss term (Switch-style), returned via metric hook
    me = jnp.mean(jax.nn.softmax(logits, -1), axis=(0, 1))       # [E]
    ce = counts.sum(axis=0).astype(jnp.float32) / (t * m.top_k)
    aux = m.n_experts * jnp.sum(me * ce)
    return y, aux


def apply_moe_ep(p, cfg, x, rules, capacity_factor=None):
    """Explicit expert-parallel MoE via shard_map over the `model` axis.

    Tokens are replicated across `model` (standard TP residual stream), so
    each model rank routes every token locally, runs ONLY its E/ep local
    experts, and the single collective is a psum of the partial outputs —
    the GSPMD scatter/gather formulation above turns the same dataflow into
    full-buffer masked all-reduces (~100x more wire bytes; see
    EXPERIMENTS.md §Perf moonshot iterations).

    Falls back to apply_moe when no mesh / non-divisible experts.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import batch_axes

    m = cfg.moe
    mesh = getattr(rules, "mesh", None)
    ep = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is None or ep == 1 or m.n_experts % ep != 0:
        return apply_moe(p, cfg, x, rules, capacity_factor)
    e_loc = m.n_experts // ep
    dp = batch_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b, s, d = x.shape
    if b % dp_size != 0:
        return apply_moe(p, cfg, x, rules, capacity_factor)
    t_loc = (b // dp_size) * s
    cf = capacity_factor or m.capacity_factor
    # per-(data-shard, expert) capacity — the deployed-MoE semantics
    capacity = max(int(t_loc * m.top_k * cf / m.n_experts), m.top_k)

    def body(tokens, router, experts):
        # fully manual: tokens is THIS data shard's slice [b/dp, s, d];
        # experts is this model rank's slice [E/ep, d, f]; routing, sort and
        # dispatch are all local — the only collective is the output psum.
        rank = jax.lax.axis_index("model")
        off = rank * e_loc
        # f32 at the boundary: replicated-input cotangents are psum'ed in
        # bwd and 16-bit all-reduce promotion crashes XLA:CPU
        tokens = tokens.astype(x.dtype)
        router = router.astype(x.dtype)
        tk = tokens.reshape(t_loc, d)
        logits = jnp.einsum("td,de->te", tk, router).astype(jnp.float32)
        gates, idx = jax.lax.top_k(logits, m.top_k)             # [t,k]
        gates = jax.nn.softmax(gates, axis=-1)

        fe = idx.reshape(-1)
        fg = gates.reshape(-1)
        ft = jnp.repeat(jnp.arange(t_loc), m.top_k)
        mine = (fe >= off) & (fe < off + e_loc)
        le = jnp.where(mine, fe - off, e_loc)                   # e_loc=drop
        order = jnp.argsort(le)                                 # mine first
        le_s, ft_s, fg_s = le[order], ft[order], fg[order]
        counts = jnp.bincount(le_s, length=e_loc + 1)[:e_loc]
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * m.top_k) - jnp.take(
            jnp.append(starts, 0), jnp.minimum(le_s, e_loc))
        keep = (le_s < e_loc) & (pos < capacity)
        le_c = jnp.where(keep, le_s, 0)
        pos_c = jnp.where(keep, pos, 0)

        buf = jnp.zeros((e_loc, capacity, d), tokens.dtype)
        buf = buf.at[le_c, pos_c].add(
            jnp.where(keep[:, None], tk[ft_s], 0).astype(tokens.dtype))
        out_buf = _expert_ffn(experts, buf, cfg.ffn_act)
        gathered = jnp.where(keep[:, None], out_buf[le_c, pos_c], 0)
        partial = jnp.zeros((t_loc, d), jnp.float32).at[ft_s].add(
            gathered.astype(jnp.float32) * fg_s[:, None])
        y = jax.lax.psum(partial, "model").astype(tokens.dtype)
        y = y.reshape(tokens.shape)

        me = jnp.mean(jax.nn.softmax(logits, -1), axis=0)       # [E]
        ce_loc = counts.astype(jnp.float32) / (t_loc * m.top_k)
        aux_partial = m.n_experts * jnp.sum(
            jax.lax.dynamic_slice(me, (off,), (e_loc,)) * ce_loc)
        aux = jax.lax.psum(aux_partial, "model")
        aux = jax.lax.pmean(aux, dp)
        return y, aux

    experts_spec = jax.tree.map(lambda _: P("model"), p["experts"])
    manual = set(dp) | {"model"}
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp), P(), experts_spec),
        out_specs=(P(dp), P()),
        axis_names=manual, check_vma=False,
    )(x.astype(jnp.float32), p["router"].astype(jnp.float32), p["experts"])

    if m.shared_experts:
        y = y + layers.apply_ffn(p["shared"], x, cfg.ffn_act)
    if m.dense_residual:
        y = y + layers.apply_ffn(p["dense"], x, cfg.ffn_act)
    return y, aux
