"""Blockwise (flash) attention Pallas kernel — FB replacement for the
softmax(QK^T)V block (causal, GQA via pre-grouped heads).

Grid (B*H, Sq/bq, Skv/bkv); kv is the innermost grid dim so the running
(max, denom, acc) scratch persists across kv steps for one q tile
(online-softmax).  Causal masking is positional; fully-masked tiles still
execute (Pallas TPU grids are dense) but contribute zeros.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, block_q: int, block_kv: int, causal: bool,
                  scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # [bq, d]
    k = k_ref[0]                                  # [bkv, d]
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * block_q + jnp.arange(block_q)
        kpos = ki * block_kv + jnp.arange(block_kv)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] \
        + jnp.dot(p.astype(v.dtype), v,
                  preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, interpret: Optional[bool] = None
                    ) -> jax.Array:
    """q [BH, Sq, D], k/v [BH, Skv, D] (heads pre-flattened/grouped)."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    gq, gkv = sq // bq, skv // bkv
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(_flash_kernel, n_kv=gkv, block_q=bq, block_kv=bkv,
                          causal=causal, scale=scale),
        grid=(bh, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
