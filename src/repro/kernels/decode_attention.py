"""Split-K decode attention Pallas kernel (FlashDecoding-style).

One query token attends to a long KV cache; the cache's sequence dim is
split across the innermost grid dim so each step reduces one KV tile with
an online-softmax carry in VMEM (same recurrence as flash_attention but
q_len == 1, so the whole accumulator is a [1, D] vector) — the kernel
analogue of the sequence-sharded decode path in ``repro.models.layers``.

On hardware this grid dim maps to parallel split-K partials combined by a
final logsumexp merge; in interpret mode the sequential reduction gives the
same numerics.  Every block keeps its last two dims TPU-tileable: the query
and output ride as [BH, 1, D] (block (1, 1, D)), the running max / denom
are (1, 1) VMEM tiles, and the cache length is an SMEM scalar.
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, n_kv: int, block_kv: int, scale: float):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                    # [1, d]
    k = k_ref[0]                                    # [bkv, d]
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)                # [1, bkv]

    m_prev = m_ref[...]                             # [1, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr \
        + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, block_kv: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q [BH, D]; k/v_cache [BH, S, D]; cache_len scalar int32 -> [BH, D]."""
    bh, d = q.shape
    s = k_cache.shape[1]
    bkv = min(block_kv, s)
    assert s % bkv == 0, (s, bkv)
    gkv = s // bkv
    scale = 1.0 / math.sqrt(d)
    # the length is one scalar for every row: it rides in SMEM, not in a
    # (1, 1) VMEM block the TPU tiling refuses
    lens = jnp.reshape(jnp.asarray(cache_len, jnp.int32), (1,))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, n_kv=gkv, block_kv=bkv,
                          scale=scale),
        grid=(bh, gkv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(lens, q[:, None, :], k_cache, v_cache)
    return out[:, 0]


def decode_attention_ref(q, k_cache, v_cache, cache_len) -> jax.Array:
    """Pure-jnp oracle. q [BH, D]; caches [BH, S, D]."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bd,bkd->bk", q, k_cache).astype(jnp.float32) * scale
    valid = jnp.arange(k_cache.shape[1]) < cache_len
    s = jnp.where(valid[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bk,bkd->bd", p.astype(q.dtype), v_cache)
