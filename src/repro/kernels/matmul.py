"""MXU-tiled matmul Pallas kernel (FPGA-analogue FB replacement for the 3mm
app and dense-layer blocks).

Grid (M/bm, N/bn, K/bk); A and B tiles stream HBM->VMEM per BlockSpec, the
fp32 accumulator lives in a VMEM scratch that persists across the K grid
dimension (innermost).  Tile defaults are MXU-aligned (128x128x128).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, precision):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=precision)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jax.Array, b: jax.Array, *, block_m: int = 128,
           block_n: int = 128, block_k: int = 128,
           interpret: Optional[bool] = None) -> jax.Array:
    """a [M, K] @ b [K, N] -> [M, N] with fp32 accumulation."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    gm, gn, gk = a.shape[0] // bm, b.shape[1] // bn, a.shape[1] // bk
    # float32 operands contract in float32: Mosaic's default multiplies
    # them in one bfloat16 pass, which is not a float32 matmul
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=gk, precision=precision),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a, b)
    return out[:m, :n]
