"""Time-domain FIR Pallas kernel — the paper's tdFIR function-block offload
target (HPEC Challenge; Intel FPGA OpenCL sample analogue).

y[f, n] = sum_k h[f, k] * x[f, n - k]   (causal, per-filter bank)

TPU adaptation of the FPGA systolic FIR: grid (F/8, N/bn); each step loads
an (8, bn) tile of eight filters plus the *previous* tile (same input bound
twice with shifted index_maps — the Pallas idiom for overlapping windows),
forms the K-1-deep sliding history in VMEM, and accumulates the tap loop on
the VPU.  Tiles are (8, 128·m) so the TPU tiling accepts them: the filter
bank is padded to a multiple of 8 rows, and the taps are unrolled so every
history slice and tap column is a static offset.  Complex data is handled
as planar re/im (MXU/VPU have no complex type).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

ROWS = 8                    # filters per tile: the TPU's sublane count


def _tdfir_kernel(xprev_ref, xcur_ref, h_ref, o_ref, *, n_taps: int,
                  block_n: int):
    j = pl.program_id(1)
    # zero history before the signal start (block 0's "previous" block
    # aliases block 0 itself; mask it off)
    xprev = jnp.where(j == 0, 0.0, xprev_ref[...])
    xfull = jnp.concatenate([xprev, xcur_ref[...]], axis=1)   # [8, 2*bn]
    h = h_ref[...]                                            # [8, bn]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for k in range(n_taps):
        # y[:, n] += h[:, k] * x[:, n-k]  ->  slice starting at bn-k
        acc = acc + h[:, k:k + 1] * xfull[:, block_n - k:2 * block_n - k]
    o_ref[...] = acc.astype(o_ref.dtype)


def tdfir(x: jax.Array, h: jax.Array, *, block_n: int = 512,
          interpret: Optional[bool] = None) -> jax.Array:
    """x [F, N] float32, h [F, K] float32 -> y [F, N] (causal FIR)."""
    f, n = x.shape
    f2, k = h.shape
    assert f == f2
    bn = min(block_n, n)
    assert bn >= k, f"block_n {bn} must cover the {k} taps"
    pf, pn = (-f) % ROWS, (-n) % bn
    x = jnp.pad(x, ((0, pf), (0, pn)))
    hp = jnp.pad(h, ((0, pf), (0, bn - k)))
    gn = x.shape[1] // bn

    out = pl.pallas_call(
        functools.partial(_tdfir_kernel, n_taps=k, block_n=bn),
        grid=(x.shape[0] // ROWS, gn),
        in_specs=[
            # previous block (clamped at the left edge; masked in-kernel)
            pl.BlockSpec((ROWS, bn),
                         lambda i, j: (i, jnp.maximum(j - 1, 0))),
            pl.BlockSpec((ROWS, bn), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, bn), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, x, hp)
    return out[:f, :n]


def tdfir_complex(x_re, x_im, h_re, h_im, **kw):
    """Complex FIR via 4 real FIRs (planar layout)."""
    rr = tdfir(x_re, h_re, **kw)
    ii = tdfir(x_im, h_im, **kw)
    ri = tdfir(x_re, h_im, **kw)
    ir = tdfir(x_im, h_re, **kw)
    return rr - ii, ri + ir
