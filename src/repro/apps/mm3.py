"""polybench 3mm: G = (A·B)·(C·D)  (paper §III.A, STANDARD_DATASET 1000^3;
reduced default here so GA measurement loops stay tractable on one core).

Loop nests mirror the C benchmark: four init loops + three matmul triple
nests.  ``seq`` runs each matmul as a lax.scan over output rows (the
single-core loop structure); ``dp`` is the parallelized XLA dot; ``tp`` adds
model-axis-style reduction splitting with an explicit partial-sum combine
(the transfer-disciplined GPU-analogue); ``pallas`` is the MXU-tiled kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.offloadable import LoopNest, OffloadableApp
from repro.kernels import matmul as mm_kernel

N_FULL = 512
N_SMALL = 64


def _seq_matmul(a, b):
    def row(_, r):
        return None, jnp.dot(r, b)
    _, rows = jax.lax.scan(row, None, a)
    return rows


def _tp_matmul(a, b, parts: int = 4):
    k = a.shape[1]
    assert k % parts == 0
    aa = a.reshape(a.shape[0], parts, k // parts)
    bb = b.reshape(parts, k // parts, b.shape[1])
    partial = jnp.einsum("mpk,pkn->pmn", aa, bb)   # p partial products
    return partial.sum(axis=0)                     # explicit combine


def _pallas_matmul(a, b):
    return mm_kernel.matmul(a, b)


def _init_nest(name, key_idx):
    def seq(state):
        iv = state["iv"]                       # [n] float index vector
        def row(c, i):
            return c, jnp.sin(i * 0.37 + key_idx) * jnp.cos(iv * 0.11
                                                            + key_idx)
        _, m = jax.lax.scan(row, None, iv)
        return dict(state, **{name.split("_")[1]: m})

    def dp(state):
        iv = state["iv"]
        m = (jnp.sin(iv * 0.37 + key_idx)[:, None]
             * jnp.cos(iv * 0.11 + key_idx)[None, :])
        return dict(state, **{name.split("_")[1]: m})

    return LoopNest(name=name, impls={"seq": seq, "dp": dp, "tp": dp},
                    trip_count=2, doc="matrix init double loop")


def _mm_nest(name, lhs, rhs, out):
    def seq(state):
        return dict(state, **{out: _seq_matmul(state[lhs], state[rhs])})

    def dp(state):
        return dict(state, **{out: jnp.dot(state[lhs], state[rhs])})

    def tp(state):
        return dict(state, **{out: _tp_matmul(state[lhs], state[rhs])})

    def pallas(state):
        return dict(state, **{out: _pallas_matmul(state[lhs], state[rhs])})

    return LoopNest(name=name,
                    impls={"seq": seq, "dp": dp, "tp": tp,
                           "pallas": pallas},
                    trip_count=3, doc="matmul triple nest")


def make_inputs(seed: int = 0, small: bool = False):
    n = N_SMALL if small else N_FULL
    return {"iv": jnp.arange(n, dtype=jnp.float32)}


def build_app() -> OffloadableApp:
    nests = [
        _init_nest("init_A", 1),
        _init_nest("init_B", 2),
        _init_nest("init_C", 3),
        _init_nest("init_D", 4),
        _mm_nest("mm1_E_AB", "A", "B", "E"),
        _mm_nest("mm2_F_CD", "C", "D", "F"),
        _mm_nest("mm3_G_EF", "E", "F", "out"),
    ]
    return OffloadableApp(name="3mm", nests=nests, make_inputs=make_inputs,
                          doc="polybench 3mm (3 chained matmuls)")
