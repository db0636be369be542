"""HPEC tdFIR: time-domain FIR filter bank (paper §III.A: 64 filters,
4096-length vectors, complex data as planar re/im).

The FIR nest is the paper's function-block offload target: the registry
entry in ``repro.apps.registry`` matches it by name ("tdfir") and by jaxpr
similarity, and supplies the Pallas kernel (FPGA analogue) plus XLA
implementations as replacements — reproducing the tdFIR row of Fig. 3.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.offloadable import LoopNest, OffloadableApp
from repro.kernels import tdfir as fir_kernel

N_FILTERS = 64
N_LEN_FULL = 4096
N_LEN_SMALL = 256
N_TAPS = 128
N_TAPS_SMALL = 16


def make_inputs(seed: int = 0, small: bool = False):
    n = N_LEN_SMALL if small else N_LEN_FULL
    taps = N_TAPS_SMALL if small else N_TAPS
    f = 8 if small else N_FILTERS
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "x_re": jax.random.normal(k1, (f, n), jnp.float32),
        "x_im": jax.random.normal(k2, (f, n), jnp.float32),
        "h_re": jax.random.normal(k3, (f, taps), jnp.float32) * 0.1,
        "h_im": jax.random.normal(k4, (f, taps), jnp.float32) * 0.1,
    }


def _fir_seq_1(x, h):
    """Single-filter FIR as the C loop nest: output-sample loop."""
    n = x.shape[0]
    k = h.shape[0]
    xp = jnp.pad(x, (k - 1, 0))

    def sample(_, i):
        window = jax.lax.dynamic_slice(xp, (i,), (k,))
        return None, jnp.dot(window, h[::-1])

    _, y = jax.lax.scan(sample, None, jnp.arange(n))
    return y


def _complex_fir(fn):
    def run(state):
        rr = fn(state["x_re"], state["h_re"])
        ii = fn(state["x_im"], state["h_im"])
        ri = fn(state["x_re"], state["h_im"])
        ir = fn(state["x_im"], state["h_re"])
        return dict(state, y_re=rr - ii, y_im=ri + ir)
    return run


def _fir_xla(x, h):
    """Vectorized causal FIR via conv (the parallelized XLA path)."""
    k = h.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0)))[:, None, :]   # [F,1,N+K-1]
    hf = h[:, None, ::-1]                               # [F,1,K]
    out = jax.lax.conv_general_dilated(
        xp, hf, window_strides=(1,), padding="VALID",
        feature_group_count=x.shape[0],
        dimension_numbers=("CNH", "OIH", "CNH"))
    return out[:, 0, :]


def _fir_pallas(x, h):
    return fir_kernel.tdfir(x, h, block_n=max(128, h.shape[1]))


def _fir_nest():
    def seq(state):
        return _complex_fir(
            lambda x, h: jax.vmap(_fir_seq_1)(x, h))(state)

    # NOTE: seq here still vmaps across filters (a C loop over 64 filters
    # adds nothing on one core); the sequential structure is the
    # per-output-sample loop, faithful to the C kernel.
    return LoopNest(
        name="tdfir_filter_bank",
        impls={"seq": seq,
               "dp": _complex_fir(_fir_xla),
               "tp": _complex_fir(_fir_xla),
               "pallas": _complex_fir(_fir_pallas)},
        trip_count=2, doc="time-domain FIR: the FB offload target")


def _scale_nest():
    def seq(state):
        def row(_, i):
            return None, (state["y_re"][i] * 0.5, state["y_im"][i] * 0.5)
        _, (yr, yi) = jax.lax.scan(row, None,
                                   jnp.arange(state["y_re"].shape[0]))
        return dict(state, y_re=yr, y_im=yi)

    def dp(state):
        return dict(state, y_re=state["y_re"] * 0.5,
                    y_im=state["y_im"] * 0.5)

    return LoopNest(name="scale_output", impls={"seq": seq, "dp": dp,
                                                "tp": dp},
                    trip_count=2, doc="output scaling loop")


def _energy_nest():
    def seq(state):
        def row(acc, i):
            return acc + jnp.sum(state["y_re"][i] ** 2
                                 + state["y_im"][i] ** 2), None
        acc, _ = jax.lax.scan(row, jnp.float32(0.0),
                              jnp.arange(state["y_re"].shape[0]))
        return dict(state, out=jnp.concatenate(
            [state["y_re"], state["y_im"],
             jnp.full((1, state["y_re"].shape[1]), acc)]))

    def dp(state):
        acc = jnp.sum(state["y_re"] ** 2 + state["y_im"] ** 2)
        return dict(state, out=jnp.concatenate(
            [state["y_re"], state["y_im"],
             jnp.full((1, state["y_re"].shape[1]), acc)]))

    return LoopNest(name="energy_check", impls={"seq": seq, "dp": dp,
                                                "tp": dp},
                    trip_count=2, doc="verification energy sum")


def build_app() -> OffloadableApp:
    return OffloadableApp(
        name="tdFIR",
        nests=[_fir_nest(), _scale_nest(), _energy_nest()],
        make_inputs=make_inputs,
        doc="HPEC time-domain FIR filter bank")
