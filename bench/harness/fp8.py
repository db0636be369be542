"""The controls' precision: float8 e4m3 under a per-tensor scale."""
from __future__ import annotations

F8_MAX = 240.0      # largest finite value with 4 exponent, 3 mantissa bits


def round_f8(x):
    """Round to float8 e4m3 (3 mantissa bits) under a per-tensor scale.
    ``reduce_precision`` is kept by XLA, where a round trip through a
    float8 dtype may be simplified away on the TPU."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s
