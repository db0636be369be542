"""Everything random in a run comes from ``--seed``, any whole number
(seeds may exceed 32 bits)."""
from __future__ import annotations

import numpy as np


def jax_key(seed: int):
    """A JAX key for ``seed``: its low 32 bits, with the high 32 folded in
    (``PRNGKey`` alone keeps only the low bits)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy generator per (seed, stream)."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])
