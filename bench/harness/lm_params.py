"""The benchmark's weights, put into the program's parameter tree.

The weights are drawn by the reference's law (``lm_reference``), on the
device in one jitted call, and placed where the program's dense decoder
(``repro.models.lm``, family ``dense``) keeps them: the tied table under
``embed``, the layers stacked on a leading axis under ``blocks``.  This is
the one file of the benchmark that follows the program's layout; a change
of that layout edits this file, and neither the reference nor the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import lm_reference

ATTN = ("wq", "wk", "wv", "wo")
FFN = ("w_gate", "w_in", "w_out")


def program_params(cfg: dict, key, like):
    """The program's parameter tree holding the reference's weights.
    ``cfg`` is what the reference reads; ``like`` the program's tree of
    shapes (``jax.eval_shape`` of its initializer), which the result has
    to match leaf for leaf."""
    n, d, dt = cfg["n_layers"], cfg["d_model"], cfg["param_dtype"]

    def build(key):
        w = jax.vmap(lambda layer: lm_reference.layer_weights(
            cfg, key, layer))(jnp.arange(n))

        def ones():
            return {"scale": jnp.ones((n, d), dt)}
        return {"embed": lm_reference.embedding(cfg, key),
                "final_norm": {"scale": jnp.ones((d,), dt)},
                "blocks": {"attn_norm": ones(),
                           "attn": {k: w[k] for k in ATTN},
                           "ffn_norm": ones(),
                           "ffn": {k: w[k] for k in FFN}}}

    got = jax.eval_shape(build, key)
    if jax.tree.structure(got) != jax.tree.structure(like) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(like))):
        raise ValueError("the program's parameter tree is not the one "
                         "bench/harness/lm_params.py builds")
    return jax.jit(build)(key)
