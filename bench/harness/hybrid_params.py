"""The benchmark's hybrid weights, put into the program's parameter tree.

The weights are drawn by the hybrid reference's law (``hybrid_reference``),
on the device in one jitted call, and placed where the program's hybrid
(``repro.models.lm``, family ``hybrid``) keeps them: the tied table under
``embed``; block ``i`` of the pattern under ``blocks/b{i}``, stacked over
the groups, so that layer ``g * len(pattern) + i`` is entry ``g``.  This is
the one file of the benchmark that follows the program's hybrid layout,
as ``lm_params`` does the dense one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.harness import hybrid_reference as R, lm_reference


def program_params(cfg: dict, key, like):
    """The program's parameter tree holding the reference's weights.
    ``cfg`` is what the reference reads; ``like`` the program's tree of
    shapes, which the result has to match leaf for leaf."""
    pat, d, dt = cfg["pattern"], cfg["d_model"], cfg["param_dtype"]
    groups = cfg["n_layers"] // len(pat)

    def build(key):
        def ones(n):
            return {"scale": jnp.ones((groups, n), dt)}
        blocks = {}
        for i, kind in enumerate(pat):
            w = jax.vmap(lambda g: R.layer_weights(
                cfg, key, g * len(pat) + i, kind))(jnp.arange(groups))
            ffn = {"w_gate": w["w_gate"], "w_in": w["w_up"],
                   "w_out": w["w_down"]}
            if kind == "mamba":
                di = R.mamba_dims(cfg)["d_inner"]
                ssm = {"w_in": w["in_proj"], "conv_w": w["conv_w"],
                       "conv_b": w["conv_b"], "A_log": w["A_log"],
                       "D": w["D"], "dt_bias": w["dt_bias"],
                       "w_out": w["out_proj"],
                       "norm_scale": jnp.ones((groups, di), dt)}
                blocks[f"b{i}"] = {"norm": ones(d), "ssm": ssm,
                                   "ffn_norm": ones(d), "ffn": ffn}
            else:
                blocks[f"b{i}"] = {"attn_norm": ones(d),
                                   "attn": {k: w[k] for k in R.ATTN},
                                   "ffn_norm": ones(d), "ffn": ffn}
        return {"embed": lm_reference.embedding(cfg, key),
                "final_norm": {"scale": jnp.ones((d,), dt)},
                "blocks": blocks}

    got = jax.eval_shape(build, key)
    if cfg["n_layers"] % len(pat) or \
            jax.tree.structure(got) != jax.tree.structure(like) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(like))):
        raise ValueError("the program's parameter tree is not the one "
                         "bench/harness/hybrid_params.py builds")
    return jax.jit(build)(key)
