"""Operations and bytes that a Mamba-2 + GQA hybrid's work needs, from its
shapes alone (the ``model`` dict of a configuration file, ``hybrid`` and
``ssm`` nested as the program's ``ModelConfig`` has them).

As in ``counts``: what the algorithm requires, not what an implementation
executes.  A slot that decodes nothing, a masked score, a padded position
of the last SSD chunk or a padded vocabulary row adds nothing; a multiply
and an add count as two operations.
"""
from __future__ import annotations

from typing import Dict, Sequence

from bench.harness.counts import _bytes


def layer_counts(m: dict) -> Dict[str, int]:
    """How many layers of each kind the model has."""
    pat = m["hybrid"]["pattern"]
    out = {"mamba": 0, "attention": 0}
    for i in range(m["n_layers"]):
        out[pat[i % len(pat)]] += 1
    return out


def ssm_dims(m: dict) -> Dict[str, int]:
    s, d = m["ssm"], m["d_model"]
    di = s["expand"] * d
    gn = s["n_groups"] * s["d_state"]
    return {"d_inner": di, "heads": di // s["headdim"], "conv": di + 2 * gn,
            "in": 2 * di + 2 * gn + di // s["headdim"]}


def matmul_params(m: dict) -> Dict[str, int]:
    """Weights that every token multiplies, per token, by layer kind: a
    Mamba layer's in- and out-projections, an attention layer's four
    projections, and each layer's gated FFN."""
    d, ff = m["d_model"], m["d_ff"]
    sd = ssm_dims(m)
    ffn = 3 * d * ff
    attn = 2 * d * m["n_heads"] * m["d_head"] \
        + 2 * d * m["n_kv_heads"] * m["d_head"]
    return {"mamba": d * sd["in"] + sd["d_inner"] * d + ffn,
            "attention": attn + ffn}


def param_bytes(m: dict) -> int:
    """Bytes of the stored weights: the tied table (padded vocabulary
    included: it is read), every layer's matrices, conv taps and bias and
    norm scales in ``param_dtype``, and ``A_log``, ``D``, ``dt_bias`` in
    float32."""
    vp = -(-m["vocab_size"] // m["vocab_pad_multiple"]) \
        * m["vocab_pad_multiple"]
    d, s = m["d_model"], m["ssm"]
    sd, n, mp = ssm_dims(m), layer_counts(m), matmul_params(m)
    pb = _bytes(m["param_dtype"])
    mamba = (mp["mamba"] + sd["conv"] * (s["conv_kernel"] + 1)
             + sd["d_inner"] + 2 * d) * pb + 3 * sd["heads"] * 4
    attn = (mp["attention"] + 2 * d) * pb
    return (vp * d + d) * pb + n["mamba"] * mamba + n["attention"] * attn


def state_bytes_per_slot(m: dict) -> int:
    """Recurrent state one slot holds: each Mamba layer's SSM state
    [heads, d_state, headdim] in float32 and its conv state [K - 1, conv
    channels] in ``dtype``."""
    s, sd = m["ssm"], ssm_dims(m)
    per = sd["heads"] * s["d_state"] * s["headdim"] * 4 \
        + (s["conv_kernel"] - 1) * sd["conv"] * _bytes(m["dtype"])
    return layer_counts(m)["mamba"] * per


def kv_bytes_per_token(m: dict) -> int:
    """Cache bytes one position holds: K and V of every attention layer."""
    return layer_counts(m)["attention"] * 2 * m["n_kv_heads"] \
        * m["d_head"] * _bytes(m["dtype"])


def ssd_flops(m: dict, n: int) -> int:
    """One Mamba layer's SSD over a prompt of ``n`` tokens, cut into chunks
    of the published length (the last one holding what is left): per
    chunk of ``q`` real positions, C B^T over its q(q+1)/2 causal pairs per
    group, the decay-weighted sum over them per head, the chunk's state
    (B x^T) and its read-out (C h) per head, and the state's pass to the
    next chunk."""
    s, sd = m["ssm"], ssm_dims(m)
    N, P, H, G = s["d_state"], s["headdim"], sd["heads"], s["n_groups"]
    total, left = 0, n
    while left > 0:
        q = min(s["chunk"], left)
        pairs = q * (q + 1) // 2
        total += 2 * pairs * N * G + 2 * pairs * P * H \
            + 2 * (2 * q * N * P * H) + 2 * H * N * P
        left -= q
    return total


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One prompt: every token through every weight and each Mamba layer's
    conv taps, each Mamba layer's SSD, causal attention in each attention
    layer (token i attends to i + 1 positions), and the head at the last
    token."""
    n = prompt_len
    cnt, mp, sd = layer_counts(m), matmul_params(m), ssm_dims(m)
    conv = 2 * m["ssm"]["conv_kernel"] * sd["conv"]
    pairs = n * (n + 1) // 2
    return 2 * n * (cnt["mamba"] * mp["mamba"]
                    + cnt["attention"] * mp["attention"]) \
        + cnt["mamba"] * (n * conv + ssd_flops(m, n)) \
        + cnt["attention"] * 4 * pairs * m["n_heads"] * m["d_head"] \
        + 2 * m["d_model"] * m["vocab_size"]


def decode_bytes(m: dict, contexts: Sequence[int]) -> int:
    """One decode step: every weight read once, and for each live slot its
    recurrent state read and written and its K/V read up to its context
    (``context`` positions, the new one written)."""
    return param_bytes(m) + sum(2 * state_bytes_per_slot(m)
                                + kv_bytes_per_token(m) * c
                                for c in contexts)
