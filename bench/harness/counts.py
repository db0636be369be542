"""Operations and bytes that the work needs, from its shapes alone.

These count what the algorithm requires, not what an implementation
happens to execute: a slot that decodes nothing, a masked attention score
or a padded vocabulary row adds nothing here.  A multiply and an add count
as two operations.
"""
from __future__ import annotations

from typing import Sequence


def _bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[dtype]


def lm_matmul_params(m: dict) -> int:
    """Weights that every token multiplies, per token, in a dense GQA
    decoder with a gated FFN and a tied output head over the real
    vocabulary."""
    d, h, kv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_head"], m["d_ff"])
    gated = m["ffn_act"] in ("swiglu", "geglu")
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d \
        + d * ff * (3 if gated else 2)
    return m["n_layers"] * per_layer


def lm_param_bytes(m: dict) -> int:
    """Bytes of the stored weights (padded vocabulary included: it is read)."""
    vp = -(-m["vocab_size"] // m["vocab_pad_multiple"]) \
        * m["vocab_pad_multiple"]
    d, ff = m["d_model"], m["d_ff"]
    gated = m["ffn_act"] in ("swiglu", "geglu")
    per_layer = (d * m["n_heads"] * m["d_head"]
                 + 2 * d * m["n_kv_heads"] * m["d_head"]
                 + m["n_heads"] * m["d_head"] * d
                 + d * ff * (3 if gated else 2) + 2 * d)
    return (vp * d + d + m["n_layers"] * per_layer) \
        * _bytes(m["param_dtype"])


def kv_bytes_per_token(m: dict) -> int:
    """Cache bytes one position holds: K and V of every layer."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["d_head"] \
        * _bytes(m["dtype"])


def attention_flops(m: dict, queries: int, keys: int) -> int:
    """Scores and the weighted sum for ``queries`` x ``keys`` pairs, over
    every layer and query head."""
    return m["n_layers"] * 4 * queries * keys * m["n_heads"] * m["d_head"]


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One prompt: every token through every weight, causal attention
    (token i attends to i + 1 positions), and the head at the last token."""
    n = prompt_len
    pairs = n * (n + 1) // 2
    return 2 * n * lm_matmul_params(m) \
        + m["n_layers"] * 4 * pairs * m["n_heads"] * m["d_head"] \
        + 2 * m["d_model"] * m["vocab_size"]


def decode_flops(m: dict, contexts: Sequence[int]) -> int:
    """One decode step of the live slots: each slot's new token through
    every weight and the head, attending to its ``context`` positions."""
    per_token = 2 * lm_matmul_params(m) + 2 * m["d_model"] * m["vocab_size"]
    return sum(per_token + attention_flops(m, 1, c) for c in contexts)


def decode_bytes(m: dict, contexts: Sequence[int]) -> int:
    """One decode step: every weight read once, and each live slot's
    cache read up to its context."""
    return lm_param_bytes(m) + kv_bytes_per_token(m) * sum(contexts)


def tdfir_flops(filters: int, samples: int, taps: int) -> int:
    """Complex FIR bank: per output sample and tap one complex multiply-add
    (four real multiplies and four real adds)."""
    return 8 * filters * samples * taps


def tdfir_bytes(filters: int, samples: int, taps: int,
                itemsize: int = 4) -> int:
    """Planar re/im input and taps read once, output written once (plus
    the verification row of the app's output)."""
    return itemsize * (2 * filters * samples + 2 * filters * taps
                       + (2 * filters + 1) * samples)
