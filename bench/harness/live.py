"""What the slot pool did inside the traced slice, from the host's token
stamps: a request's ``k``-th token (``k >= 1``) came from a decode step
that attended to ``prompt_len + k`` positions, and its first token from
the prefill in the tick that admitted it."""
from __future__ import annotations

from typing import Dict, List


def _traced(run, t: float) -> bool:
    return run.traced is not None and run.traced[0] <= t < run.traced[1]


def decode_contexts(run) -> List[List[int]]:
    """For each tick of the traced slice that ran a decode step, the
    contexts of its live slots."""
    per_tick: Dict[int, List[int]] = {}
    for r in run.requests:
        for k, tick in enumerate(r.token_tick):
            if k >= 1 and _traced(run, run.tick_s[tick]):
                per_tick.setdefault(tick, []).append(r.prompt_len + k)
    return [per_tick[t] for t in sorted(per_tick)]


def prefilled(run) -> List[int]:
    """Prompt lengths of the requests admitted in the traced slice."""
    return [r.prompt_len for r in run.requests
            if r.admit_s is not None and _traced(run, r.admit_s)]
