"""Batcher: the host's own time per engine tick, mean over the window's
``engine_tick`` spans of the tick's duration less its ``first_token_wait``
and ``token_wait`` spans, the waits on the device (program spans).  Moves
itl_p95_ms."""
from bench.harness import spans
from bench.harness.stats import mean

WAITS = ("first_token_wait", "token_wait")


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    kids = spans.children(recs)
    v = mean([spans.duration(t) - sum(
        spans.duration(d) for d in spans.descendants(kids, t)
        if d["type"] == "span" and d["name"] in WAITS)
        for t in spans.named(recs, "engine_tick")])
    return None if v is None else v * 1e3
