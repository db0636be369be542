"""Batcher: mean time from a request's due time to the start of the tick
that admitted it, over every admitted request (host clock).  Moves
ttft_p50_ms."""
from bench.harness.stats import mean


def read(run):
    v = mean([r.admit_s - r.due_s for r in run.requests
              if r.admit_s is not None])
    return None if v is None else v * 1e3
