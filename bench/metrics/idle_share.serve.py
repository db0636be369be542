"""Device: share of the traced window in which no operation ran on the
chip, 1 - busy / window (device trace).  Moves itl_p95_ms."""
from bench.harness import trace as tr


def read(run):
    if run.trace is None:
        return None
    v = tr.idle_share(run.trace)
    return None if v is None else 100.0 * v
