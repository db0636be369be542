"""Model step: device milliseconds per execution of the vmapped decode
step over the slot pool (device trace).  Moves itl_p95_ms."""
from bench.harness import trace as tr

DECODE = "jit_pool_step"


def read(run):
    if run.trace is None:
        return None
    secs, execs = tr.module_seconds(run.trace, DECODE)
    if not execs or secs <= 0:
        return None
    return secs * 1e3 / execs
