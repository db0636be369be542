"""The whole decode step's share of the chip's bf16 peak: the operations
the live slots need (from shapes) over the decode programs' device time
(device trace).  Moves itl_p95_ms."""
from bench.harness import counts, trace as tr
from bench.harness.live import decode_contexts

DECODE = "jit_pool_step"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    secs, execs = tr.module_seconds(run.trace, DECODE)
    steps = decode_contexts(run)
    if not execs or secs <= 0 or not steps:
        return None
    m = run.config["model"]
    flops = sum(counts.decode_flops(m, c) for c in steps)
    return 100.0 * flops / secs / run.peaks["bf16_flops"]
