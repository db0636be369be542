"""Roofline of a Mamba-2 + GQA hybrid's decode step: the bytes each step
needs (every weight once, each live slot's recurrent state read and
written, and its K/V up to its context in the attention layers;
``counts_hybrid``) over the decode programs' device time, as a share of
the chip's HBM bandwidth (device trace).  Moves itl_p95_ms."""
from bench.harness import counts_hybrid, trace as tr
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
    nbytes = sum(counts_hybrid.decode_bytes(m, c) for c in steps)
    return 100.0 * nbytes / secs / run.peaks["hbm_bytes_per_s"]
