"""Model step: a Mamba-2 + GQA hybrid's prefill programs' share of the
chip's bf16 peak, the operations every prompt prefilled in the traced
window needs (projections, convs, the SSD at the published chunk and
causal attention; ``counts_hybrid``) over the prefill programs' device
time (device trace).  Moves ttft_p50_ms."""
from bench.harness import counts_hybrid, trace as tr
from bench.harness.live import prefilled

PREFILL = "jit_pf"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    secs, execs = tr.module_seconds(run.trace, PREFILL)
    if not execs or secs <= 0:
        return None
    m = run.config["model"]
    flops = sum(counts_hybrid.prefill_flops(m, n) for n in prefilled(run))
    if not flops:
        return None
    return 100.0 * flops / secs / run.peaks["bf16_flops"]
