"""Model step: device milliseconds of the prefill programs per thousand
prompt tokens prefilled in the traced window (device trace).  Moves
ttft_p50_ms."""
from bench.harness import trace as tr
from bench.harness.live import prefilled

PREFILL = "jit_pf"


def read(run):
    if run.trace is None:
        return None
    secs, execs = tr.module_seconds(run.trace, PREFILL)
    toks = sum(prefilled(run))
    if not execs or not toks or secs <= 0:
        return None
    return secs * 1e3 / (toks / 1e3)
