"""Selected destination: the least time the chip could take for tdFIR's
work, the larger of its operations over the bf16 peak (the table has no
float32 peak, so this is the higher peak and the share the lower bound)
and its bytes over HBM bandwidth, both from the application's shapes,
over ``app_ms``.  Moves app_ms."""
from bench.harness import counts


def read(run):
    if not run.peaks:
        return None
    runs = sum(b["runs"] for b in run.app_blocks)
    if not runs:
        return None
    per_run = sum(b["s"] for b in run.app_blocks) / runs
    c = run.config
    f, n, k = c["filters"], c["samples"], c["taps"]
    least = max(counts.tdfir_flops(f, n, k) / run.peaks["bf16_flops"],
                counts.tdfir_bytes(f, n, k) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_run
