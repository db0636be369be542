"""Median, over every request due in the window, of the time from when the
request was due (open loop) to its first token on the host (host clock).
A request whose first token never came is counted as failed, not here."""
from bench.harness.stats import percentile


def read(run):
    ttft = [r.ttft_s for r in run.requests if r.ttft_s is not None]
    v = percentile(ttft, 50)
    return None if v is None else v * 1e3
