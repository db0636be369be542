"""95th percentile over every gap between consecutive output tokens of
every request, up to the window's close (host clock)."""
from bench.harness.stats import percentile


def gaps(run):
    out = []
    for r in run.requests:
        ts = [t for t in r.token_s if t <= run.cell.seconds]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def read(run):
    v = percentile(gaps(run), 95)
    return None if v is None else v * 1e3
