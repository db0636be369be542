"""Batcher: mean time from a request's ``submit`` event to the start of
its ``admit`` span, matched by ``rid``, over every request admitted in
the window (program spans).  Moves ttft_p50_ms."""
from bench.harness import spans
from bench.harness.stats import mean


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    submitted = {e["attrs"]["rid"]: e["t"]
                 for e in spans.named(recs, "submit", kind="event")}
    v = mean([a["t0"] - submitted[a["attrs"]["rid"]]
              for a in spans.named(recs, "admit")
              if a["attrs"].get("rid") in submitted])
    return None if v is None else v * 1e3
