"""Planner -> TimedRunner: seconds of JAX compile stages (trace, lower,
backend compile or compile-cache load) inside ``measure`` spans, over the
number of ``measure`` spans (program spans and ``compile`` events; nested
stages counted once).  Moves plan_s."""
from bench.harness import spans


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    measures = spans.named(recs, "measure")
    if not measures:
        return None
    kids = spans.children(recs)
    stages = [(e["t"] - e["attrs"]["seconds"], e["t"])
              for m in measures for e in spans.descendants(kids, m)
              if e["type"] == "event" and e["name"] == "compile"]
    return spans.union_s(stages) / len(measures)
