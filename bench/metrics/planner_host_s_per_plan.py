"""Planner: the planner's own host time per plan, mean over ``offload``
spans of the span's duration less the union of the ``measure`` spans
inside it: GA, lint, cache and selection work (program spans).  Moves
plan_s."""
from bench.harness import spans
from bench.harness.stats import mean


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    kids = spans.children(recs)
    return mean([spans.duration(o) - spans.union_s(
        [(d["t0"], d["t1"]) for d in spans.descendants(kids, o)
         if d["type"] == "span" and d["name"] == "measure"])
        for o in spans.named(recs, "offload")])
