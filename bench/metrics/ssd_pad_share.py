"""Model step: positions the SSD pads in the last chunk of a prompt, over
the prompt tokens, for every prefill of the measured window (program
spans: ``ssd_pad`` of each ``prefill`` span over the ``prompt_len`` of the
``admit`` span around it).  Moves ttft_p50_ms."""
from bench.harness import spans


def read(run):
    recs = spans.records(run)
    if recs is None:
        return None
    admits = {r["id"]: r for r in spans.named(recs, "admit")}
    pad = toks = 0
    for r in spans.named(recs, "prefill"):
        parent = admits.get(r["parent"])
        if "ssd_pad" not in r["attrs"] or parent is None:
            continue
        pad += r["attrs"]["ssd_pad"]
        toks += parent["attrs"]["prompt_len"]
    return 100.0 * pad / toks if toks else None
