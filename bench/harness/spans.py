"""The program's own spans: what the metric readers of ``program_span``
metrics read.

A run made with the program's profiler-clock tracer installed
(``repro.obs.profiler_tracer``) carries the measured window's records as
``run.obs_records``: dicts, each a span (``id``, ``parent``, ``name``,
``t0``, ``t1``, ``attrs``) or an event (``parent``, ``name``, ``t``,
``attrs``), on the host's ``perf_counter`` clock, in seconds.  A run
without them has no such field, or ``None``, and its readers return
``None``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def records(run) -> Optional[List[dict]]:
    """The run's program records, or None when it recorded none."""
    return getattr(run, "obs_records", None) or None


def named(recs: Iterable[dict], name: str, kind: str = "span") -> List[dict]:
    return [r for r in recs if r["type"] == kind and r["name"] == name]


def children(recs: Iterable[dict]) -> Dict[int, List[dict]]:
    """Each span's id mapped to the records whose parent it is."""
    out: Dict[int, List[dict]] = {}
    for r in recs:
        if r.get("parent") is not None:
            out.setdefault(r["parent"], []).append(r)
    return out


def descendants(kids: Dict[int, List[dict]], root: dict) -> List[dict]:
    """Every record below ``root`` in the span tree."""
    out, todo = [], list(kids.get(root["id"], ()))
    while todo:
        r = todo.pop()
        out.append(r)
        if r["type"] == "span":
            todo.extend(kids.get(r["id"], ()))
    return out


def duration(span: dict) -> float:
    return span["t1"] - span["t0"]


def union_s(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, end = 0.0, float("-inf")
    for s, t in sorted(intervals):
        if t > end:
            total += t - max(s, end)
            end = t
    return total
