"""Reduction of a profiler trace to device busy time, per-op time and idle
gaps attributed to what the host was doing.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events: the device's operations (``XLA Ops`` line of the
``/device:TPU:0`` plane, each tagged with the program it ran in from the
``XLA Modules`` line) and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names on the host plane).  Everything
after that works on those events alone, so a recorded trace can be checked
in beside the tests (``bench/tests/data``).  All times are nanoseconds on
the profiler's one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: int
    dur_ns: int
    module: str = ""

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """A traced window: device ops, program executions and host spans."""
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    host: List[Event] = field(default_factory=list)
    window: Tuple[int, int] = (0, 0)

    def to_json(self) -> dict:
        def ev(e):
            return [e.name, e.start_ns, e.dur_ns, e.module]
        return {"window": list(self.window),
                "ops": [ev(e) for e in self.ops],
                "modules": [ev(e) for e in self.modules],
                "host": [ev(e) for e in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def evs(rows):
            return [Event(r[0], int(r[1]), int(r[2]), r[3]) for r in rows]
        return cls(ops=evs(d["ops"]), modules=evs(d["modules"]),
                   host=evs(d["host"]), window=tuple(d["window"]))


def short_name(name: str) -> str:
    """An op's instruction name: the TPU trace names an op by its whole
    HLO text (``%fusion.3 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _tag_modules(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Tag each op with the program execution that contains its start."""
    mods = sorted(modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        name = ""
        if i >= 0 and op.start_ns < mods[i].end_ns:
            name = mods[i].name
        out.append(Event(op.name, op.start_ns, op.dur_ns, name))
    return out


def load_xplane(trace_dir: str, host_names: Iterable[str],
                window_name: str = "window", device_index: int = 0
                ) -> Optional[Trace]:
    """Read the newest ``.xplane.pb`` under ``trace_dir``.

    Keeps host spans whose name is in ``host_names``; the span named
    ``window_name`` gives the traced window.  Returns None when the trace
    holds no device plane (no chip) or no window span.
    """
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        return None
    data = jax.profiler.ProfileData.from_file(paths[-1])
    keep = set(host_names) | {window_name}
    dev_name = f"{DEVICE_PLANE_PREFIX}TPU:{device_index}"
    ops: List[Event] = []
    modules: List[Event] = []
    host: List[Event] = []
    found_device = False
    for plane in data.planes:
        if plane.name == dev_name:
            found_device = True
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else modules
                for e in line.events:
                    dest.append(Event(short_name(e.name), int(e.start_ns),
                                      int(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in keep:
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.duration_ns)))
    windows = [h for h in host if h.name == window_name]
    if not found_device or not windows:
        return None
    w = max(windows, key=lambda h: h.dur_ns)
    host = [h for h in host if h.name != window_name]
    return clip(Trace(ops=_tag_modules(ops, modules), modules=modules,
                      host=host, window=(w.start_ns, w.end_ns)))


def clip(trace: Trace) -> Trace:
    """Keep only what lies inside the window, cut at its edges."""
    lo, hi = trace.window

    def cut(evs):
        out = []
        for e in evs:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                out.append(Event(e.name, s, t - s, e.module))
        return out
    return Trace(ops=cut(trace.ops), modules=cut(trace.modules),
                 host=cut(trace.host), window=trace.window)


def busy_intervals(ops: Sequence[Event]) -> List[Tuple[int, int]]:
    """Union of the ops' intervals, merged and sorted."""
    iv = sorted((e.start_ns, e.end_ns) for e in ops if e.dur_ns > 0)
    merged: List[List[int]] = []
    for s, t in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_s(trace: Trace) -> float:
    return sum(t - s for s, t in busy_intervals(trace.ops)) / 1e9


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window; None for an empty window or one with no op."""
    w = window_s(trace)
    if w <= 0 or not trace.ops:
        return None
    return 1.0 - busy_s(trace) / w


def self_ns(ops: Sequence[Event]) -> List[int]:
    """Each op's own time: its duration less that of the ops nested in it
    (a ``while`` op spans the ops of its body)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    own = [e.dur_ns for e in ops]
    stack: List[int] = []
    for i in order:
        e = ops[i]
        while stack and e.end_ns > ops[stack[-1]].end_ns:
            stack.pop()             # not inside the op on top: a sibling
        if stack:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return own


def op_seconds(trace: Trace) -> Dict[str, float]:
    """Device seconds per op name (own time, nesting removed), summed over
    the window."""
    out: Dict[str, float] = {}
    for e, own in zip(trace.ops, self_ns(trace.ops)):
        out[e.name] = out.get(e.name, 0.0) + own / 1e9
    return out


def module_seconds(trace: Trace, match: str) -> Tuple[float, int]:
    """Device seconds of the ops run by programs whose name contains
    ``match``, and how many executions of such programs the window holds.
    Busy time, not the programs' spans: an execution's span can include
    waits on the host."""
    execs = sum(1 for m in trace.modules if match in m.name)
    ops = [e for e in trace.ops if match in e.module]
    secs = sum(t - s for s, t in busy_intervals(ops)) / 1e9
    return secs, execs


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps in the window, each named by the
    innermost host span that covers its middle (``"none"`` if none)."""
    lo, hi = trace.window
    busy = busy_intervals(trace.ops)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        cover = [h for h in trace.host if h.start_ns <= mid < h.end_ns]
        name = min(cover, key=lambda h: h.dur_ns).name if cover else "none"
        out.append([name, (e - s) / 1e9])
    return out
