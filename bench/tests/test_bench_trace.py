"""Trace reduction: busy and idle share, per-op and per-program device time,
and idle gaps named by the host span that covers them — on a hand-built
trace, and on a small trace recorded on a TPU v5e (``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

from bench.harness import trace as tr
from bench.harness.trace import Event, Trace

DATA = Path(__file__).parent / "data"


def hand_trace():
    # window 0..100 ns; ops 10..30 and 25..40 overlap, 60..70 alone
    ops = [Event("fusion.1", 10, 20, "jit_pool_step"),
           Event("copy.2", 25, 15, "jit_pool_step"),
           Event("fusion.1", 60, 10, "jit_pf")]
    modules = [Event("jit_pool_step", 5, 40), Event("jit_pf", 55, 20)]
    host = [Event("tick", 0, 50), Event("idle_wait", 45, 55)]
    return Trace(ops=ops, modules=modules, host=host, window=(0, 100))


def test_busy_is_the_union_of_op_intervals():
    t = hand_trace()
    assert tr.busy_intervals(t.ops) == [(10, 40), (60, 70)]
    assert tr.busy_s(t) == pytest.approx(40e-9)
    assert tr.window_s(t) == pytest.approx(100e-9)
    assert tr.idle_share(t) == pytest.approx(0.6)


def test_per_op_and_per_program_time():
    t = hand_trace()
    assert tr.op_seconds(t) == pytest.approx(
        {"fusion.1": 30e-9, "copy.2": 15e-9})
    # a loop op spanning its body keeps only its own time
    loop = Trace(ops=[Event("while.1", 0, 100), Event("fusion.2", 10, 30),
                      Event("copy.3", 50, 20), Event("fusion.4", 200, 5)],
                 window=(0, 300))
    assert tr.op_seconds(loop) == pytest.approx(
        {"while.1": 50e-9, "fusion.2": 30e-9, "copy.3": 20e-9,
         "fusion.4": 5e-9})
    assert tr.short_name("%fusion.3 = bf16[2]{0} fusion(%p)") == "fusion.3"
    secs, execs = tr.module_seconds(t, "jit_pool_step")
    assert (secs, execs) == (pytest.approx(30e-9), 1)
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                          ["c", 2.0]]


def test_idle_gaps_are_named_by_the_innermost_covering_host_span():
    gaps = tr.idle_gaps(hand_trace())
    # gaps: 0..10 (tick), 40..60 (mid 50: tick and idle_wait; the shorter
    # idle_wait is innermost), 70..100 (idle_wait)
    assert gaps == [["idle_wait", pytest.approx(30e-9)],
                    ["idle_wait", pytest.approx(20e-9)],
                    ["tick", pytest.approx(10e-9)]]


def test_clip_cuts_events_at_the_window():
    t = Trace(ops=[Event("a", -5, 10), Event("b", 95, 10),
                   Event("c", 200, 5)],
              window=(0, 100))
    c = tr.clip(t)
    assert [(e.name, e.start_ns, e.dur_ns) for e in c.ops] == \
        [("a", 0, 5), ("b", 95, 5)]


def test_ops_take_the_program_that_contains_them():
    ops = tr._tag_modules([Event("x", 12, 1), Event("y", 50, 1)],
                          [Event("jit_a", 10, 5)])
    assert [o.module for o in ops] == ["jit_a", ""]


def test_an_empty_window_has_no_idle_share():
    assert tr.idle_share(Trace(window=(0, 100))) is None


def test_json_round_trip():
    t = hand_trace()
    assert Trace.from_json(json.loads(json.dumps(t.to_json()))) == t


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob(
    "*.trace.json.gz")))
def test_recorded_v5e_trace(name):
    """83 ms of the chat cell's traced slice, recorded on a TPU v5e (a
    prefill, the slot insert and a decode step): ops nest properly on the
    device's line (own times add up to the busy time), busy never exceeds
    the window, the programs are found by name, and every idle gap is
    named by a host span of the benchmark."""
    with gzip.open(DATA / name, "rt") as f:
        t = Trace.from_json(json.load(f))
    assert t.ops and t.modules and t.host
    assert 0.0 < tr.busy_s(t) <= tr.window_s(t)
    assert 0.0 <= tr.idle_share(t) < 1.0
    assert sum(tr.op_seconds(t).values()) == pytest.approx(tr.busy_s(t),
                                                           rel=1e-6)
    assert all(o.module for o in t.ops)
    if name.startswith("chat"):
        secs, execs = tr.module_seconds(t, "jit_pool_step")
        assert execs >= 1 and 0.010 < secs / execs < 0.200
        assert tr.module_seconds(t, "jit_pf")[1] >= 1
    gaps = tr.idle_gaps(t)
    assert gaps and {g[0] for g in gaps} <= {"tick", "idle_wait",
                                             "plan_offload", "app_runs",
                                             "none"}
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
