"""Metric arithmetic of the benchmark: percentiles over every sample, TTFT
from the due time, gaps up to the close, rates over the window, and the
operation and byte counts of the decode step, the prefill and tdFIR."""
import json
import statistics
import types
from pathlib import Path

import pytest

from bench.harness import counts, stats, traffic
from bench.harness.core import BENCH, load_kind, load_module

serve_kind = load_kind("serve")

GRANITE = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
M = GRANITE["model"]


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def req(rid, due, tokens, prompt_len=10, out_len=None, ticks=None):
    r = serve_kind.Req(rid, due, prompt_len, out_len or len(tokens), None)
    r.token_s = list(tokens)
    r.token_tick = list(ticks if ticks is not None else range(len(tokens)))
    return r


def serve_run(reqs, seconds=10.0, **kw):
    cell = types.SimpleNamespace(seconds=seconds)
    kw.setdefault("traced", (0.0, seconds))
    kw.setdefault("tick_s", [0.1 * i for i in range(100)])
    return types.SimpleNamespace(requests=reqs, cell=cell, trace=None,
                                 config=GRANITE, **kw)


@pytest.mark.parametrize("values,p,want", [
    ([3.0, 1.0, 2.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([7.0], 95, 7.0),
])
def test_percentile_interpolates_over_every_sample(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_is_none_and_range_is_checked():
    assert stats.percentile([], 50) is None
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_spread_is_quartile_distance_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


def test_ttft_counts_from_the_due_time_over_every_request():
    reqs = [req("a", 1.0, [1.05, 1.1]), req("b", 2.0, [2.3]),
            req("c", 3.0, [3.2]), req("d", 4.0, [])]
    # TTFTs 50, 300, 200 ms; the request with no token is not a sample
    assert reader("ttft_p50_ms").read(serve_run(reqs)) == pytest.approx(200)


def test_gaps_pool_every_request_and_stop_at_the_close():
    reqs = [req("a", 0.0, [0.1, 0.2, 0.4, 11.0]),   # last gap after close
            req("b", 0.0, [1.0, 1.5])]
    m = reader("itl_p95_ms")
    assert sorted(m.gaps(serve_run(reqs))) == pytest.approx([0.1, 0.2, 0.5])
    assert m.read(serve_run(reqs)) == pytest.approx(
        1e3 * stats.percentile([0.1, 0.2, 0.5], 95))


def test_admit_wait_is_a_mean_from_due_to_admitting_tick():
    a, b = req("a", 1.0, [1.2]), req("b", 2.0, [2.5])
    a.admit_s, b.admit_s = 1.1, 2.3
    assert reader("admit_wait_ms").read(serve_run([a, b])) == \
        pytest.approx(200.0)


def test_plan_rates_cover_every_call_and_run_in_the_window():
    run = types.SimpleNamespace(
        plans=[{"s": 2.0, "candidates": 20}, {"s": 3.0, "candidates": 30}],
        app_blocks=[{"runs": 400, "s": 0.3}, {"runs": 100, "s": 0.2}])
    assert reader("plan_s").read(run) == pytest.approx(2.5)
    assert reader("app_ms").read(run) == pytest.approx(1.0)
    assert reader("candidates_per_plan").read(run) == pytest.approx(25)
    assert reader("measure_s_per_candidate").read(run) == pytest.approx(0.1)


def test_trace_metrics_are_silent_without_a_trace():
    run = serve_run([req("a", 0.0, [0.1, 0.2])], peaks=None)
    for name in ("prefill_ms_per_ktok", "prefill_mfu", "decode_step_ms",
                 "decode_hbm_share", "decode_mfu", "idle_share.serve"):
        assert reader(name).read(run) is None


def test_granite_counts_from_shapes():
    # 40 layers x (q, o: 2048x2048; k, v: 2048x512; gated FFN 3x2048x8192)
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert counts.lm_matmul_params(M) == 40 * per_layer
    # the stored weights the v5e rehearsal reads: 5,068,099,584 bytes
    assert counts.lm_param_bytes(M) == 5_068_099_584
    # 40 layers x K,V x 8 kv heads x 64 x 2 bytes
    assert counts.kv_bytes_per_token(M) == 81_920


def test_prefill_flops_count_causal_pairs_and_one_head():
    n = 512
    want = (2 * n * counts.lm_matmul_params(M)
            + 40 * 4 * (n * (n + 1) // 2) * 32 * 64
            + 2 * 2048 * 49155)
    assert counts.prefill_flops(M, n) == want


def test_decode_counts_only_live_slots():
    one = counts.decode_flops(M, [100])
    assert counts.decode_flops(M, [100, 100]) == 2 * one
    assert counts.decode_flops(M, []) == 0
    assert one == 2 * counts.lm_matmul_params(M) + 2 * 2048 * 49155 \
        + 40 * 4 * 100 * 32 * 64
    # weights once per step, cache per live position
    assert counts.decode_bytes(M, [100, 50]) == \
        counts.lm_param_bytes(M) + 81_920 * 150


def test_tdfir_counts():
    assert counts.tdfir_flops(64, 4096, 128) == 8 * 64 * 4096 * 128
    assert counts.tdfir_bytes(64, 4096, 128) == 4 * (
        2 * 64 * 4096 + 2 * 64 * 128 + 129 * 4096)


def test_slot_contents_from_token_stamps_inside_the_traced_slice():
    from bench.harness.live import decode_contexts, prefilled
    a = req("a", 0.0, [0.1, 0.2, 0.3], prompt_len=10, ticks=[0, 0, 1])
    b = req("b", 0.0, [0.2, 0.3], prompt_len=5, ticks=[1, 1])
    c = req("c", 0.0, [0.4, 0.5], prompt_len=7, ticks=[3, 3])
    a.admit_s, b.admit_s, c.admit_s = 0.0, 0.1, 0.3
    # ticks start at 0.0, 0.1, 0.2, 0.3: tick 0 decodes a (11); tick 1 a
    # (12) and b (6); tick 3 c (8)
    assert decode_contexts(serve_run([a, b, c])) == [[11], [12, 6], [8]]
    assert prefilled(serve_run([a, b, c])) == [10, 5, 7]
    # a slice from 0.05 s to 0.25 s holds tick 1 and b's admission only
    sliced = serve_run([a, b, c], traced=(0.05, 0.25))
    assert decode_contexts(sliced) == [[12, 6]]
    assert prefilled(sliced) == [5]


def test_schedule_keeps_the_work_and_shuffles_its_order():
    tr = json.loads((BENCH / "traffic" / "chat.json").read_text())
    a = traffic.schedule(tr, 2 ** 40 + 1, 30)
    b = traffic.schedule(tr, 7, 30)
    assert len(a) == len(b) == round(tr["rate_per_s"] * 30)
    assert sorted(d.prompt_len for d in a) == sorted(d.prompt_len for d in b)
    assert sorted(d.out_len for d in a) == sorted(d.out_len for d in b)
    assert [d.prompt_len for d in a] != [d.prompt_len for d in b]
    assert a[-1].due_s == pytest.approx(b[-1].due_s)
    assert all(d.prompt_len in tr["prompt"]["grid"] for d in a)
    assert all(d.prompt_len + d.out_len <= tr["cache_len"] for d in a)
    assert traffic.schedule(tr, 7, 30) == b


def test_schedule_refuses_traffic_that_overflows_the_cache():
    tr = json.loads((BENCH / "traffic" / "chat.json").read_text())
    tr = dict(tr, cache_len=64)
    with pytest.raises(ValueError):
        traffic.schedule(tr, 1, 30)


def test_every_traffic_file_fits_its_cache():
    for path in (BENCH / "traffic").glob("*.json"):
        tr = json.loads(Path(path).read_text())
        if tr["kind"] == "serve":
            assert max(tr["prompt"]["grid"]) + tr["output"]["max"] \
                <= tr["cache_len"], path.name
