"""Loop-aware HLO analyzer: flops within tolerance of analytic counts."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo_analysis import analyze_hlo
from repro.core import cost_model


def test_scanned_matmul_flops_scaled_by_trip_count():
    L, B, D = 7, 64, 128

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h.sum()

    comp = jax.jit(jax.grad(f)).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((B, D), jnp.float32)).compile()
    res = analyze_hlo(comp.as_text())
    fwd = 2 * L * B * D * D
    # fwd + bwd(2x) = 3x fwd, within 40% (elementwise + loss noise)
    assert fwd * 2.0 < res["flops"] < fwd * 4.5, res["flops"]
    # XLA's own counter misses the loop factor
    xla = comp.cost_analysis()["flops"]
    assert res["flops"] > 2.5 * xla


def test_single_matmul_flops_exact():
    def f(a, b):
        return a @ b

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 32), jnp.float32)).compile()
    res = analyze_hlo(comp.as_text())
    assert res["flops"] == pytest.approx(2 * 64 * 128 * 32, rel=0.05)


def test_bytes_lower_bounded_by_io():
    def f(a, b):
        return a @ b

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 32), jnp.float32)).compile()
    res = analyze_hlo(comp.as_text())
    io_bytes = 4 * (64 * 128 + 128 * 32 + 64 * 32)
    assert res["bytes"] >= io_bytes * 0.9


def test_roofline_terms_and_dominance():
    rl = cost_model.roofline_terms(
        1e12, 1e9, 1e6, n_chips=256, model_flops=2e14)
    assert rl.compute_s == pytest.approx(1e12 / cost_model.PEAK_FLOPS)
    assert rl.memory_s == pytest.approx(1e9 / cost_model.HBM_BW)
    assert rl.collective_s == pytest.approx(1e6 / cost_model.ICI_BW)
    assert rl.dominant == "compute"
    assert rl.step_time_s == rl.compute_s
    assert 0 < rl.roofline_fraction <= 1.0


def test_model_flops_train_vs_decode():
    from repro.configs import ARCHS, SHAPES
    cfg = ARCHS["granite-3-2b"]
    t = cost_model.model_flops_for(cfg, SHAPES["train_4k"])
    d = cost_model.model_flops_for(cfg, SHAPES["decode_32k"])
    assert t == pytest.approx(6 * cfg.n_params() * 256 * 4096, rel=1e-6)
    assert d == pytest.approx(2 * cfg.n_params() * 128, rel=1e-6)


def test_moe_uses_active_params():
    from repro.configs import ARCHS, SHAPES
    cfg = ARCHS["arctic-480b"]
    assert cfg.active_params() < 0.2 * cfg.n_params()
    t = cost_model.model_flops_for(cfg, SHAPES["train_4k"])
    assert t == pytest.approx(6 * cfg.active_params() * 256 * 4096,
                              rel=1e-6)
