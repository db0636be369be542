"""The plan kind of cell: the paper's planner, ``plan_offload``, over an application,
back to back, each plan followed by runs of the destination it selected.

Set-up makes the application's inputs on the device from the seed at the
configuration's sizes, and warms up by planning once (which compiles, or
fetches from the compile cache, every candidate the search measures) and
compiling the selected destination.  The window repeats: one
``plan_offload`` with the traffic file's GA settings, then the selected
destination run back to back, each run ending in ``block_until_ready``,
for ``app_block_s`` seconds.  A destination selected for the first time
inside the window is compiled there (and counted).

The check: the last output of each destination selected in the window
against a float64 FIR computed on the host with NumPy, the number compared
being the largest error over the largest reference magnitude, per part of
the output.
"""
from __future__ import annotations

import time

import numpy as np

HOST_SPANS = ("plan_offload", "app_runs")


def make_inputs(cfg: dict, seed: int):
    """Planar complex signal and taps, normal, taps scaled by 0.1."""
    import jax
    import jax.numpy as jnp
    from bench.harness.seeds import jax_key

    f, n, k = cfg["filters"], cfg["samples"], cfg["taps"]

    @jax.jit
    def draw(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {"x_re": jax.random.normal(k1, (f, n), jnp.float32),
                "x_im": jax.random.normal(k2, (f, n), jnp.float32),
                "h_re": jax.random.normal(k3, (f, k), jnp.float32) * 0.1,
                "h_im": jax.random.normal(k4, (f, k), jnp.float32) * 0.1}
    return draw(jax_key(seed))


def fir_reference(inputs) -> np.ndarray:
    """The application's output in float64: the causal complex FIR of each
    filter, scaled by one half, and a last row holding its energy."""
    x = (np.asarray(inputs["x_re"], np.float64)
         + 1j * np.asarray(inputs["x_im"], np.float64))
    h = (np.asarray(inputs["h_re"], np.float64)
         + 1j * np.asarray(inputs["h_im"], np.float64))
    n = x.shape[1]
    y = np.stack([np.convolve(xf, hf)[:n] for xf, hf in zip(x, h)]) * 0.5
    energy = np.sum(y.real ** 2 + y.imag ** 2)
    return np.concatenate([y.real, y.imag, np.full((1, n), energy)])


def rel_err(out, ref) -> float:
    """Largest error over the largest reference magnitude, taken per part
    of the output (real rows, imaginary rows, energy row), worst part."""
    out = np.asarray(out, np.float64)
    if out.shape != ref.shape or not np.isfinite(out).all():
        return float("inf")
    f = (ref.shape[0] - 1) // 2
    parts = (slice(0, f), slice(f, 2 * f), slice(2 * f, None))
    return max(float(np.max(np.abs(out[p] - ref[p])) / np.max(np.abs(ref[p])))
               for p in parts)


def fir_control(inputs):
    """The control: the application's output with each product's operands
    rounded to float8 e4m3 (per-tensor scale) and float32 accumulation, in
    the selected destination's place."""
    import jax
    import jax.numpy as jnp
    from bench.harness.fp8 import round_f8

    def fir(x, h):
        k = h.shape[1]
        xp = jnp.pad(round_f8(x), ((0, 0), (k - 1, 0)))[:, None, :]
        hf = round_f8(h)[:, None, ::-1]
        return jax.lax.conv_general_dilated(
            xp, hf, (1,), "VALID", feature_group_count=x.shape[0],
            dimension_numbers=("CNH", "OIH", "CNH"),
            precision=jax.lax.Precision.HIGHEST)[:, 0, :]

    @jax.jit
    def app(s):
        y_re = (fir(s["x_re"], s["h_re"]) - fir(s["x_im"], s["h_im"])) * 0.5
        y_im = (fir(s["x_re"], s["h_im"]) + fir(s["x_im"], s["h_re"])) * 0.5
        e = jnp.sum(y_re ** 2 + y_im ** 2)
        return jnp.concatenate([y_re, y_im, jnp.full((1, y_re.shape[1]), e)])
    return app(inputs)


def destination(app, choice):
    """The selected destination, compiled: the path the window times."""
    import jax
    return jax.jit(app.build(choice))


def run(cell, devs):
    import jax
    from repro.apps import APPS
    from repro.core.ga import GAConfig
    from repro.core.measure import TimedRunner
    from repro.core.planner import UserTarget, plan_offload
    from bench.harness.core import memory_peak

    cfg, tr, run_ = cell.config, cell.traffic, cell.run
    app = APPS[cfg["app"]]()
    inputs = make_inputs(cfg, cell.seed)
    ga_cfg = GAConfig.for_gene_length(
        min(app.gene_length, int(tr["ga_gene_length_cap"])),
        seed=int(tr["ga_seed"]))

    def plan():
        return plan_offload(app, UserTarget(), inputs=inputs,
                            runner=TimedRunner(repeats=int(
                                tr["timed_repeats"])),
                            ga_cfg=ga_cfg)

    fns = {}

    def selected_fn(report):
        key = tuple(sorted(report.selected.choice.items()))
        if key not in fns:
            fns[key] = destination(app, dict(report.selected.choice))
        return key, fns[key]

    key, fn = selected_fn(plan())
    jax.block_until_ready(fn(inputs))

    plans, blocks, last = [], [], {}
    with cell.window() as t0:
        while time.perf_counter() - t0 < cell.seconds:
            cell.poll()
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("plan_offload"):
                report = plan()
            b = time.perf_counter()
            plans.append({
                "s": b - a,
                "candidates": sum(r.n_measurements for r in report.records)})
            key, fn = selected_fn(report)
            n, a = 0, time.perf_counter()
            with jax.profiler.TraceAnnotation("app_runs"):
                while True:
                    out = fn(inputs)
                    out.block_until_ready()
                    n += 1
                    if time.perf_counter() - a >= float(tr["app_block_s"]):
                        break
            blocks.append({"runs": n, "s": time.perf_counter() - a})
            last[key] = out
    run_.memory_peak_bytes = memory_peak(devs)
    run_.plans, run_.app_blocks = plans, blocks
    run_.attempted = len(plans)
    run_.failed = 0

    ref = fir_reference(inputs)
    run_.check("rel_err", max(rel_err(o, ref) for o in last.values()),
               cfg["check"]["rel_err"])


def control(run):
    """The control's reading on the cell's inputs, checked at the
    configuration's limit in a run of its own (returned)."""
    inputs = make_inputs(run.config, run.cell.seed)
    out = run.fresh()
    out.check("rel_err", rel_err(fir_control(inputs), fir_reference(inputs)),
              run.config["check"]["rel_err"])
    return out
