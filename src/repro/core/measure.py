"""Verification environment: dynamic measurement of candidate patterns.

Two runners (DESIGN.md §2 "verification environment"):

  * :class:`TimedRunner` — actually executes the candidate on this machine,
    times it (best-of-k after a compile warmup), and applies the paper's
    result-equality check: a result differing from the un-offloaded
    reference, or a timeout, sets processing time to 1000 s so the pattern
    dies out of the GA.

  * :class:`CompiledCostRunner` — lowers + compiles the candidate for a
    production mesh and scores it with the three-term roofline from the
    loop-aware HLO analysis.  Dynamic in the paper's sense (the measured
    object is the artifact the toolchain actually produced), used where the
    workload cannot run on the verification machine (pod-scale models).
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.ga import Evaluation
from repro.core import cost_model
from repro.core.search_cache import analyze_compiled
from repro.obs import get_tracer


def outputs_close(a, b, rtol=1e-2, atol=1e-2) -> bool:
    try:
        la = jax.tree.leaves(a)
        lb = jax.tree.leaves(b)
        if len(la) != len(lb):
            return False
        for x, y in zip(la, lb):
            x = np.asarray(x)
            y = np.asarray(y)
            if x.shape != y.shape:
                return False
            if x.dtype.kind in "biu" and y.dtype.kind in "biu":
                # integer/bool results compare exactly — a float64 round
                # trip is silently lossy above 2**53
                if not np.array_equal(x, y):
                    return False
                continue
            x = x.astype(np.float64)
            y = y.astype(np.float64)
            if not np.allclose(x, y, rtol=rtol, atol=atol, equal_nan=False):
                return False
            if not np.isfinite(x).all():
                return False
        return True
    except Exception:
        return False


class TimedRunner:
    def __init__(self, timeout_s: float = 180.0, rtol: float = 1e-2,
                 atol: float = 1e-2, repeats: int = 3):
        self.timeout_s = timeout_s
        self.rtol = rtol
        self.atol = atol
        self.repeats = repeats

    def measure(self, fn: Callable, inputs, reference_out) -> Evaluation:
        """Time fn(inputs) and check it against reference_out.

        ``reference_out=None`` means "this IS the reference run": the result
        is trivially correct and callers reuse ``info["output"]`` instead of
        executing the reference a second time (see planner.plan_offload).

        Spans (repro.obs): ``measure`` holds ``first_call`` (jit trace,
        lowering, compile or compile-cache load, and the first run),
        ``repeats`` (the timed runs) and ``compare`` (the result check).
        """
        tracer = get_tracer()
        with tracer.span("measure", cat="plan", track="planner",
                         reference=reference_out is None) as span:
            ev = self._measure(tracer, fn, inputs, reference_out)
            span.set(correct=ev.correct, timed_out=ev.timed_out)
        return ev

    def _measure(self, tracer, fn, inputs, reference_out) -> Evaluation:
        jfn = jax.jit(fn)
        try:
            with tracer.span("first_call", cat="plan", track="planner"):
                t0 = time.perf_counter()
                out = jax.block_until_ready(jfn(inputs))  # compile + run
                first = time.perf_counter() - t0
            if first > self.timeout_s:
                return Evaluation(time_s=first, correct=False,
                                  timed_out=True)
            times = []
            with tracer.span("repeats", cat="plan", track="planner",
                             n=self.repeats):
                for _ in range(self.repeats):
                    # every call gets the budget, not only the first: a
                    # candidate whose steady-state repeats hang must die
                    # through the paper's penalty path instead of running
                    # unbounded (per-call, so a legitimately
                    # slow-but-correct candidate under timeout_s per run
                    # is still measured)
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(jfn(inputs))
                    dt = time.perf_counter() - t0
                    if dt > self.timeout_s:
                        return Evaluation(time_s=dt, correct=False,
                                          timed_out=True)
                    times.append(dt)
            if reference_out is None:
                # reference run: keep the output for reuse; candidate runs
                # drop it (the GA cache would otherwise pin one output-sized
                # array per evaluated gene string)
                return Evaluation(time_s=min(times), correct=True,
                                  info={"first_call_s": first,
                                        "output": out})
            with tracer.span("compare", cat="plan", track="planner"):
                correct = outputs_close(out, reference_out, self.rtol,
                                        self.atol)
            return Evaluation(time_s=min(times), correct=correct,
                              info={"first_call_s": first})
        except Exception as e:   # compile error == paper's "conversion fails"
            return Evaluation(time_s=float("inf"), correct=False,
                              info={"error": repr(e)[:500]})


class CompiledCostRunner:
    def __init__(self, mesh=None, n_chips: Optional[int] = None,
                 model_flops: float = 0.0):
        self.mesh = mesh
        self.n_chips = n_chips or (mesh.size if mesh is not None else 1)
        self.model_flops = model_flops

    def score_analysis(self, analyzed: dict, verify_s: float = 0.0, *,
                       bubble_fraction: float = 0.0,
                       cache_hit: Optional[bool] = None) -> Evaluation:
        """Roofline-score an ``analyze_hlo`` result dict — pure arithmetic.

        This is the cache-hit scoring path (repro.core.search_cache): the
        analysis dict stands in for the compiled artifact, so re-scoring
        the same artifact under a different ``bubble_fraction`` or
        selection policy never touches HLO text.
        """
        try:
            rl = cost_model.roofline_from_analysis(
                analyzed, n_chips=self.n_chips,
                model_flops=self.model_flops,
                bubble_fraction=bubble_fraction)
            info = {"roofline": rl.to_dict(), "verify_s": verify_s}
            if cache_hit is not None:
                info["cache_hit"] = cache_hit
            return Evaluation(time_s=rl.step_time_s, correct=True,
                              info=info)
        except Exception as e:
            return Evaluation(time_s=float("inf"), correct=False,
                              info={"error": repr(e)[:500]})

    def score_compiled(self, compiled, verify_s: float = 0.0, *,
                       bubble_fraction: float = 0.0) -> Evaluation:
        """Roofline-score an already-compiled executable.

        Split from :meth:`measure_lowered` so callers that batch the XLA
        lowering/compilation across a GA population (examples/
        autoplan_model.py) can score the artifacts afterwards.
        ``bubble_fraction`` folds a pipeline schedule's idle fraction into
        the modeled step time (``cost_model.plan_bubble_fraction``), so the
        ``modeled`` policy ranks schedule genes correctly.  The HLO
        analysis is memoized per artifact (search_cache.analyze_compiled):
        scoring the same executable twice parses its text once.
        """
        try:
            analyzed = analyze_compiled(compiled)
        except Exception as e:
            return Evaluation(time_s=float("inf"), correct=False,
                              info={"error": repr(e)[:500]})
        return self.score_analysis(analyzed, verify_s,
                                   bubble_fraction=bubble_fraction)

    def measure_lowered(self, jitted, *args_sds,
                        bubble_fraction: float = 0.0) -> Evaluation:
        try:
            t0 = time.perf_counter()
            compiled = jitted.lower(*args_sds).compile()
            verify_s = time.perf_counter() - t0
        except Exception as e:
            return Evaluation(time_s=float("inf"), correct=False,
                              info={"error": repr(e)[:500]})
        return self.score_compiled(compiled, verify_s,
                                   bubble_fraction=bubble_fraction)

    def measure(self, fn: Callable, inputs_sds, in_shardings=None, *,
                bubble_fraction: float = 0.0) -> Evaluation:
        jitted = (jax.jit(fn, in_shardings=in_shardings)
                  if in_shardings is not None else jax.jit(fn))
        return self.measure_lowered(jitted, inputs_sds,
                                    bubble_fraction=bubble_fraction)
