"""The profiler's clock: a recording tracer whose spans also land on the
``jax.profiler`` trace, and JAX's compile stages as ``compile`` events.

:func:`profiler_tracer` returns a :class:`~repro.obs.Tracer` built with
``annotate=jax.profiler.TraceAnnotation``: while a profiler trace is
running, each span is also a host-plane event, on the clock the device's
ops are stamped with, so a gap on the device is named by the innermost
program span that covers it.  The first call also registers, once per
process, a ``jax.monitoring`` listener that records one ``compile`` event
on the ambient tracer (:func:`~repro.obs.get_tracer`) for each compile
stage JAX reports, with its ``stage`` and ``seconds``; the event's
``parent`` is the span open when the stage ended.  Stages:

  * ``trace`` — Python function to jaxpr;
  * ``lower`` — jaxpr to an MLIR module;
  * ``compile`` — the backend compile, or the fetch from the persistent
    compile cache that replaces it (``cache_load`` lies inside it);
  * ``cache_load`` — the persistent-cache fetch alone.

A stage can nest in another (a jitted callee traced inside its caller's
trace), so a reader that sums seconds takes the union of the events'
intervals ``[t - seconds, t]``.

jax is imported inside the functions: importing :mod:`repro.obs` stays
jax-free.
"""
from __future__ import annotations

from repro.obs.tracer import Tracer, get_tracer

COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_listening = False


def _on_duration(event: str, seconds: float, **_kw):
    stage = COMPILE_STAGES.get(event)
    if stage is not None:
        get_tracer().event("compile", cat="jax", track="jax", stage=stage,
                           seconds=float(seconds))


def profiler_tracer() -> Tracer:
    """A recording tracer on the profiler's clock (see module docstring)."""
    global _listening
    import jax.monitoring
    import jax.profiler
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return Tracer(annotate=jax.profiler.TraceAnnotation)
