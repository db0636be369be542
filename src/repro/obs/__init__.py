"""repro.obs — unified tracing and post-mortem reporting.

One observability layer over plan -> publish -> serve -> control:

  * :class:`Tracer` / :class:`Span` — nested spans + instant events on
    caller-supplied clocks (wall for offline search, the virtual tick
    clock for serve/control via :meth:`Tracer.set_time`); the ambient
    tracer (:func:`get_tracer`) defaults to the no-op
    :data:`NULL_TRACER`, so instrumentation costs nothing when disabled;
  * :func:`profiler_tracer` — a recording tracer whose spans also sit on
    the ``jax.profiler`` trace's host plane, on the device ops' clock,
    plus ``compile`` events for each JAX compile stage
    (:mod:`repro.obs.profiler`);
  * exporters — byte-stable JSONL (:func:`write_jsonl`), Perfetto-loadable
    Chrome trace JSON (:func:`write_chrome_trace`), text summary
    (:func:`text_summary`); and ``python -m repro.obs.report`` rendering
    the post-mortem (see :mod:`repro.obs.report`).

Zero dependencies: importing this package never pulls jax or numpy
(:func:`profiler_tracer` imports jax when called).
"""
from repro.obs.export import (chrome_trace, jsonl_line, read_jsonl,
                              text_summary, write_chrome_trace, write_jsonl)
from repro.obs.profiler import profiler_tracer
from repro.obs.tracer import (NULL_SPAN, NULL_TRACER, NullTracer, Span,
                              Tracer, get_tracer, set_tracer, use_tracer)

__all__ = [
    "Tracer", "Span", "NullTracer", "NULL_TRACER", "NULL_SPAN",
    "get_tracer", "set_tracer", "use_tracer",
    "profiler_tracer",
    "write_jsonl", "read_jsonl", "jsonl_line",
    "chrome_trace", "write_chrome_trace", "text_summary",
]
