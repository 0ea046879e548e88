"""Unified observability: metrics registry, Prometheus exposition, and
request tracing onto one Perfetto timeline (docs/observability.md).

Three pieces, one time axis:

* :mod:`horovod_tpu.obs.registry` — typed process-wide metrics
  (Counter/Gauge/Histogram with labels, duplicate-name detection,
  Prometheus text exposition).  Serving engines keep private
  registries; training/elastic/timeline metrics live in
  :func:`~horovod_tpu.obs.registry.default_registry`.
* :mod:`horovod_tpu.obs.tracing` — per-request trace ids
  (``X-Trace-Id``) propagated submit → prefill → decode → retirement,
  with a timing breakdown in every ``/generate`` response and a JSONL
  event log; request spans, tick-phase spans, and lifecycle instants
  (XLA compiles, engine restarts, watchdog stalls, elastic
  re-rendezvous) render through the existing
  :class:`horovod_tpu.timeline.Timeline` writer.
* :func:`training_step` — the training-side span: wraps one step,
  observing ``training_step_seconds`` and nesting a ``train_step``
  span into the same timeline the serving spans land on.

    from horovod_tpu import obs
    obs.tracing.start("/tmp/trace.json", jsonl_path="/tmp/trace.jsonl")
    for batch in data:
        with obs.training_step():
            params, opt_state, loss = step(params, opt_state, batch)
"""

from __future__ import annotations

import contextlib
import time

from jax.profiler import StepTraceAnnotation

from horovod_tpu.obs import (  # noqa: F401
    aggregate,
    fleet,
    registry,
    trace_store,
    tracing,
    xprof,
)
from horovod_tpu.obs.registry import (  # noqa: F401
    Counter,
    DuplicateMetricError,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    elastic_metrics,
    training_metrics,
)
from horovod_tpu.obs.trace_store import TraceStore  # noqa: F401
from horovod_tpu.obs.tracing import (  # noqa: F401
    PARENT_SPAN_HEADER,
    SAMPLED_HEADER,
    TRACE_ID_HEADER,
    RequestTrace,
    SpanRecorder,
    SpanSampling,
    Tracer,
    mint_span_id,
    mint_trace_id,
    record_compile,
)

__all__ = [
    "aggregate", "fleet", "registry", "trace_store", "tracing", "xprof",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DuplicateMetricError", "default_registry",
    "training_metrics", "elastic_metrics",
    "TRACE_ID_HEADER", "PARENT_SPAN_HEADER", "SAMPLED_HEADER",
    "RequestTrace", "Tracer", "SpanRecorder", "SpanSampling",
    "TraceStore", "mint_trace_id", "mint_span_id",
    "record_compile", "training_step",
]


@contextlib.contextmanager
def training_step(name: str = "train_step"):
    """Span one training step: observes ``training_step_seconds`` /
    ``training_steps_total`` / ``training_last_step_seconds`` in the
    default registry (the last-step gauge also rides the elastic
    heartbeat, feeding the driver's straggler detector), refreshes the
    live ``training_mfu`` gauge when
    :func:`horovod_tpu.obs.xprof.set_training_cost` armed it, and, when
    a timeline is recording, nests a ``train_step`` span onto the same
    time axis as the serving request spans.  The step is also a
    ``jax.profiler.StepTraceAnnotation("hvd:<name>")``: a profiler
    trace taken over the loop groups its device operations by step."""
    m = training_metrics()
    from horovod_tpu import timeline as TL

    tl = TL.get()
    t0 = time.monotonic()
    if tl is not None:
        tl.begin(name, "training")
    try:
        with StepTraceAnnotation(tracing.PHASE_PREFIX + name,
                                 step_num=int(m.steps.value)):
            yield
    finally:
        dt = time.monotonic() - t0
        if tl is not None:
            tl.end(name)
        m.step_time.observe(dt)
        m.steps.inc()
        m.last_step.set(dt)
        xprof.observe_step(dt, m.mfu)
