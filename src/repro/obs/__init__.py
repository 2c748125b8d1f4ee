"""repro.obs — zero-overhead-when-disabled telemetry.

One module-level session gates everything:

    from repro import obs

    obs.configure(trace="run.jsonl", metrics="metrics.json",
                  meta={"arch": "vgg16", "engine": "twophase"})
    ...
    obs.shutdown()          # writes the metrics dump, closes the trace

Instrumentation sites call :func:`scope` / :func:`emit` /
:func:`counter` / :func:`gauge` / :func:`histogram` unconditionally.

:func:`scope` is the one span primitive.  It names a block of work in
every trace that can see it, by one name (the span's name with its tick
appended, ``fp_row3``):

* inside jit tracing, ``jax.named_scope`` tags every op traced in the
  block, so the compiled step's HLO metadata (``op_name``) and hence the
  device ops of a profiler trace say which segment, row and backward
  phase they belong to — metadata only, the compiled ops are the same;
* ``jax.profiler.TraceAnnotation`` puts a host span into any running
  profiler trace (trace time inside jit, run time in the launch loops);
* with a session open, a ``span`` record with its start ``t_ns`` and
  ``dur_ns`` on ``time.perf_counter_ns()`` goes to the tracer.

When no session is active, ``emit`` returns immediately, ``scope``
records nothing, and the metric constructors hand back the shared
:data:`~repro.obs.metrics.NULL_METRIC` no-op.  The row executors' hooks
fire at trace time (jit caches the trace), so their records describe the
traced program once, not every step.

Registration is one call per layer (see ROADMAP "Observability"):
the row-program executor, the serve scheduler and the launch CLIs all
emit into whatever session is active; no plumbing of sink objects
through call stacks.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from repro.obs.metrics import (METRICS_SCHEMA, Counter, Gauge, Histogram,
                               MetricsRegistry, NULL_METRIC)
from repro.obs.trace import TRACE_SCHEMA, Tracer, read_jsonl

__all__ = [
    "configure", "shutdown", "enabled", "session", "capture",
    "scope", "emit", "span", "event", "counter", "gauge", "histogram",
    "Tracer", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "NULL_METRIC", "read_jsonl",
    "TRACE_SCHEMA", "METRICS_SCHEMA",
]


class Session:
    """An active obs session: a tracer plus a metrics registry."""

    def __init__(self, trace: Optional[str] = None,
                 metrics: Optional[str] = None,
                 meta: Optional[dict] = None):
        self.tracer = Tracer(trace, meta=meta)
        self.metrics = MetricsRegistry()
        self.metrics_path = metrics

    def close(self) -> None:
        if self.metrics_path:
            self.metrics.dump(self.metrics_path)
        self.tracer.close()


#: the one active session, or None (disabled mode)
_session: Optional[Session] = None


def configure(trace: Optional[str] = None, metrics: Optional[str] = None,
              meta: Optional[dict] = None) -> Session:
    """Open a session.  Replaces (and closes) any active one."""
    global _session
    if _session is not None:
        _session.close()
    _session = Session(trace=trace, metrics=metrics, meta=meta)
    return _session


def shutdown() -> None:
    """Close the active session, writing the metrics dump if configured."""
    global _session
    if _session is not None:
        _session.close()
        _session = None


def enabled() -> bool:
    return _session is not None


def session() -> Optional[Session]:
    return _session


@contextlib.contextmanager
def capture(trace: Optional[str] = None, metrics: Optional[str] = None,
            meta: Optional[dict] = None):
    """Scoped session for tests and library callers: restores whatever
    session (or none) was active before."""
    global _session
    prev = _session
    _session = Session(trace=trace, metrics=metrics, meta=meta)
    try:
        yield _session
    finally:
        _session.close()
        _session = prev


# -- emission (the hot path: one global load + one None check) ----------

def emit(kind: str, name: str, tick=None, **attrs) -> None:
    s = _session
    if s is not None:
        s.tracer.emit(kind, name, tick, **attrs)


@contextlib.contextmanager
def scope(name: str, tick=None, **attrs):
    """Name the enclosed work ``name`` (``f"{name}{tick}"`` with a tick)
    in the compiled HLO's op metadata and in any running profiler trace,
    and, with a session open, record it as a timed ``span``."""
    label = name if tick is None else f"{name}{tick}"
    with jax.named_scope(label), jax.profiler.TraceAnnotation(label):
        s = _session
        if s is None:
            yield
            return
        rec = s.tracer.open_span(name, tick, **attrs)
        try:
            yield
        finally:
            s.tracer.close_span(rec)


def span(name: str, tick=None, **attrs) -> None:
    emit("span", name, tick, **attrs)


def event(name: str, tick=None, **attrs) -> None:
    emit("event", name, tick, **attrs)


def counter(name: str):
    s = _session
    return NULL_METRIC if s is None else s.metrics.counter(name)


def gauge(name: str):
    s = _session
    return NULL_METRIC if s is None else s.metrics.gauge(name)


def histogram(name: str):
    s = _session
    return NULL_METRIC if s is None else s.metrics.histogram(name)
