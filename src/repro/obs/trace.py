"""Span tracer with a schema-versioned JSONL sink.

Every record is one JSON object per line.  The first line of a trace
file is a ``header`` record pinning the schema version and the run
metadata (arch, engine, plan digest — whatever :func:`repro.obs.configure`
was given); every subsequent line is one of

  ``span``        a named unit of work at a tick (fp_row / bp_row /
                  train_step ...), with free-form attrs
  ``event``       a point occurrence (offload / prefetch / admit /
                  preempt / page_grow ...), same shape as a span
  ``plan_audit``  a measured-vs-estimated peak-bytes record (see
                  :mod:`repro.obs.audit`)

"Tick" is whatever index the emitting layer is denominated in — the row
index inside the row-program executor, the scheduler tick in serve, the
optimiser step in train.  A span opened by :func:`repro.obs.scope` also
carries ``t_ns`` (its start on ``time.perf_counter_ns()``) and ``dur_ns``.
The profiler's host events share that clock up to one offset, which the
``obs_anchor`` span that :func:`repro.obs.cli.profiled` writes into both
traces gives.  Spans recorded by :meth:`Tracer.span` and events carry no
time, so those parts of two runs of one config still diff cleanly.

The in-memory ``records`` list is always kept (tests and
``ServeReport.timeline()`` read it), in the order spans were opened; the
JSONL file is written only when a path is given, and a record reaches it
once every span open at its creation has closed.
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Optional

#: version of the trace-record layout (bump on breaking change)
TRACE_SCHEMA = 1


class Tracer:
    """Structured-record sink: in-memory list + optional JSONL file."""

    def __init__(self, path: Optional[str] = None, meta: Optional[dict] = None):
        self.path = path
        self.records: List[dict] = []
        self._n_open = 0
        self._n_written = 0
        if path and os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        self._fh = open(path, "w") if path else None
        header = {"schema": TRACE_SCHEMA, "kind": "header",
                  **(meta or {})}
        self._write(header)

    def _write(self, rec: dict) -> None:
        self.records.append(rec)
        self._drain()

    def _drain(self) -> None:
        """Write every record whose spans have all closed."""
        if self._fh is None or self._n_open:
            return
        for rec in self.records[self._n_written:]:
            self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        self._n_written = len(self.records)

    @staticmethod
    def _record(kind: str, name: str, tick, attrs: dict) -> dict:
        rec = {"kind": kind, "name": name}
        if tick is not None:
            # row/step ticks are ints; scheduler ticks may be fractional
            # (poisson arrivals) — keep whichever the layer is denominated in
            t = float(tick)
            rec["tick"] = int(t) if t.is_integer() else t
        if attrs:
            rec["attrs"] = attrs
        return rec

    def emit(self, kind: str, name: str, tick=None, **attrs) -> None:
        self._write(self._record(kind, name, tick, attrs))

    def span(self, name: str, tick=None, **attrs) -> None:
        self.emit("span", name, tick, **attrs)

    def event(self, name: str, tick=None, **attrs) -> None:
        self.emit("event", name, tick, **attrs)

    def open_span(self, name: str, tick=None, **attrs) -> dict:
        """Record a span starting now; :meth:`close_span` gives it its
        duration."""
        rec = self._record("span", name, tick, attrs)
        rec["t_ns"] = time.perf_counter_ns()
        self._n_open += 1
        self._write(rec)
        return rec

    def close_span(self, rec: dict) -> None:
        rec["dur_ns"] = time.perf_counter_ns() - rec["t_ns"]
        self._n_open -= 1
        self._drain()

    def close(self) -> None:
        if self._fh is not None:
            self._n_open = 0
            self._drain()
            self._fh.close()
            self._fh = None


def read_jsonl(path: str) -> List[dict]:
    """Read a trace file back, validating the header's schema version."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    if not records or records[0].get("kind") != "header":
        raise ValueError(f"{path!r} is not a trace file (no header record)")
    schema = records[0].get("schema")
    if schema != TRACE_SCHEMA:
        raise ValueError(f"trace {path!r} has schema {schema!r}; this "
                         f"reader understands {TRACE_SCHEMA}")
    return records
