"""Nested span tracer exporting Chrome trace-event JSON.

Host-side orchestration (engine phases, timeline rounds, aggregation,
checkpointing, co-sim coupling) is a tree of spans; this records them
as Chrome trace-event "X" (complete) events viewable in Perfetto /
``chrome://tracing``.  Disabled tracers are strict no-ops: ``span()``
yields immediately with no timestamping, so instrumented code paths
cost one attribute check when tracing is off.

Instrumented call sites go through :func:`maybe_span`, which also
writes every span into the JAX profiler's own trace, on the clock of
the device events, whether or not a collector is given.

Format (Chrome trace-event spec,
https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):
``{"traceEvents": [{"name", "ph": "X", "ts", "dur", "pid", "tid",
"cat", "args"}, ...], "displayTimeUnit": "ms"}`` with timestamps in
microseconds.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import List

__all__ = ["SpanTracer", "load_trace", "validate_trace", "maybe_span"]

_REQUIRED_KEYS = ("name", "ph", "ts", "dur", "pid", "tid")


class SpanTracer:
    """Collects nested spans; ``enabled=False`` is a strict no-op."""

    def __init__(self, enabled: bool = True,
                 clock=time.perf_counter):
        self.enabled = enabled
        self._clock = clock
        self._t0 = clock()
        self.events: List[dict] = []
        self._depth = 0
        self._pid = os.getpid()
        self._tid = threading.get_ident() & 0xFFFF

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, cat: str = "repro", **args):
        if not self.enabled:
            yield self
            return
        t_start = self._now_us()
        self._depth += 1
        try:
            yield self
        finally:
            self._depth -= 1
            self.events.append({
                "name": name,
                "ph": "X",
                "ts": t_start,
                "dur": self._now_us() - t_start,
                "pid": self._pid,
                "tid": self._tid,
                "cat": cat,
                "args": {k: _jsonable(v) for k, v in args.items()},
            })

    def to_chrome(self) -> dict:
        return {
            "traceEvents": sorted(self.events, key=lambda e: e["ts"]),
            "displayTimeUnit": "ms",
        }

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
            f.write("\n")


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except (TypeError, ValueError):
        return repr(v)


def load_trace(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def validate_trace(payload: dict) -> List[dict]:
    """Schema check; returns the events (raises on malformed input)."""
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("trace payload missing 'traceEvents' list")
    for e in events:
        missing = [k for k in _REQUIRED_KEYS if k not in e]
        if missing:
            raise ValueError(f"trace event {e.get('name')!r} missing "
                             f"required keys {missing}")
        if e["ph"] not in ("X", "i", "B", "E", "M"):
            raise ValueError(f"unknown trace phase {e['ph']!r}")
        if e["ph"] == "X" and e["dur"] < 0:
            raise ValueError(f"negative span duration in {e['name']!r}")
    return events


def maybe_span(collector, name: str, **args):
    """A host span named ``name`` at an instrumented call site.

    The span always enters ``jax.profiler.TraceAnnotation(name)``, which
    puts it in an active profiler trace beside the device events and is
    close to free when no profiler session is active.  With a
    ``collector`` it is also recorded, with ``args``, into
    ``collector.tracer``.  ``name`` is a stable ``<layer>.<step>``
    string; per-call values (a round index, a row count) go in ``args``
    and reach only the collector."""
    from jax.profiler import TraceAnnotation   # lazy: numpy-only users

    if collector is None:
        return TraceAnnotation(name)
    return _both(TraceAnnotation(name), collector.tracer.span(name, **args))


@contextmanager
def _both(outer, inner):
    with outer, inner:
        yield
