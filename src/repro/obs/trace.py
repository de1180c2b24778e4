"""Structured span tracing for the serving drivers (host side).

Spans bracket the COARSE phases of a serve call -- pack, compile,
staging, the device workload -- not per-iteration events (those live in
the device rings, obs/rings.py; putting a host span around a loop
iteration would reintroduce exactly the per-iteration sync the
scheduler exists to avoid).

Every span enters the matching ``jax.profiler.TraceAnnotation`` scope,
so a profiler trace collected around a serve call shows the same phase
boundaries on its host plane, on the clock its device ops are put on.

A closed span is the record

    {"name": "serve.workload", "t0": ..., "dur_s": ..., "attrs": {...}}

with ``t0`` in seconds since the epoch, taken on entry to the
annotation from the wall clock the profiler stamps host events with
(an ``.xplane.pb`` gives host event starts relative to its Task
Environment's ``profile_start_time``, on that same clock).  A tracer
RECORDS spans -- keeps them in ``spans`` and appends them, one JSON line
each, to ``path`` -- only while recording is on: it was given a path,
or ``record=True``.  Off, a span costs the annotation and two clock
reads, and a long-running server keeps nothing.  ``collect()`` hands
the spans closed inside a block to its caller whether or not the tracer
records (the scheduler builds ``ServeReport.host_s`` from it).
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax


class SpanTracer:
    """Times spans; records them while a path or ``record`` is set."""

    def __init__(self, path: Optional[str] = None, record: bool = False):
        self.path = path
        self.record = record
        self.spans: List[Dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    @property
    def recording(self) -> bool:
        return self.record or bool(self.path)

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Dict]:
        """Time a ``with`` block as span ``name``.  The yielded dict is
        the span's attrs -- callers may add results discovered inside
        the block (e.g. token counts) before it closes."""
        a = dict(attrs)
        with jax.profiler.TraceAnnotation(name):
            t0 = time.time()
            yield a
            dur = time.time() - t0
        rec = dict(name=name, t0=round(t0, 6), dur_s=round(dur, 6), attrs=a)
        for got in getattr(self._local, "collectors", ()):
            got.append(rec)
        if not self.recording:
            return
        with self._lock:
            self.spans.append(rec)
            if self.path:
                with open(self.path, "a") as f:
                    f.write(json.dumps(rec, sort_keys=True) + "\n")

    @contextlib.contextmanager
    def collect(self) -> Iterator[List[Dict]]:
        """Yield a list that receives every span this thread closes
        inside the block, recording on or off."""
        got: List[Dict] = []
        outer = getattr(self._local, "collectors", ())
        self._local.collectors = outer + (got,)
        try:
            yield got
        finally:
            self._local.collectors = outer

    def drain(self) -> List[Dict]:
        """Return and clear the recorded spans."""
        with self._lock:
            out, self.spans = self.spans, []
        return out


_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    return _TRACER


def set_trace_path(path: Optional[str]) -> None:
    """Point the process-global tracer's JSONL sink at ``path``."""
    _TRACER.path = path


def span(name: str, **attrs):
    """``with span("serve.pack"): ...`` against the global tracer."""
    return _TRACER.span(name, **attrs)
