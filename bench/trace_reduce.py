"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What it reads:

* device ops: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane.
  An event's name is the HLO instruction's text; ``op_name`` keeps the
  instruction name without its ``%`` and ``.<n>`` suffix, which for a
  Pallas kernel is the name of its ``pallas_call``.
* host spans: events named ``bench.*`` on the ``/host:CPU`` plane, which
  the harness writes with ``jax.profiler.TraceAnnotation``.

The device clock in the trace runs a little ahead of the host's.  Each
``XLA Modules`` event carries a ``run_id``, as does the host's
``DoEnqueueProgram`` event that launched it; a program cannot start
before it is enqueued, so the device events are shifted by the median
lag of start behind enqueue where that lag is negative.

What it computes, within a window [t0, t1] on the host clock:

* busy seconds: the length of the union of the device op intervals,
  averaged over the device planes;
* idle gaps: the stretches of the window no device op covers, each put
  down to the innermost ``bench.*`` span around its midpoint;
* device seconds of the ops whose name matches any of a list of names.
"""
from __future__ import annotations

import dataclasses
import re
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_NAME = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?(?: =|$)")


def op_name(text: str) -> str:
    """'%ccim_matmul_prepacked_pallas.1 = s32[...] custom-call(...)'
    -> 'ccim_matmul_prepacked_pallas'."""
    m = _NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


@dataclasses.dataclass
class Trace:
    """Events in seconds on the host clock.  ``ops`` holds one list of
    (name, start, end) per device plane."""
    ops: List[List[Tuple[str, float, float]]]
    spans: List[Tuple[str, float, float]]
    shift_s: float = 0.0


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 -- a stat the reader cannot decode
        return {}


def load(path: str, align: bool = True) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, starts, enqueues = [], [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        t = e.start_ns * 1e-9
                        dev.append((op_name(e.name), t,
                                    t + e.duration_ns * 1e-9))
                elif line.name == "XLA Modules" and align:
                    for e in line.events:
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            starts.setdefault(rid, e.start_ns * 1e-9)
            ops.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        t = e.start_ns * 1e-9
                        spans.append((e.name, t, t + e.duration_ns * 1e-9))
                    elif align and e.name == "DoEnqueueProgram":
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            enqueues.setdefault(rid, e.start_ns * 1e-9)
    lags = [enqueues[r] - starts[r] for r in starts if r in enqueues]
    shift = max(0.0, statistics.median(lags)) if lags else 0.0
    if shift:
        ops = [[(n, a + shift, b + shift) for n, a, b in dev] for dev in ops]
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]),
                 shift_s=shift)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    t0, t1 = window
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Union of device op intervals inside the window, averaged over
    the device planes."""
    if not trace.ops:
        return 0.0
    per = [sum(b - a for a, b in union(clip([(s, e) for _, s, e in dev],
                                            window)))
           for dev in trace.ops]
    return sum(per) / len(per)


def idle_gaps(trace: Trace, window: Interval,
              plane: int = 0) -> List[Tuple[str, float, float]]:
    """(label, start, end) of every stretch of the window with no device
    op on ``plane``; label = innermost bench.* span around the middle."""
    busy = union(clip([(s, e) for _, s, e in trace.ops[plane]], window))
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < window[1]:
        gaps.append((t, window[1]))
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        around = [s for s in trace.spans if s[1] <= mid <= s[2]]
        label = (min(around, key=lambda s: s[2] - s[1])[0] if around
                 else "outside bench spans")
        out.append((label, a, b))
    return out


def gaps_by_label(gaps: Sequence[Tuple[str, float, float]]
                  ) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = defaultdict(float)
    for label, a, b in gaps:
        tot[label] += b - a
    return sorted(tot.items(), key=lambda kv: -kv[1])


def device_time(trace: Trace, names: Sequence[str], window: Interval,
                plane: int = 0) -> Tuple[float, int]:
    """(seconds, count) of ops on ``plane`` inside the window whose name
    is one of ``names``."""
    want = set(names)
    secs, n = 0.0, 0
    for name, s, e in trace.ops[plane]:
        if name in want and e > window[0] and s < window[1]:
            secs += min(e, window[1]) - max(s, window[0])
            n += 1
    return secs, n


def top_ops(trace: Trace, window: Interval, k: int = 10,
            plane: int = 0) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = defaultdict(float)
    for name, s, e in trace.ops[plane]:
        if e > window[0] and s < window[1]:
            tot[name] += min(e, window[1]) - max(s, window[0])
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def span_window(trace: Trace, name: str) -> Optional[Interval]:
    """First start to last end of the host spans called ``name``."""
    hits = [(s, e) for n, s, e in trace.spans if n == name]
    if not hits:
        return None
    return min(s for s, _ in hits), max(e for _, e in hits)
