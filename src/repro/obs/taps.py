"""Trace-time value taps: let inner kernels export telemetry scalars.

The serving models are traced into ONE executable (the scheduler's
while-loop switch), and the interesting health signals -- e.g. how many
ADC codes the packed GEMM epilogue clipped -- are born deep inside that
trace, under a ``lax.scan`` over layers and sometimes under a second
scan over accumulate chunks.  Threading an explicit "stats" output
through every model/kernel signature would contaminate dozens of APIs
for a value that only exists when telemetry is on.

Instead, kernels ``emit(name, value)`` into a module-level collector
stack that is only populated while a ``collect()`` context is active
*at trace time*:

  * ``collect()`` is pushed by the scheduler around tracing a switch
    branch (launch/scheduler.py) and drained into the on-device counter
    array in the same trace -- the emitted values are ordinary tracers
    of the enclosing trace, consumed in that same trace.
  * ``active()`` is a plain Python bool, so a kernel traced with no
    collector (telemetry off, or any other caller) contributes ZERO
    extra operations -- the metrics-off HLO is byte-identical.
  * ``scan(body, init, xs)`` relays emissions across a ``lax.scan``
    boundary: tap values emitted inside the body are tracers of the
    body trace and may not leak out, so the relay drains them into
    extra per-step scan outputs and re-emits their sum (over the scan
    axis) in the enclosing trace.  With no collector active it IS
    ``jax.lax.scan`` -- same primitive, same jaxpr.

Emissions are summed per name on drain; every tap value must therefore
be an additive count/total (int32 -- the serve-path lint forbids 64-bit
avals, analysis/tracer.py).
"""
from __future__ import annotations

import contextlib
from typing import Dict, FrozenSet, Iterable, Iterator, List, Optional

import jax
import jax.numpy as jnp

# stack of live collector frames (innermost last), and the names each
# takes (None: every name); trace-time only
_STACK: List[Dict[str, List[jax.Array]]] = []
_NAMES: List[Optional[FrozenSet[str]]] = []


def active(name: Optional[str] = None) -> bool:
    """True while some ``collect()`` frame is open (trace-time check)
    and, given ``name``, the innermost one takes it."""
    return bool(_STACK) and (name is None or _NAMES[-1] is None
                             or name in _NAMES[-1])


def emit(name: str, value) -> None:
    """Record ``value`` under ``name`` in the innermost collector.
    No-op (and no tracing of ``value``'s producers happens at the call
    site -- guard any extra computation with ``active(name)``) where no
    open frame takes ``name``."""
    if active(name):
        _STACK[-1].setdefault(name, []).append(jnp.asarray(value))


@contextlib.contextmanager
def collect(names: Optional[Iterable[str]] = None
            ) -> Iterator[Dict[str, List[jax.Array]]]:
    """Open a collector frame; yields the frame dict (name -> values).
    ``names`` limits what it takes; by default it takes what the
    enclosing frame takes (the scan and switch relays), or every name."""
    frame: Dict[str, List[jax.Array]] = {}
    _NAMES.append(frozenset(names) if names is not None
                  else (_NAMES[-1] if _NAMES else None))
    _STACK.append(frame)
    try:
        yield frame
    finally:
        _STACK.pop()
        _NAMES.pop()


def drain_sum(frame: Dict[str, List[jax.Array]], name: str,
              dtype=jnp.int32) -> jax.Array:
    """Sum of everything emitted under ``name`` in ``frame`` (0 if none)."""
    vals = frame.get(name, [])
    if not vals:
        return jnp.zeros((), dtype)
    out = jnp.zeros((), dtype)
    for v in vals:
        out = out + v.astype(dtype)
    return out


def scan(body, init, xs):
    """``jax.lax.scan`` that relays tap emissions across the boundary.

    The body runs under its own collector frame; whatever it emitted
    becomes an extra stacked scan output, summed over the scan axis and
    re-emitted into the enclosing frame.  Inactive -> plain lax.scan.
    """
    if not _STACK:
        return jax.lax.scan(body, init, xs)

    def body2(c, s):
        with collect() as frame:
            c2, ys = body(c, s)
        tapped = {k: drain_sum(frame, k) for k in sorted(frame)}
        return c2, (ys, tapped)

    c2, (ys, tapped) = jax.lax.scan(body2, init, xs)
    for k, v in tapped.items():
        emit(k, jnp.sum(v))
    return c2, ys


def switch(index, branches, *operands):
    """``jax.lax.switch`` that relays tap emissions across the boundary.

    ``lax.switch`` requires structurally identical branch outputs, so
    every branch reports each name that any branch emits, 0 for a name
    it does not (a branch that skips a kernel emits less than one that
    runs it); the names are found by tracing each branch abstractly
    first.  Inactive -> plain lax.switch, same jaxpr.
    """
    if not _STACK:
        return jax.lax.switch(index, branches, *operands)

    def wrap(b, names=None):
        def b2(*ops):
            with collect() as frame:
                out = b(*ops)
            return out, {k: drain_sum(frame, k)
                         for k in sorted(frame if names is None else names)}
        return b2

    names = set()
    for b in branches:
        names.update(jax.eval_shape(wrap(b), *operands)[1])
    out, tapped = jax.lax.switch(index, [wrap(b, names) for b in branches],
                                 *operands)
    for k, v in tapped.items():
        emit(k, v)
    return out
