"""Device scopes: the serve loop's layers, named in the compiled program.

A ``jax.named_scope`` adds its name to the JAX name stack of every op
traced inside it.  The stack reaches the optimised HLO as each
instruction's ``metadata={op_name="..."}`` (fusions keep their root's),
so a profiler trace's device ops can be put down to the scopes by
instruction name (``ContinuousBatchingScheduler.compiled_loop_text``).
Scopes add no op, and the pre-optimisation StableHLO text
(``loop_hlo_text``, the fingerprint gate's input) does not carry them.

The serve loop's scopes:

* ``sched.harvest``, ``sched.admit``, ``sched.prefill``, ``sched.step``:
  the scheduler's switch branches; ``sched.sample``: token sampling;
* ``lm.step``, ``lm.prefill``, ``lm.verify``: the model's decode step,
  prefill (chunk) and speculative verify, norms and residuals included;
* ``lm.embed``, ``lm.cim`` (every CIM projection), ``lm.attn`` (the
  attention core with its cache write and read), ``lm.ssm`` (the mamba2
  mixer), ``lm.moe`` (a held-expert layer's router, its held experts and
  their gated sum), ``lm.moe_shared`` (its shared expert), ``lm.head``.
"""
from __future__ import annotations

import functools

import jax


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``.

    A fresh scope per call: ``@jax.named_scope(name)`` used directly as a
    decorator keeps one context object, and its saved state, for every
    call."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return call
    return wrap
