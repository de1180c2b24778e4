"""The scheduler's share of the device: serve-loop device time under no
``lm.*`` scope (the branches' bookkeeping, sampling, the carry's copies
and the loop's control) over the traced batch's busy seconds.

Reads ``run.scope_trace`` and ``run.loop_text`` as ``decode_step_ms``
does."""
import scope_reduce
import trace_reduce

LAYER = "scheduler"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    stacks = scope_reduce.run_stacks(run)
    if stacks is None:
        return None
    busy = trace_reduce.busy_seconds(run.scope_trace.base, run.window)
    return scope_reduce.outside(stacks, "lm.") / busy if busy > 0 else None
