"""Device milliseconds per prefill chunk: the traced batch's device time
under the scheduler's ``sched.prefill`` scope over its prefill chunks
(paged cells only; a contiguous cell prefills inside ``sched.admit``).

Reads ``run.scope_trace`` and ``run.loop_text`` as ``decode_step_ms``
does."""
import scope_reduce

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "output_tok_s"
SCOPE = "sched.prefill"


def read(run):
    stacks = scope_reduce.run_stacks(run)
    n = sum(b.report.n_pf for b in run.batches)
    if stacks is None or not n:
        return None
    return 1e3 * scope_reduce.under(stacks, SCOPE) / n
