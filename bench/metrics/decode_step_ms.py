"""Device milliseconds per pooled decode step: the traced batch's device
time under the scheduler's ``sched.step`` scope over its decode steps.

Reads ``run.scope_trace`` (``scope_reduce.ScopeTrace``) and
``run.loop_text`` (``compiled_loop_text(Q)``), which ``scopes.py``
supplies; a run without them reads nothing."""
import scope_reduce

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "output_tok_s"
SCOPE = "sched.step"


def read(run):
    stacks = scope_reduce.run_stacks(run)
    n = sum(b.report.n_steps for b in run.batches)
    if stacks is None or not n:
        return None
    return 1e3 * scope_reduce.under(stacks, SCOPE) / n
