"""The whole step's share of the chip's peak, in %: the least time the
traced batch's work needs at the peaks (CIM projections at the int8
peak; attention, recurrent state and the LM head at the bf16 peak;
every prompt and served token's row, none from a cache; see work.py)
over the traced window."""
import work

LAYER = "model step"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    if run.window is None or run.peaks is None:
        return None
    reqs = [(len(p), len(b.report.tokens_by_rid()[rid]))
            for b in run.batches for rid, p, _ in b.requests]
    i8, b16 = work.step_work(run.arch, run.model, reqs)
    least = (i8 / run.peaks["int8_ops_per_s"]
             + b16 / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / (run.window[1] - run.window[0])
