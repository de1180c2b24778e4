"""1 - (union of device-op intervals / traced window), from the profiler
trace of one batch; the window runs from the batch's staging to its
tokens on the host."""
import trace_reduce

LAYER = "device"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(run):
    if run.trace is None or run.window is None:
        return None
    t0, t1 = run.window
    return 1.0 - trace_reduce.busy_seconds(run.trace, run.window) / (t1 - t0)
