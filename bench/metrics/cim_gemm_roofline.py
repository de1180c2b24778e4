"""The CIM GEMM kernels' share of their roofline, in %: the least time
their required work needs on the chip over their device time in the
trace.

Work (work.py): 2*M*K*N int8 operations per call of a whole pass
(``arch.cim_sites``) over the rows the model must compute (prompt tokens
not in shared blocks, and every served token but the last), times the
share of them the call sees (1 but for routed experts); bytes: every CIM
weight once per model pass (decode steps + prefill chunks), 1 B per
activation in and 4 B per int32 out.
Least time = max(ops / int8 peak, bytes / HBM bandwidth); which of the
two binds is logged.  Kernel time is the device time of the ops named
in KERNELS; the log line compares their count per model pass with the
CIM calls a pass should make, so a projection that quietly runs as XLA
shows there.
"""
import sys

import trace_reduce
import work

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "output_tok_s"
KERNELS = ("ccim_matmul_prepacked_pallas", "ccim_matmul_prepacked_skinny_pallas")


def read(run):
    if run.trace is None or run.window is None or run.peaks is None:
        return None
    secs, count = trace_reduce.device_time(run.trace, KERNELS, run.window)
    if count == 0 or secs <= 0:
        return None
    rows = passes = 0
    bs = run.layout["block_size"]
    for b in run.batches:
        got = b.report.tokens_by_rid()
        for i, (rid, p, _) in enumerate(b.requests):
            shared = 0 if b.shared_blocks is None else int(b.shared_blocks[i]) * bs
            rows += work.request_rows(len(p), len(got[rid]), shared)
        passes += b.report.n_steps + b.report.n_pf
    ops = work.cim_ops(run.arch, run.model, rows)
    nbytes = work.cim_bytes(run.arch, run.model, rows, passes)
    t_ops = ops / run.peaks["int8_ops_per_s"]
    t_mem = nbytes / run.peaks["hbm_bytes_per_s"]
    sites = len(run.arch.cim_sites(run.model))
    print(f"cim_gemm_roofline: {count} kernel events over {passes} model "
          f"passes = {count / max(passes, 1):.3f} per pass (a pass makes "
          f"{sites} CIM calls); {secs:.6f} s; bound by "
          f"{'int8 ops' if t_ops >= t_mem else 'HBM bytes'} "
          f"({t_ops:.6f} s ops, {t_mem:.6f} s bytes)", file=sys.stderr)
    return 100.0 * max(t_ops, t_mem) / secs
