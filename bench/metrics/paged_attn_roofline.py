"""The paged-attention decode kernel's share of its roofline, in %: the
least time of the cache reads its decode rows require (every served
token but the first reads its whole context, over the published
unpadded heads, in bf16; see work.py) over its device time in the
trace.  Least time = max(bytes / HBM bandwidth, ops / bf16 peak)."""
import trace_reduce
import work

LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "output_tok_s"
KERNELS = ("paged_attention_pallas",)


def read(run):
    if run.trace is None or run.window is None or run.peaks is None:
        return None
    if run.arch.kv_bytes_per_token(run.model) == 0:
        return None
    secs, count = trace_reduce.device_time(run.trace, KERNELS, run.window)
    if count == 0 or secs <= 0:
        return None
    nbytes = ops = 0
    for b in run.batches:
        got = b.report.tokens_by_rid()
        for rid, p, _ in b.requests:
            n = len(got[rid])
            nbytes += work.decode_kv_bytes(run.arch, run.model, len(p), n)
            ops += work.decode_attn_ops(run.arch, run.model, len(p), n)
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
