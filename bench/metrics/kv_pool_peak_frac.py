"""Peak blocks in use over the allocatable blocks of the paged KV pool
(all but the trash block), the largest over the traced batches.  A
model with no attention keeps no KV, and reads nothing here."""
LAYER = "paged cache"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    if run.arch.kv_bytes_per_token(run.model) == 0:
        return None
    peak = max(b.report.peak_blocks for b in run.batches)
    return peak / (run.layout["n_blocks"] - 1)
