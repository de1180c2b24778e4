"""Share of the paged decode-attention kernel's grid (slots x table
entries, every decode step) that live rows' lengths cover, over the
traced batches: the scheduler's ``ServeReport.attn_live_block_frac``,
weighted by each batch's decode steps.  The kernel does work on these
blocks only.  A model with no attention, or a program without the
counter, reads nothing here."""
LAYER = "kernels"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    live = steps = 0
    for b in run.batches:
        frac = getattr(b.report, "attn_live_block_frac", None)
        if not frac:
            return None
        live += frac * b.report.n_steps
        steps += b.report.n_steps
    return live / steps if steps else None
