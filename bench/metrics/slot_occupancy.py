"""Generated tokens over the slot-iterations spent, summed over the
traced batches: slots * decode steps + admits + prefill chunks (the
formula of the scheduler's ``ServeReport.occupancy``)."""
LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    tokens = spent = 0
    for b in run.batches:
        r = b.report
        tokens += r.total_tokens
        spent += r.slots * r.n_steps + r.n_admits + r.n_pf
    return tokens / spent if spent else None
