"""Share of prompt tokens served from shared cache blocks: the prefix
plan's shared blocks times the block size, over all prompt tokens of the
traced batches."""
LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    shared = prompt = 0
    for b in run.batches:
        if b.shared_blocks is None:
            return None
        shared += int(b.shared_blocks.sum()) * run.layout["block_size"]
        prompt += sum(len(p) for _, p, _ in b.requests)
    return shared / prompt if prompt else None
