"""XLA backend compiles during the traced batches' ``run()`` calls
(``ServeReport.n_compiles``, fed by the program's ``jax.monitoring``
counter in ``repro/obs/compiles.py``), summed.  A compile inside the
window is time no batch should pay: set-up warms every shape."""
LAYER = "launcher"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    counts = [getattr(b.report, "n_compiles", None) for b in run.batches]
    if not counts or any(n is None for n in counts):
        return None
    return sum(counts)
