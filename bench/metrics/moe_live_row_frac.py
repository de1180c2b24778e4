"""Share of the held experts' computed rows that the router sent them:
the (live row, held expert) pairs the routers chose over the rows the
held experts' CIM calls computed, every MoE layer of every decode step
and prefill chunk of the traced batches (``ServeReport.moe_routed_rows``
and ``moe_expert_rows``).  Every held expert computes every row of a
pass, so this is what a dispatch that computes only routed rows would
keep of that work.  A model without held experts, or a program without
the counters, reads nothing here."""
LAYER = "kernels"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    routed = computed = 0
    for b in run.batches:
        routed += getattr(b.report, "moe_routed_rows", 0)
        computed += getattr(b.report, "moe_expert_rows", 0)
    return routed / computed if computed else None
