"""Host seconds per traced batch that ``ContinuousBatchingScheduler.run``
spends outside its device loop: span ``sched.run`` less span
``sched.loop`` (the check, staging, the carry, any compile, and fetching
the results), from the program's own spans (``ServeReport.host_s``),
averaged over the traced batches."""
LAYER = "scheduler"
SOURCE = "program_span"
MOVES = "output_tok_s"


def read(run):
    per = []
    for b in run.batches:
        host = getattr(b.report, "host_s", None) or {}
        if "sched.run" not in host or "sched.loop" not in host:
            return None
        per.append(host["sched.run"] - host["sched.loop"])
    return sum(per) / len(per) if per else None
