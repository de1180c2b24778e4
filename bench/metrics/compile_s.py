"""Seconds to build the scheduler and its loop for the cell's queue
length (``compile_for(Q)``) in set-up, host clock; a warm persistent
compile cache makes this a load."""
LAYER = "launcher"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.host.get("compile_s")
