"""Seconds of the CIM pack (``lm.pack_cim_params``) in set-up, host clock
around the call and its ``block_until_ready``."""
LAYER = "launcher"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.host.get("pack_s")
