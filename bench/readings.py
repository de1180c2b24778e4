#!/usr/bin/env python3
"""Readings that set a cell's check limit: the program's and the
controls' gaps on several seeds, in one process that holds the chip.

    python3 bench/readings.py --workload <cell> --seeds 11 12 13 [--batches 1]
    python3 bench/readings.py --config <file> --traffic <mix> --seeds ...

Per seed it sets the cell up as a benchmark run does, serves
``--batches`` batches (each finishes the mix's longest requests),
frees the program, and reads over the run's own sample: the program's
``far_share`` at several thresholds (``check.FAR_SIGMAS``) with its mean and
widest gap, and the same for the configuration's control (``check.control``)
at the same positions, each with the verdict its ``far_share`` gets at the
configuration's limit (the control's has to be false).  One JSON line per seed goes
to stdout and to ``chiprun_out/readings-<cell>.jsonl``.  It is no part
of a benchmark run; ``--rehearse`` reads at the smoke size.

``--config`` (a configuration file, relative to the repository's root)
with ``--traffic`` (a mix under ``traffic/``) reads a configuration,
at whatever depth its file states, on one chip, before any cell of
``BENCHMARK.json`` lists it; its lines are named ``<file's
name>.<mix>``.
"""
import argparse
import json
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import check  # noqa: E402
import run as bench  # noqa: E402


def main(argv=None, root: Optional[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload")
    which.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batches", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if (args.config is None) != (args.traffic is None):
        ap.error("--config and --traffic go together")
    root = root or bench.REPO
    if args.config:
        spec = bench.config_spec(root, args.config, args.traffic,
                                 args.rehearse)
    else:
        spec = bench.load_spec(root, args.workload, args.rehearse)
    name = spec["cell"]["name"]
    devs = bench._device_or_exit(spec["cell"]["chips"], args.rehearse)
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"readings-{name}.jsonl")
    for seed in args.seeds:
        t0 = time.time()
        cell = bench.Cell(spec, seed)
        setup = time.time() - t0
        batches = [cell.serve(i) for i in range(args.batches)]
        peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
        cell.free()
        t = time.time()
        finished, pick, gaps = cell.gaps(
            batches, controls=(spec["config"]["check"]["control"],))
        row = {"cell": name, "seed": seed, "setup_s": setup,
               "host": cell.host, "batch_walls": [b.wall_s for b in batches],
               "tokens": sum(b.report.total_tokens for b in batches),
               "peak_bytes": peak, "bad": check.bad_requests(
                   finished, cell.model["vocab_size"]),
               "sampled_tokens": sum(len(g) for g in gaps["served"]),
               "check_s": time.time() - t}
        lim = spec["config"]["check"]
        for name, g in gaps.items():
            share = check.far_share(g, lim["far_sigma"])
            row[name] = {"mean": check.mean_gap(g), "widest": check.widest_gap(g),
                         "far_share": {str(f): check.far_share(g, f)
                                       for f in check.FAR_SIGMAS},
                         "correct": check.verdict(0, share,
                                                  lim["max_far_share"])["correct"]}
        line = json.dumps(row)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
