#!/usr/bin/env python3
"""Where a cell's traced batch spends its device and host time, by the
program's own names.

    python3 bench/scopes.py --workload <cell> --seed <n> [--batches 2]

Sets the cell up as a benchmark run does (``run.Cell``), takes the
compiled serve loop's HLO text (``compiled_loop_text(Q)``), and profiles
``--batches`` batches, each as a benchmark's traced run profiles its
one (``bench.batch`` around ``Cell.serve``, the harness's ``bench.*``
spans on).  Per batch it prints on stderr:

* device seconds by scheduler branch (``sched.*``) and model layer
  (``lm.*``), and what did not resolve to a name stack;
* host seconds of the program's spans (``sched.*``, ``launch.*``) and
  the idle device seconds under each;
* the readers ``metrics/decode_step_ms.py``, ``prefill_chunk_ms.py``,
  ``sched_device_frac.py``, ``host_overhead_s.py`` and
  ``window_compiles.py`` on that batch;

and one JSON line per batch to stdout and to
``chiprun_out/scopes-<cell>.jsonl``.  It is no part of a benchmark run:
the benchmark's ``run.py`` does not hand a reader the loop's text or
the trace's instruction names.  Needs the chip (device planes).
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench  # noqa: E402
import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402

READERS = ("decode_step_ms", "prefill_chunk_ms", "sched_device_frac",
           "host_overhead_s", "window_compiles")


def profile(cell, index: int):
    """Serve batch ``index`` under the profiler; (batch, ScopeTrace)."""
    import jax
    from jax.profiler import TraceAnnotation

    trace_dir = tempfile.mkdtemp(prefix="bench-scopes-")
    try:
        jax.profiler.start_trace(trace_dir)
        with TraceAnnotation("bench.batch"):
            batch = cell.serve(index)
        jax.profiler.stop_trace()
        path = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb")][0]
        return batch, scope_reduce.load(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def reduce_batch(spec, cell, batch, trace, text: str, peaks) -> dict:
    """The numbers of one profiled batch, logged on stderr."""
    window = trace_reduce.span_window(trace.base, "bench.batch")
    busy = trace_reduce.busy_seconds(trace.base, window)
    own = scope_reduce.self_seconds(trace, window)
    stacks, unresolved, other = scope_reduce.by_stack(own, text)
    loop = sum(stacks.values())
    bench.log(f"device: busy {busy:.6f} s of {window[1] - window[0]:.6f}; "
              f"serve loop {loop:.6f} s resolved, {unresolved:.6f} s "
              f"unresolved, {other:.6f} s in other programs; resolved "
              f"{loop / busy:.4%} of busy")
    for branch, layer, secs in scope_reduce.split(stacks):
        bench.log(f"  {branch or '(loop control)':15s} "
                  f"{layer or '(no lm scope)':15s} {secs:.6f} s "
                  f"{secs / busy:.2%}")
    host = scope_reduce.host_by_span(trace, window)
    bench.log("host spans: " + ", ".join(f"{k} {v:.6f}"
                                         for k, v in sorted(host.items())))
    idle = scope_reduce.idle_by_span(trace, window)
    bench.log("idle device seconds by program span: " + ", ".join(
        f"{k} {v:.6f}" for k, v in idle))
    run = bench.Run(cell=spec["cell"]["name"], model=cell.model,
                    layout=cell.layout, host=cell.host, batches=[batch],
                    memory={}, peaks=peaks, trace=trace.base, window=window,
                    arch=cell.arch)
    run.scope_trace, run.loop_text = trace, text
    metrics = {}
    for name in READERS:
        metrics[name] = bench._load_reader(spec["bench_dir"], name).read(run)
    bench.log("readers: " + ", ".join(f"{k} {v}" for k, v in metrics.items()))
    return {"busy_s": busy, "window_s": window[1] - window[0],
            "loop_resolved_s": loop, "unresolved_s": unresolved,
            "other_programs_s": other,
            "split": [list(x) for x in scope_reduce.split(stacks)],
            "host_s": host, "idle_by_span": [list(x) for x in idle],
            "report_host_s": batch.report.host_s,
            "n_steps": batch.report.n_steps, "n_pf": batch.report.n_pf,
            "n_admits": batch.report.n_admits,
            "n_iter": batch.report.n_iter, "metrics": metrics}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batches", type=int, default=2)
    args = ap.parse_args(argv)
    spec = bench.load_spec(bench.REPO, args.workload, rehearse=False)
    devs = bench._device_or_exit(spec["cell"]["chips"], rehearse=False)
    with open(os.path.join(spec["bench_dir"], "peaks.json")) as f:
        peaks = json.load(f).get(devs[0].device_kind)
    t0 = time.time()
    cell = bench.Cell(spec, args.seed)
    from repro.obs import backend_compiles
    bench.log(f"setup {time.time() - t0:.3f} s ({cell.host}); "
              f"{backend_compiles()} backend compiles in this process")
    t = time.time()
    text = cell.sched.compiled_loop_text(cell.q)
    bench.log(f"compiled loop text: {len(text)} chars in "
              f"{time.time() - t:.3f} s; op_name metadata "
              f"{text.count('op_name=')}, sched.step {text.count('sched.step')}"
              f", lm.cim {text.count('lm.cim')}")
    cell.annotate()
    out_dir = os.path.join(bench.REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for i in range(args.batches):
        batch, trace = profile(cell, i)
        row = {"cell": args.workload, "seed": args.seed, "batch": i,
               **reduce_batch(spec, cell, batch, trace, text, peaks)}
        line = json.dumps(row)
        print(line, flush=True)
        with open(os.path.join(out_dir, f"scopes-{args.workload}.jsonl"),
                  "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
