#!/usr/bin/env python3
"""Chip benchmark of the packed-CIM serving path: offline batches.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the one chip.  A run of a cell (``BENCHMARK.json``
``workloads``: a configuration under a traffic mix) goes:

1. set-up, timed from process start to the first timed batch as
   ``setup_s``: the persistent compile cache, the weights made on the
   device from the seed (weights.py), the CIM pack
   (``lm.pack_cim_params``), the paged ``ContinuousBatchingScheduler``
   and its loop compiled for the cell's queue length Q, and one warm-up
   batch of Q requests with 2 new tokens each;
2. the window: batches of Q requests drawn from (seed, batch index)
   (workload.py) served back to back through
   ``ContinuousBatchingScheduler.run``; no batch starts after
   ``--seconds``.  ``output_tok_s`` is every generated token over the
   window's whole length, host staging between batches included;
3. with ``--trace 1`` the window is one batch under the JAX profiler
   instead, and the cell's per-layer metrics are read from it by the
   readers in ``metrics/<name>.py``;
4. the check (check.py) against the plain reference (reference.py),
   after the device's peak memory is read and the program's state freed.

The last stdout line is one JSON object: correct, attempted, failed,
metrics, device, [breakdown,] checks.  The numbers compared are also
the last lines on stderr.  With no TPU, or fewer chips than the cell
asks for, the run exits 2 and prints no result.

Everything about a cell is found by name: ``configs/<config>.json``
(as ``BENCHMARK.json`` names it), the architecture that configuration
names under its ``arch`` key, ``arch/<arch>.py`` (its weights,
reference layers and work counts), ``traffic/<traffic>.json`` and
``metrics/<metric>.py``.  ``--rehearse`` runs a cell at the
configuration's ``smoke`` size on any backend, for rehearsal on a CPU;
it prints counts only, never a time or a device metric.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

WARMUP_NEW_TOKENS = 2
WARMUP_BATCH = 2 ** 31 - 1        # batch index the warm-up draws from
END_TO_END = ("output_tok_s", "setup_s")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _load_module(bench_dir: str, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py``, loaded from its path."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reader(bench_dir: str, name: str):
    return _load_module(bench_dir, "metrics", name)


def _benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec(root: str, bench: dict, cell: dict, config_file: str,
          rehearse: bool) -> Dict[str, Any]:
    bench_dir = os.path.join(root, bench["paths"][0])
    with open(os.path.join(root, config_file)) as f:
        config = json.load(f)
    if "arch" not in config:
        raise SystemExit(f"{config_file}: the configuration names no 'arch'")
    import workload as wl
    mix = wl.load_mix(os.path.join(bench_dir, "traffic",
                                   cell["traffic"] + ".json"))
    if rehearse:
        smoke = config.get("smoke", {})
        config = _merge(config, {k: v for k, v in smoke.items()
                                 if k in ("model", "serving", "check")})
        config["traffic_scale"] = smoke.get("traffic_scale", 1.0)
    name = cell["name"]
    in_cell = lambda m: "workloads" not in m or name in m["workloads"]
    return dict(cell=cell, config=config, mix=mix, bench_dir=bench_dir,
                arch=_load_module(bench_dir, "arch", config["arch"]),
                end_to_end=[m for m in bench["end_to_end"] if in_cell(m)],
                per_layer=[m for m in bench["per_layer"] if in_cell(m)])


def load_spec(root: str, workload: str, rehearse: bool) -> Dict[str, Any]:
    """The cell's entry, configuration, traffic mix, architecture module
    and metric entries."""
    bench = _benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return _spec(root, bench, cell, conf_entry["file"], rehearse)


def config_spec(root: str, config_file: str, traffic: str,
                rehearse: bool) -> Dict[str, Any]:
    """As ``load_spec``, for a configuration file (relative to ``root``)
    under a traffic mix, on one chip, whether or not a cell lists them."""
    name = os.path.splitext(os.path.basename(config_file))[0]
    cell = {"name": f"{name}.{traffic}", "config": name, "traffic": traffic,
            "chips": 1}
    return _spec(root, _benchmark(root), cell, config_file, rehearse)


@dataclasses.dataclass
class Batch:
    requests: list                # (rid, prompt, max_new) per request
    report: Any                   # the scheduler's ServeReport
    shared_blocks: Any            # per-request shared-prefix blocks, or None
    wall_s: float                 # host clock around run(): staging to tokens


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader sees (``metrics/<name>.py``)."""
    cell: str
    model: dict                   # the configuration's model section
    layout: dict                  # block_size, n_tbl, n_blocks
    host: dict                    # host-clock seconds of the set-up steps
    batches: List[Batch]          # the batches the metrics are read from
    memory: dict                  # the device's memory_stats() after them
    peaks: Optional[dict] = None  # peaks.json row of this device kind
    trace: Any = None             # trace_reduce.Trace of the traced batch
    window: Any = None            # (t0, t1) of that batch on the host clock
    arch: Any = None              # the configuration's arch/<name>.py module


def _device_or_exit(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        log(f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform!r} device(s)")
        raise SystemExit(2)
    return devs


def plan(spec: Dict[str, Any], seed: int):
    """The cell's paged layout (block_size, n_tbl, n_blocks) and its
    traffic (workload.Traffic, whose ``q`` is the queue length)."""
    import workload as wl
    config = spec["config"]
    model, sv = config["model"], config["serving"]
    bs = sv["block_size"]
    n_tbl = -(-(sv["prompt_cap"] + sv["max_new_cap"]) // bs)
    layout = dict(block_size=bs, n_tbl=n_tbl,
                  n_blocks=sv.get("n_blocks", sv["slots"] * n_tbl + 1))
    traffic = wl.Traffic(
        spec["mix"], seed, sv["slots"], model["vocab_size"],
        sv["prompt_cap"], sv["max_new_cap"],
        multiple=spec["arch"].prompt_multiple(model, sv),
        scale=config.get("traffic_scale", 1.0))
    return layout, traffic


class Cell:
    """One cell's program under test: set up from the seed, then serving
    batches of the cell's traffic.  ``readings.py`` drives it too."""

    def __init__(self, spec: Dict[str, Any], seed: int):
        import jax
        sys.path.insert(0, os.path.join(REPO, "src"))
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.paging import PagedLayout
        from repro.launch.scheduler import (ContinuousBatchingScheduler,
                                            Request)
        from repro.models import lm
        from repro.models.config import ModelConfig

        import weights

        self.spec, self.seed, self.Request = spec, seed, Request
        self.arch, config = spec["arch"], spec["config"]
        self.model, sv = config["model"], config["serving"]
        self.cache_dir = enable_compile_cache()
        # every program of the run goes into the cache, not only those
        # that take a second to compile, so set-up repeats run to run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.prompt_cap, self.new_cap = sv["prompt_cap"], sv["max_new_cap"]
        self.layout, self.traffic = plan(spec, seed)
        self.q = self.traffic.q
        cfg = ModelConfig(**self.model, cim_mode=True)

        host = self.host = {}
        t = time.time()
        raw = jax.block_until_ready(
            weights.make_params(self.arch, self.model, seed))
        host["weights_s"] = time.time() - t
        t = time.time()
        params = jax.block_until_ready(lm.pack_cim_params(raw, cfg))
        host["pack_s"] = time.time() - t
        del raw
        t = time.time()
        self.sched = ContinuousBatchingScheduler(
            params, cfg, slots=sv["slots"], prompt_len=self.prompt_cap,
            max_new_cap=self.new_cap,
            paged=PagedLayout(**self.layout), prefill_chunk=sv["prefill_chunk"])
        self.sched.compile_for(self.q)
        host["compile_s"] = time.time() - t
        t = time.time()
        self.sched.run([Request(rid, p, WARMUP_NEW_TOKENS)
                        for rid, p, _ in self.traffic.batch(WARMUP_BATCH)])
        host["warmup_s"] = time.time() - t

    def serve(self, index: int) -> Batch:
        import numpy as np
        reqs = self.traffic.batch(index)
        t0 = time.time()
        rep = self.sched.run([self.Request(rid, p, n) for rid, p, n in reqs])
        wall = time.time() - t0
        prefix = getattr(self.sched, "last_prefix_plan", None)
        return Batch(reqs, rep, None if prefix is None else
                     np.asarray(prefix.n_shared_blocks), wall)

    def annotate(self) -> None:
        """Put the scheduler's staging and carry set-up into host spans
        of the profiler trace, so idle gaps can be put down to them."""
        from jax.profiler import TraceAnnotation

        def wrap(name, fn):
            def call(*a, **k):
                with TraceAnnotation(name):
                    return fn(*a, **k)
            return call

        self.sched._stage = wrap("bench.stage", self.sched._stage)
        self.sched._init_carry = wrap("bench.carry", self.sched._init_carry)

    def free(self) -> None:
        """Drop the program's state (packed weights, caches, executables)."""
        self.sched = None
        gc.collect()

    def gaps(self, batches: List[Batch], controls=()):
        """The check's reference readings over the sample of ``batches``:
        (finished requests, sample indices, {name: per-request gaps}),
        ``controls`` named by their activation dtypes."""
        import numpy as np

        import check
        import weights
        finished = []
        for b in batches:
            got = b.report.tokens_by_rid()
            for rid, prompt, n in b.requests:
                finished.append((rid, prompt, n,
                                 got.get(rid, np.zeros(0, np.int32))))
        lim = self.spec["config"]["check"]
        pick = check.sample(finished, lim["sample"], self.seed)
        params = weights.make_params(self.arch, self.model, self.seed)
        gaps = check.logit_gaps(
            self.arch, params, self.model,
            [(finished[i][1], finished[i][3]) for i in pick],
            self.prompt_cap + self.new_cap, self.new_cap, lim["act"], controls)
        del params
        return finished, pick, gaps


def main(argv=None, root: Optional[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke size on any backend; counts only")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be >= 0")
    spec = load_spec(root or REPO, args.workload, args.rehearse)
    devs = _device_or_exit(spec["cell"]["chips"], args.rehearse)

    import jax

    import check
    import trace_reduce

    dev = devs[0]
    peaks = None
    if not args.rehearse:
        with open(os.path.join(spec["bench_dir"], "peaks.json")) as f:
            table = json.load(f)
        if dev.device_kind not in table:
            raise SystemExit(f"no peaks for device kind {dev.device_kind!r}"
                             " in peaks.json")
        peaks = table[dev.device_kind]

    cell = Cell(spec, args.seed)
    setup_s = time.time() - T_START
    host = dict(cell.host, setup_s=setup_s)
    log(f"device {dev.platform} {dev.device_kind!r} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {cell.cache_dir}")
    log("setup: " + ", ".join(f"{k} {v:.4f}" for k, v in host.items()))

    batches: List[Batch] = []
    trace = window = None
    if args.trace:
        from jax.profiler import TraceAnnotation
        cell.annotate()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(trace_dir)
            with TraceAnnotation("bench.batch"):
                batches.append(cell.serve(0))
            jax.profiler.stop_trace()
            xplanes = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                       for f in fs if f.endswith(".xplane.pb")]
            trace = trace_reduce.load(xplanes[0])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        window = trace_reduce.span_window(trace, "bench.batch")
        window_s = batches[0].wall_s
    else:
        t0 = time.time()
        while not batches or time.time() - t0 < args.seconds:
            batches.append(cell.serve(len(batches)))
        window_s = time.time() - t0
    n_tok = sum(b.report.total_tokens for b in batches)
    log(f"window: {len(batches)} batches of {cell.q} requests, {n_tok} "
        f"tokens in {window_s:.4f} s (batch walls "
        + ", ".join(f"{b.wall_s:.3f}" for b in batches) + ")")
    memory = dict(dev.memory_stats() or {})
    run = Run(cell=spec["cell"]["name"], model=cell.model,
              layout=cell.layout, host=host,
              batches=batches, memory=memory, peaks=peaks, trace=trace,
              window=window, arch=cell.arch)

    cell.free()
    t = time.time()
    finished, pick, gaps = cell.gaps(batches)
    served, lim = gaps["served"], spec["config"]["check"]
    bad = check.bad_requests(finished, cell.model["vocab_size"])
    log(f"check: reference over {len(pick)} requests "
        f"({sum(len(g) for g in served)} served tokens) in "
        f"{time.time() - t:.3f} s; gaps in logit standard deviations: "
        f"mean {check.mean_gap(served)}, widest {check.widest_gap(served)}")
    log(f"check: far_share at {check.FAR_SIGMAS} sd: "
        f"{[check.far_share(served, f) for f in check.FAR_SIGMAS]}")
    share = (None if lim["far_sigma"] is None
             else check.far_share(served, lim["far_sigma"]))
    verdict = check.verdict(bad, share, lim["max_far_share"])

    metrics: Dict[str, dict] = {}
    if not args.trace and not args.rehearse:
        e2e = {"output_tok_s": n_tok / window_s, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            if m["name"] not in e2e:
                raise SystemExit(f"end-to-end metric {m['name']!r} is not "
                                 f"one the harness measures {END_TO_END}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            if args.rehearse and m["source"] != "program_counter":
                continue
            value = _load_reader(spec["bench_dir"], m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": memory.get("peak_bytes_in_use")}
    if args.rehearse:
        device = {"platform": dev.platform, "count": len(devs)}
    out = {"correct": verdict["correct"], "attempted": len(finished),
           "failed": bad, "metrics": metrics, "device": device}
    if args.rehearse:
        out = {"rehearsal": True, **out}
    if trace is not None and window is not None:
        busy = trace_reduce.busy_seconds(trace, window)
        device["busy_s"] = busy
        device["window_s"] = window[1] - window[0]
        out["breakdown"] = {
            "device_ops": [list(x) for x in
                           trace_reduce.top_ops(trace, window)],
            "idle_gaps": [list(x) for x in trace_reduce.gaps_by_label(
                trace_reduce.idle_gaps(trace, window))[:10]]}
        log(f"trace: busy {busy:.6f} s of {window[1] - window[0]:.6f} s; "
            f"device clock shifted {trace.shift_s * 1e3:.3f} ms")
    out["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
