import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
#   Placeholder host devices exist ONLY for the dry-run; smoke tests and
#   benches see 1 device (this env var is set nowhere else).

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell we record:
  * memory_analysis()      -- per-device bytes: proves the cell fits HBM
  * cost_analysis()        -- HLO FLOPs / bytes for the roofline terms
  * collective byte counts -- parsed from the partitioned HLO text
and write JSON to results/dryrun/. Any sharding mismatch, OOM-at-compile or
unsupported collective is a bug in the framework and fails the cell.

CPU only: this never runs on a TPU.  It forces 512 placeholder host
devices, and ``--all`` imports JAX in the parent and then starts one child
JAX process per cell -- on a chip the parent would hold the device and
every child would fail or hang.  No chip path (``chip_smoke.py``, the
serving launchers) imports it.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all            # every cell, both meshes
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from ..configs import ARCHS, get_config
from . import hlo_analysis
from .mesh import make_production_mesh
from .specs import SHAPES, applicable, build_cell

RESULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "results", "dryrun")


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    ok, reason = applicable(cfg, shape)
    rec = dict(arch=arch, shape=shape,
               mesh="2x16x16" if multi_pod else "16x16")
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(cfg, shape, mesh)
    with mesh:
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.arg_shapes)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    t0 = time.time()
    hlo = hlo_analysis.analyse(compiled.as_text())
    t_analyse = time.time() - t0
    rec.update(
        status="ok",
        meta=cell.meta,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        analyse_s=round(t_analyse, 1),
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            generated_code_bytes=getattr(mem, "generated_code_size_in_bytes",
                                         None),
        ),
        # trip-count-aware, per-device (see hlo_analysis.py)
        flops=hlo["dot_flops"],
        hlo_bytes=hlo["dot_traffic_bytes"],
        hlo_bytes_all_ops=hlo["traffic_bytes"],
        collectives={"bytes": hlo["collective_bytes"],
                     "counts": hlo["collective_counts"],
                     "total_bytes": hlo["collective_total_bytes"]},
        # raw XLA numbers for reference (while bodies counted once!)
        xla_cost=dict(flops=cost.get("flops"),
                      bytes_accessed=cost.get("bytes accessed")),
    )
    print(f"[dryrun] {arch} x {shape} x {rec['mesh']}: "
          f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
          f"flops={rec['flops']:.3e} coll={rec['collectives']['total_bytes']:.3e}B")
    print(f"  memory: {rec['memory']}")
    return rec


def _cell_path(arch, shape, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    return os.path.join(RESULT_DIR, f"{arch}__{shape}__{mesh}.json")


def run_all(force: bool = False, timeout: int = 3600):
    os.makedirs(RESULT_DIR, exist_ok=True)
    failures = []
    for arch in ARCHS:
        for shape in SHAPES:
            for mp in (False, True):
                path = _cell_path(arch, shape, mp)
                if os.path.exists(path) and not force:
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", path]
                if mp:
                    cmd.append("--multi-pod")
                r = subprocess.run(cmd, timeout=timeout,
                                   capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append((arch, shape, mp, r.stdout[-2000:] +
                                     r.stderr[-2000:]))
                    print(f"[dryrun] FAIL {arch} x {shape} mp={mp}")
                    print(r.stderr[-2000:])
                else:
                    print(r.stdout.strip().splitlines()[-2]
                          if r.stdout.strip() else "")
    print(f"[dryrun] done, {len(failures)} failures")
    for a, s, mp, _ in failures:
        print("  FAIL:", a, s, "multi_pod" if mp else "single")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()
    if args.all:
        failures = run_all(force=args.force)
        sys.exit(1 if failures else 0)
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
    else:
        print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
