#!/usr/bin/env python3
"""Rehearse the benchmark without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--cells ...] [--no-run] [--no-compile]

1. Runs every cell of BENCHMARK.json at its configuration's smoke size
   on the CPU (``run.py --rehearse``): paths, traffic, the scheduler's
   paged loop and the check, end to end.
2. Compiles, for a described TPU v5e and at each configuration's full
   size, the decode step at the cell's slots, the prefill chunk at its
   chunk size, and the whole serve loop at the cell's queue length, and
   prints each program's ``memory_analysis()``: what the chip's
   compiler would refuse, and whether the packed weights, the paged pool
   and the loop's carry fit one chip.  Nothing runs; no time is measured.

The program picks its Pallas routes from ``jax.default_backend()``; for
the compile this script answers "tpu", so the kernels are compiled as
the chip runs them.  The persistent compile cache is off meanwhile: a
compile for a described chip cannot be read back without one.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench  # noqa: E402

GIB = 1 << 30


def rehearse_cell(cell: str) -> dict:
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench.main(["--workload", cell, "--seed", "7", "--seconds", "1",
                         "--rehearse"])
    out = json.loads(buf.getvalue().strip().splitlines()[-1])
    print(f"rehearse {cell}: rc {rc}, correct {out['correct']}, "
          f"{out['attempted']} requests, checks {out['checks']}", flush=True)
    return out


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"args {m.argument_size_in_bytes / GIB:.3f} GiB, out "
            f"{m.output_size_in_bytes / GIB:.3f} GiB, temp "
            f"{m.temp_size_in_bytes / GIB:.3f} GiB, alias "
            f"{m.alias_size_in_bytes / GIB:.3f} GiB")


def compile_cell(cell: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    sys.path.insert(0, os.path.join(bench.REPO, "src"))
    from repro.launch.paging import PagedLayout
    from repro.launch.scheduler import ContinuousBatchingScheduler
    from repro.models import lm
    from repro.models.config import ModelConfig

    import weights

    spec = bench.load_spec(bench.REPO, cell, rehearse=False)
    model, sv = spec["config"]["model"], spec["config"]["serving"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
    cfg = ModelConfig(**model, cim_mode=True)
    slots, C = sv["slots"], sv["prefill_chunk"]
    layout, traffic = bench.plan(spec, 0)
    layout, q = PagedLayout(**layout), traffic.q

    real_backend = jax.default_backend
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.default_backend = lambda: "tpu"
    try:
        params = on_chip(jax.eval_shape(
            lambda: lm.pack_cim_params(
                weights.make_params(spec["arch"], model, 0), cfg)))
        cache = on_chip(jax.eval_shape(
            lambda: lm.init_paged_cache(cfg, slots, layout.n_blocks,
                                        layout.block_size, layout.n_tbl)))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
        live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=chip)
        dec = jax.jit(lambda p, t, c, l: lm.decode_step(p, cfg, t, c, l)
                      ).lower(params, i32(slots, 1), cache, live).compile()
        n_k = dec.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"compile {cell}: decode step, {slots} slots: {n_k} kernel "
              f"calls; {_mem(dec)}", flush=True)
        pf = jax.jit(lambda p, t, c, s: lm.prefill_chunk_into_slot(
            p, cfg, t, c, s)).lower(params, i32(1, C), cache, i32()).compile()
        print(f"compile {cell}: prefill chunk of {C}: {_mem(pf)}", flush=True)
        sched = ContinuousBatchingScheduler(
            params, cfg, slots=slots, prompt_len=sv["prompt_cap"],
            max_new_cap=sv["max_new_cap"], paged=layout, prefill_chunk=C)
        real_carry = sched._init_carry
        sched._init_carry = lambda *a, **k: on_chip(
            jax.eval_shape(lambda: real_carry(*a, **k)))
        loop = sched._lower_loop(q).compile()
        print(f"compile {cell}: serve loop, Q={q}, {layout.n_blocks} "
              f"blocks: {_mem(loop)}", flush=True)
    finally:
        jax.default_backend = real_backend
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", nargs="*")
    ap.add_argument("--no-run", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args(argv)
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        cells = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    ok = True
    if not args.no_run:
        for cell in cells:
            ok &= rehearse_cell(cell)["correct"]
    if not args.no_compile:
        for cell in cells:
            compile_cell(cell)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
