"""Autotune the CIM GEMM block schedules for an architecture's decode
shapes and persist the winners to benchmarks/TUNING_CACHE.json.

  PYTHONPATH=src python -m benchmarks.autotune --arch minicpm-2b --smoke

The search times the full prepacked serving op per candidate block (see
repro.kernels.ccim_matmul.autotune), so the cache reflects the decode hot
path end to end.  ops.py / ccim.py consult the cache at trace time: the
serve loop and the continuous-batching scheduler pick tuned blocks when
their executables are built and never recompile across steps.  Every
candidate is bit-identical (int32 partial sums), so a stale or missing
cache only costs speed -- CI uploads the file as an artifact.
"""
import argparse
import os
import sys

try:
    from .common import emit
except ImportError:   # direct script execution (python benchmarks/autotune.py)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import emit


def decode_shapes(arch: str, smoke: bool = True, batches=(2, 4)) -> list:
    """The (M, K, N) GEMMs one decode step of ``arch`` actually runs,
    fused projection groups included (models.layers._dense_group)."""
    from repro.configs import get_config
    cfg = get_config(arch, smoke=smoke)
    D = cfg.d_model
    shapes = set()
    for B in batches:
        # hybrid (zamba2) runs BOTH: mamba layers plus a shared attn+mlp
        # block, so it collects the attention/MLP shapes too
        if cfg.family in ("dense", "moe", "vlm", "audio", "hybrid"):
            dh, hq, hkv = cfg.head_dim, cfg.padded_heads, cfg.padded_kv_heads
            shapes.add((B, D, (hq + 2 * hkv) * dh))   # fused QKV
            shapes.add((B, hq * dh, D))               # wo
            if cfg.d_ff:
                shapes.add((B, D, 2 * cfg.d_ff))      # fused gate/up
                shapes.add((B, cfg.d_ff, D))          # w2
        if cfg.family in ("ssm", "hybrid"):
            DI, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
            shapes.add((B, D, 2 * DI + 2 * N + H))    # fused w_z/w_x/w_bc/w_dt
            shapes.add((B, DI, D))                    # out_proj
    return sorted(shapes)


def run(arch: str = "minicpm-2b", smoke: bool = True, batches=(2, 4),
        iters: int = 5) -> str:
    from repro.kernels.ccim_matmul import autotune

    shapes = decode_shapes(arch, smoke, batches)
    shapes.append((4, 1024, 256))   # the kernel-bench decode reference shape
    results = autotune.autotune_shapes(shapes, iters=iters)
    for name, entry in results.items():
        detail = (f"chunk_block {entry['chunk_block']}"
                  if "chunk_block" in entry
                  else f"bn {entry['bn']} bk {entry['bk']}")
        emit(f"tune.{name}", entry["us"], detail)
    path = autotune.save()
    print(f"# wrote {path} ({len(results)} entries, arch {arch})")
    print(f"# {autotune.cache_summary()}")
    return path


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batches", type=int, nargs="+", default=[2, 4])
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    run(args.arch, args.smoke, tuple(args.batches), args.iters)


if __name__ == "__main__":
    main()
