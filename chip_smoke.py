"""Bring-up smoke run of the packed-CIM serving path on one TPU.

    python chip_smoke.py

One process, which holds the chip throughout; it starts no other.

Phase A  mamba2-130m at its published CONFIG (full width and depth),
         served by ``launch.serve.serve_continuous``: pack, the
         continuous-batching scheduler, the paged cache and its
         paged-vs-contiguous token-parity assert.
Phase B  minicpm-2b at CONFIG widths with ``n_layers`` cut 40 -> 8 (the
         pack keeps ~17 B per weight, so 40 layers would need ~46 GB of
         HBM), served through the same pack, scheduler and paged path.
         This adds fused QKV, the flash prefill scan and the
         paged-attention kernel.

Both phases serve 8 requests over 4 slots, with 128-token prompts and
16-64 new tokens each, and then check that:
  * the Pallas kernels equal the XLA fast path (``==``) on a real-width
    packed projection, for the decode route (M=4) and the prefill route
    (M=128);
  * the logits of a prefill forward are finite;
  * the compiled decode step calls a Pallas kernel (``tpu_custom_call``),
    so a kernel quietly replaced by XLA fails the run.

Per-phase seconds and tok/s are printed for information: this is a
bring-up check, not a benchmark.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
With no TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SLOTS = 4
N_REQUESTS = 8
PROMPT_LEN = 128
STOP_LENGTHS = (16, 64, 32, 48)
BLOCK_SIZE = 16
MINICPM_LAYERS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX sees platform {dev.platform!r}); "
            "this smoke run needs one TPU chip")
    return dev


def paged_layout():
    from repro.launch.paging import PagedLayout, cdiv
    n_tbl = cdiv(PROMPT_LEN + max(STOP_LENGTHS), BLOCK_SIZE)
    return PagedLayout(block_size=BLOCK_SIZE, n_tbl=n_tbl,
                       n_blocks=SLOTS * n_tbl + 1)


def check_kernel_routes(name: str, K: int, N: int, seed: int = 0) -> None:
    """Pallas == XLA fast path on one real-width packed projection, for
    the decode (skinny) and prefill (general) kernel routes."""
    import jax
    import numpy as np
    from repro.core.engine import pack_cim_weights, packed_cim_matmul_int

    kw, kx = jax.random.split(jax.random.PRNGKey(seed))
    packed = pack_cim_weights(0.02 * jax.random.normal(kw, (K, N)))
    run = jax.jit(lambda x, p, pallas: packed_cim_matmul_int(
        x, p, use_pallas=pallas), static_argnums=2)
    for M in (4, 128):
        xq = jax.random.randint(jax.random.fold_in(kx, M), (M, K), -127, 128)
        if "tpu_custom_call" not in run.lower(xq, packed, True).compile(
                ).as_text():
            raise AssertionError(f"{name} M={M}: no Pallas kernel compiled")
        got = np.asarray(run(xq, packed, True))
        want = np.asarray(run(xq, packed, False))
        if not np.array_equal(got, want):
            raise AssertionError(
                f"{name} M={M}: Pallas differs from the XLA fast path at "
                f"{int((got != want).sum())} of {got.size} outputs")
        log(f"check: {name} K={K} N={N} M={M} Pallas == XLA fast path: pass")


def check_model(cfg, params) -> None:
    """Finite prefill logits, and a Pallas kernel in the decode step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, PROMPT_LEN), 0,
                                cfg.vocab_size)
    logits, _ = jax.jit(lambda p, t: lm.forward(p, cfg, t))(params, tokens)
    logits = np.asarray(logits)
    if logits.shape != (1, PROMPT_LEN, cfg.vocab_size):
        raise AssertionError(f"logits shape {logits.shape}")
    if not np.isfinite(logits).all():
        raise AssertionError(f"{(~np.isfinite(logits)).sum()} non-finite "
                             "logits")
    log(f"check: {cfg.name} prefill logits {logits.shape} finite: pass")

    layout = paged_layout()
    cache = lm.init_paged_cache(cfg, SLOTS, layout.n_blocks,
                                layout.block_size, layout.n_tbl)
    step = jax.jit(lambda p, t, c, live: lm.decode_step(p, cfg, t, c, live))
    hlo = step.lower(params, jnp.zeros((SLOTS, 1), jnp.int32), cache,
                     jnp.ones((SLOTS,), bool)).compile().as_text()
    n = hlo.count("custom_call_target=\"tpu_custom_call\"")
    if n == 0:
        raise AssertionError(f"{cfg.name}: compiled decode step has no "
                             "tpu_custom_call (a kernel fell back to XLA)")
    log(f"check: {cfg.name} compiled decode step has {n} tpu_custom_call "
        "sites: pass")


def check_tokens(report, requests, vocab: int) -> None:
    got = report.tokens_by_rid()
    for r in requests:
        toks = got[r.rid]
        if len(toks) != r.max_new_tokens or not ((0 <= toks) &
                                                 (toks < vocab)).all():
            raise AssertionError(f"request {r.rid}: {len(toks)} tokens "
                                 f"(want {r.max_new_tokens}) or out of vocab")


def phase_a(smoke: bool = False) -> None:
    """mamba2-130m at CONFIG through ``serve_continuous``."""
    import jax
    from repro.configs import get_config
    from repro.launch.serve import serve_continuous
    from repro.models import lm

    t0 = time.time()
    _, stats = serve_continuous(
        "mamba2-130m", smoke=smoke, slots=SLOTS, prompt_len=PROMPT_LEN,
        n_requests=N_REQUESTS, stop_lengths=STOP_LENGTHS, cim=True,
        paged=paged_layout())
    if not stats.get("tokens_match_contiguous"):
        raise AssertionError("paged-vs-contiguous parity did not run")
    cont = stats["continuous"]
    log(f"phase A: pack {stats['pack_s']} s, compile {stats['compile_s']} s, "
        f"workload {cont['wall_s']} s, {cont['tok_s']} tok/s "
        f"({cont['total_tokens']} tokens); phase total "
        f"{time.time() - t0:.1f} s")
    log("check: mamba2-130m paged tokens == contiguous tokens: pass")

    check_kernel_routes("mamba2-130m in_proj", 768, 3352)
    cfg = dataclasses.replace(get_config("mamba2-130m", smoke=smoke),
                              cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    check_model(cfg, jax.block_until_ready(lm.pack_cim_params(params, cfg)))


def phase_b(smoke: bool = False, n_layers: int = MINICPM_LAYERS) -> None:
    """minicpm-2b at CONFIG widths, depth cut to fit one chip."""
    import jax
    from repro.configs import get_config
    from repro.launch.scheduler import (ContinuousBatchingScheduler,
                                        mixed_length_requests)
    from repro.models import lm

    full = get_config("minicpm-2b", smoke=smoke)
    cfg = dataclasses.replace(full, n_layers=n_layers, cim_mode=True)
    log(f"reduced: n_layers {full.n_layers}->{n_layers} "
        "(packed leaves ~17 B/weight)")
    t0 = time.time()
    raw, _ = lm.init(jax.random.PRNGKey(0), cfg)
    params = jax.block_until_ready(lm.pack_cim_params(raw, cfg))
    del raw
    t_pack = time.time() - t0

    t0 = time.time()
    sched = ContinuousBatchingScheduler(
        params, cfg, slots=SLOTS, prompt_len=PROMPT_LEN,
        max_new_cap=max(STOP_LENGTHS), paged=paged_layout())
    sched.compile_for(N_REQUESTS)
    t_compile = time.time() - t0
    requests = mixed_length_requests(N_REQUESTS, PROMPT_LEN, cfg.vocab_size,
                                     stop_lengths=STOP_LENGTHS)
    report = sched.run(requests)
    check_tokens(report, requests, cfg.vocab_size)
    log(f"phase B: pack {t_pack:.1f} s, compile {t_compile:.1f} s, workload "
        f"{report.wall_s:.3f} s, {report.tok_s:.1f} tok/s "
        f"({report.total_tokens} tokens)")
    log(f"check: minicpm-2b {N_REQUESTS} requests served to their lengths: "
        "pass")

    check_kernel_routes("minicpm-2b qkv", 2304, 9216)
    check_model(cfg, params)


def main() -> int:
    dev = require_tpu()
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    for name, phase in (("A", phase_a), ("B", phase_b)):
        t0 = time.time()
        phase()
        log(f"phase {name} done in {time.time() - t0:.1f} s")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device peak bytes in use: {stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
