"""Serving drivers: fixed-batch lock-step loop + continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --batch 4 --prompt-len 32 --gen 16 [--cim] [--no-pack] [--continuous]

``serve`` is the LOCK-STEP driver: one fixed batch is prefilled together
and decodes in lock step for exactly ``gen`` tokens -- there is no
per-request stop handling here, and a short request occupies its slot
until the whole batch ends.  It is the baseline the continuous-batching
scheduler (launch/scheduler.py, ``serve_continuous`` below) is measured
against: the scheduler tracks per-slot EOS/max-new-tokens on device and
refills freed slots from a request queue mid-stream.

Serving dataflow under --cim (weight-stationary, like the silicon):

  pack     : every projection is quantized + bit-plane-decomposed ONCE
             (lm.pack_cim_params), off the token loop -- the array write.
  prefill  : one batched forward over the prompt fills the KV cache.
  decode   : activation-only quantization per token; generated tokens are
             collected ON DEVICE and transferred once at the end.

``--no-pack`` keeps the legacy per-call weight conditioning -- the
pre-refactor baseline benchmarks compare against; tokens are bit-identical
either way.  Exercised by tests/test_train_serve.py,
tests/test_scheduler.py, tests/test_engine.py and examples/cim_serve.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config
from ..data import DataConfig, batch_at
from ..models import lm
from ..obs import REGISTRY, ObsConfig, get_tracer, set_trace_path, span
from .compile_cache import enable_compile_cache
from .paging import PagedLayout
from .scheduler import (ContinuousBatchingScheduler, mixed_length_requests,
                        sampling_key)


def serve(arch: str, smoke: bool = True, batch: int = 4, prompt_len: int = 32,
          gen: int = 16, cim: bool = False, temperature: float = 0.0,
          seed: int = 0, pack: bool = True, return_stats: bool = False,
          plan=None, noise_seed=None, fuse: bool = True,
          metrics: bool = False):
    """Returns generated tokens (batch, gen); with ``return_stats=True``,
    returns (tokens, stats) where stats separates compile / pack /
    prefill / decode time -- prefill and decode steps are AOT-compiled up
    front, so every throughput number is pure execution.

    ``metrics=True`` records pack/compile/prefill/decode spans through
    the obs tracer (obs/trace.py), publishes the run's totals into the
    process metrics registry (``repro.obs.REGISTRY`` -- Prometheus text
    via ``export_prometheus()``), and attaches the registry snapshot as
    ``stats["metrics"]``.  The lock-step driver has no device rings --
    those are a scheduler feature (``serve_continuous(metrics=True)``).

    ``plan`` (a repro.plan.DeploymentPlan) serves each projection under
    its own macro config/fidelity (implies cim); plans are static, so the
    AOT-compiled prefill/decode executables serve the mixed-fidelity model
    with zero recompiles.  ``noise_seed`` turns on deterministic analog-
    noise emulation (cfg.cim_noise_seed) -- packed and unpacked serving
    stay bit-identical under it.  ``fuse`` (default on) enables horizontal
    projection fusion (cfg.cim_fuse): plan-compatible QKV / gate-up /
    mamba-input projections execute as one wide macro GEMM each, tokens
    bit-identical to the unfused path (``fuse=False`` is the A/B baseline).
    """
    if metrics:
        get_tracer().record = True  # stats["spans"]: this call's spans
    cfg = get_config(arch, smoke=smoke)
    if plan is not None:
        cim = True
        cfg = dataclasses.replace(cfg, cim_plan=plan)
    if not fuse:
        cfg = dataclasses.replace(cfg, cim_fuse=False)
    if noise_seed is not None:
        if not cim:
            raise ValueError(
                "noise_seed emulates the macro's analog noise and needs "
                "cim=True (or a plan); without it serving would silently "
                "run noise-free")
        cfg = dataclasses.replace(cfg, cim_noise_seed=noise_seed)
    if cim:
        cfg = dataclasses.replace(cfg, cim_mode=True)
    pack = pack and cim
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=prompt_len,
                      global_batch=batch, seed=seed,
                      n_frontend_tokens=cfg.n_frontend_tokens
                      if cfg.family == "vlm" else 0,
                      d_model=cfg.d_model)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    # sampling draws from its own stream -- the decode loop used to split
    # the params-init key, so init and sampling consumed the same PRNG
    # stream (regression-tested in tests/test_scheduler.py)
    skey = sampling_key(seed)
    b = batch_at(dcfg, 0)
    tokens = jnp.asarray(b["tokens"])
    fe = (jnp.asarray(b["frontend_embs"]).astype(jnp.bfloat16)
          if "frontend_embs" in b else None)

    t_pack = 0.0
    if pack:
        t0 = time.time()
        # pack_cim_params is jit-compiled internally (eager == jit packs
        # are bit-identical); under a plan each projection packs for its
        # own entry's macro config
        with span("serve.pack", arch=arch):
            params = jax.block_until_ready(lm.pack_cim_params(params, cfg))
        t_pack = time.time() - t0

    n_frontend = fe.shape[1] if fe is not None else 0
    max_seq = prompt_len + gen + n_frontend
    cache = lm.init_cache(cfg, batch, max_seq)
    # AOT-compile both steps so every reported time is pure execution
    # (trace+compile otherwise dominates prefill_s at smoke scale and any
    # PR touching compile time would show a phantom throughput change);
    # lowering with the pre-prefill cache is sound -- cache shapes are
    # static across the whole generation.
    t0 = time.time()
    with span("serve.compile", arch=arch):
        prefill = jax.jit(lambda p, t, c, f: lm.prefill(p, cfg, t, c, f),
                          donate_argnums=(2,)
                          ).lower(params, tokens, cache, fe).compile()
        tok0 = jnp.zeros((batch, 1), jnp.int32)
        decode = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c),
                         donate_argnums=(2,)
                         ).lower(params, tok0, cache).compile()
    t_compile = time.time() - t0

    def sample(logits):
        """One token per row: greedy at temperature 0, else categorical.
        The key split happens only when sampling -- a greedy run must not
        pay per-token split dispatches inside the timed decode loop."""
        nonlocal skey
        if temperature > 0:
            skey, sub = jax.random.split(skey)
            return jax.random.categorical(
                sub, logits[:, -1] / temperature)[:, None].astype(jnp.int32)
        return jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)

    t0 = time.time()
    with span("serve.prefill", arch=arch):
        logits, cache = prefill(params, tokens, cache, fe)
        # the first generated token goes through the same sampler as the
        # rest (it used to be unconditionally greedy while later tokens
        # sampled)
        tok = sample(logits)
        tok.block_until_ready()
    t_prefill = time.time() - t0

    out = [tok]                      # device-side; one transfer at the end
    t0 = time.time()
    with span("serve.decode", arch=arch, steps=gen - 1):
        for i in range(gen - 1):
            logits, cache = decode(params, tok, cache)
            tok = sample(logits)
            out.append(tok)
        gen_tokens = np.asarray(jnp.concatenate(out, axis=1))
    t_decode = time.time() - t0

    decode_steps = gen - 1
    decode_tok_s = (batch * decode_steps / t_decode
                    if decode_steps and t_decode > 0 else float("nan"))
    # the prefill forward covers frontend embeddings too (vlm prepends
    # n_frontend_tokens) -- count the true prefill length, not just text
    prefill_len = prompt_len + n_frontend
    stats = dict(
        arch=arch, batch=batch, prompt_len=prompt_len, gen=gen,
        cim=cim, packed=pack,
        compile_s=round(t_compile, 4),
        pack_s=round(t_pack, 4),
        prefill_s=round(t_prefill, 4),
        decode_s=round(t_decode, 4),
        decode_tok_s=round(decode_tok_s, 2),
        prefill_tok_s=round(batch * prefill_len / t_prefill, 2)
        if t_prefill > 0 else float("nan"),
    )
    if metrics:
        REGISTRY.counter(
            "serve_tokens_total",
            "tokens emitted by the serving drivers").inc(batch * gen)
        REGISTRY.gauge("serve_decode_tok_s",
                       "lock-step decode throughput").set(decode_tok_s)
        REGISTRY.histogram(
            "serve_decode_step_seconds", "mean decode-step latency",
            buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0)).observe_many(
            [t_decode / decode_steps] * decode_steps if decode_steps else [])
        stats["metrics"] = REGISTRY.snapshot()
        stats["spans"] = get_tracer().drain()
        get_tracer().record = False
    mode = ("cim-packed" if pack else "cim-unpacked") if cim else "fp"
    print(f"[serve] {arch} ({mode}): batch {batch}, prompt {prompt_len}, "
          f"gen {gen} | compile {t_compile:.2f}s, pack {t_pack:.2f}s, "
          f"prefill {t_prefill:.2f}s, decode {t_decode:.2f}s "
          f"({decode_tok_s:.1f} tok/s)")
    if return_stats:
        return gen_tokens, stats
    return gen_tokens


def serve_speculative(arch: str, smoke: bool = True, batch: int = 2,
                      prompt_len: int = 16, gen: int = 48, draft_k: int = 8,
                      draft_adc_bits=None, draft_plan=None,
                      temperature: float = 0.0, seed: int = 0, plan=None,
                      cim: bool = True, pack: bool = True, fuse: bool = True,
                      compare_baseline: bool = True,
                      return_stats: bool = False, metrics: bool = False):
    """Plan-cascade speculative lock-step driver: ONE AOT dispatch per
    draft/verify ROUND instead of one per token.

    The draft plan (``plan.draft_plan_for_model``: the all-analog shadow
    of the serving plan, or ``draft_plan`` verbatim) serves from the SAME
    packed weights as the verify plan -- no second pack, no recompiles.
    Each round drafts ``draft_k`` tokens under the draft config, rolls the
    cache positions back, verifies all k+1 positions in one wide skinny-M
    forward under the deployed config, and accepts the longest agreeing
    prefix plus a correction token.  Because the whole round is one
    executable, the per-dispatch overhead that dominates ``serve``'s
    decode loop at smoke scale is amortized over every accepted token --
    that, plus the analog draft skipping the DCIM plane dot, is the
    speedup.

    Greedy output is bit-identical to ``serve`` (asserted when
    ``compare_baseline``); temperature>0 uses standard rejection sampling,
    so it matches the verify model in distribution (not bitwise -- the
    baseline consumes its key stream once per token, this driver once per
    draft/uniform/resample event).

    Returns tokens (batch, gen); with ``return_stats=True``, (tokens,
    stats) including acceptance_rate, tokens_per_round and (when
    ``compare_baseline``) ``decode_speedup_speculative``.
    """
    if metrics:
        get_tracer().record = True  # stats["spans"]: this call's spans
    cfg = get_config(arch, smoke=smoke)
    if plan is not None:
        cim = True
        cfg = dataclasses.replace(cfg, cim_plan=plan)
    if not fuse:
        cfg = dataclasses.replace(cfg, cim_fuse=False)
    if cim:
        cfg = dataclasses.replace(cfg, cim_mode=True)
    pack = pack and cim
    if draft_plan is None:
        from ..plan import draft_plan_for_model
        draft_plan = draft_plan_for_model(cfg, draft_adc_bits)
    dcfg = dataclasses.replace(cfg, cim_plan=draft_plan) if cim else cfg
    K = draft_k

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=prompt_len,
                    global_batch=batch, seed=seed, d_model=cfg.d_model)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    tokens = jnp.asarray(batch_at(dc, 0)["tokens"])
    t_pack = 0.0
    if pack:
        t0 = time.time()
        params = jax.block_until_ready(lm.pack_cim_params(params, cfg))
        t_pack = time.time() - t0

    # live rows can overshoot the target by up to one block per round and
    # verify probes K rows past the frontier -- size the cache for both
    cache = lm.init_cache(cfg, batch, prompt_len + gen + 2 * K + 1)

    def round_fn(params, last0, cache, key, live):
        pos0 = cache["pos"]
        last, d_toks, d_logits = last0, [], []
        for _ in range(K):
            logits, cache = lm.decode_step(params, dcfg, last, cache,
                                           live=live)
            key, sub = jax.random.split(key)
            if temperature > 0:
                tok = jax.random.categorical(
                    sub, logits[:, -1] / temperature).astype(jnp.int32)
                d_logits.append(logits[:, -1])
            else:
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            d_toks.append(tok)
            last = tok[:, None]
        drafts = jnp.stack(d_toks, axis=1)                  # (B, K)
        vtoks = jnp.concatenate([last0, drafts], axis=1)    # (B, K+1)
        cache = dict(cache, pos=pos0)                       # rollback
        vlogits, cache = lm.verify_step(params, cfg, vtoks, cache)
        cand = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)
        if temperature <= 0:
            v_arg = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            match = (v_arg[:, :K] == drafts).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            corr = v_arg
        else:
            dlg = jnp.stack(d_logits, axis=1)
            p_d = jax.nn.softmax(dlg / temperature, axis=-1)
            p_v = jax.nn.softmax(vlogits / temperature, axis=-1)
            pd_tok = jnp.take_along_axis(p_d, drafts[..., None], -1)[..., 0]
            pv_tok = jnp.take_along_axis(
                p_v[:, :K], drafts[..., None], -1)[..., 0]
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, drafts.shape)
            acc = (u * pd_tok < pv_tok).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
            pv_n = jnp.take_along_axis(p_v, n_acc[:, None, None], 1)[:, 0]
            pd_ext = jnp.concatenate(
                [p_d, jnp.zeros_like(p_d[:, :1])], axis=1)
            pd_n = jnp.take_along_axis(pd_ext, n_acc[:, None, None], 1)[:, 0]
            res = jnp.maximum(pv_n - pd_n, 0.0)
            tot = jnp.sum(res, axis=-1, keepdims=True)
            res = jnp.where(tot > 0, res / jnp.maximum(tot, 1e-38), pv_n)
            key, sub = jax.random.split(key)
            corr = jax.random.categorical(
                sub, jnp.log(jnp.maximum(res, 1e-38)))[:, None].astype(
                jnp.int32)
        cols = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
        emitted = jnp.where(cols == n_acc[:, None], corr, cand)
        n_emit = jnp.where(live, n_acc + 1, 0)
        new_last = jnp.where(
            live[:, None],
            jnp.take_along_axis(emitted, n_acc[:, None], axis=1), last0)
        cache = dict(cache, pos=pos0 + n_emit)
        return emitted, n_emit, new_last, cache, key

    t0 = time.time()
    prefill = jax.jit(lambda p, t, c: lm.prefill(p, cfg, t, c),
                      donate_argnums=(2,)
                      ).lower(params, tokens, cache).compile()
    tok0 = jnp.zeros((batch, 1), jnp.int32)
    key0 = sampling_key(seed)
    live0 = jnp.ones((batch,), jnp.bool_)
    round_exe = jax.jit(round_fn, donate_argnums=(2,)).lower(
        params, tok0, cache, key0, live0).compile()
    t_compile = time.time() - t0

    t0 = time.time()
    logits, cache = prefill(params, tokens, cache)
    key = key0
    if temperature > 0:
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(
            sub, logits[:, -1] / temperature)[:, None].astype(jnp.int32)
    else:
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    tok.block_until_ready()
    t_prefill = time.time() - t0

    target = gen - 1                      # decode tokens after the first
    first = np.asarray(tok)[:, 0]
    rows = [[int(t)] for t in first]
    counts = np.zeros(batch, np.int64)
    n_rounds = n_drafted = n_accepted = 0
    t0 = time.time()
    while counts.min() < target:
        live = jnp.asarray(counts < target)
        emitted, n_emit, tok, cache, key = round_exe(
            params, tok, cache, key, live)
        em, ne = np.asarray(emitted), np.asarray(n_emit)
        for b in range(batch):
            rows[b].extend(em[b, :ne[b]].tolist())
        n_drafted += K * int((counts < target).sum())
        n_accepted += int(np.maximum(ne - 1, 0).sum())
        counts += ne
        n_rounds += 1
    t_decode = time.time() - t0
    gen_tokens = np.asarray([r[:gen] for r in rows], dtype=np.int64)

    decode_tok_s = (batch * target / t_decode if t_decode > 0
                    else float("nan"))
    stats = dict(
        arch=arch, batch=batch, prompt_len=prompt_len, gen=gen,
        cim=cim, packed=pack, draft_k=K,
        draft_plan=draft_plan.summary()["<default>"],
        compile_s=round(t_compile, 4), pack_s=round(t_pack, 4),
        prefill_s=round(t_prefill, 4), decode_s=round(t_decode, 4),
        decode_tok_s=round(decode_tok_s, 2), n_rounds=n_rounds,
        n_drafted=n_drafted, n_accepted=n_accepted,
        acceptance_rate=round(n_accepted / n_drafted, 4) if n_drafted
        else float("nan"),
        tokens_per_round=round(batch * target / n_rounds, 2) if n_rounds
        else float("nan"),
    )
    if metrics:
        REGISTRY.counter("serve_tokens_total",
                         "tokens emitted by the serving drivers").inc(
            batch * gen)
        REGISTRY.counter("serve_drafted_total",
                         "speculative draft tokens proposed").inc(n_drafted)
        REGISTRY.counter("serve_accepted_total",
                         "speculative draft tokens accepted").inc(n_accepted)
        REGISTRY.gauge("serve_decode_tok_s",
                       "lock-step decode throughput").set(decode_tok_s)
        stats["metrics"] = REGISTRY.snapshot()
        stats["spans"] = get_tracer().drain()
        get_tracer().record = False
    print(f"[serve-spec] {arch} (k={K}, draft {stats['draft_plan']}): "
          f"batch {batch}, gen {gen} | decode {t_decode:.2f}s "
          f"({decode_tok_s:.1f} tok/s), acceptance "
          f"{stats['acceptance_rate']:.0%}")
    if compare_baseline:
        base_toks, base = serve(arch, smoke=smoke, batch=batch,
                                prompt_len=prompt_len, gen=gen, cim=cim,
                                temperature=temperature, seed=seed,
                                pack=pack, return_stats=True, plan=plan,
                                fuse=fuse)
        if temperature <= 0:
            np.testing.assert_array_equal(
                gen_tokens, base_toks,
                err_msg="speculative greedy decode changed tokens vs the "
                        "non-speculative baseline")
            stats["tokens_match_baseline"] = True
        stats["baseline_decode_tok_s"] = base["decode_tok_s"]
        stats["decode_speedup_speculative"] = round(
            decode_tok_s / base["decode_tok_s"], 2)
        print(f"[serve-spec] speedup vs non-speculative: "
              f"{stats['decode_speedup_speculative']:.2f}x"
              + (" (tokens identical)" if temperature <= 0 else ""))
    if return_stats:
        return gen_tokens, stats
    return gen_tokens


def serve_continuous(arch: str, smoke: bool = True, slots: int = 2,
                     prompt_len: int = 16, n_requests: int = 8,
                     stop_lengths=(4, 16, 8, 12), cim: bool = False,
                     pack: bool = True, temperature: float = 0.0,
                     seed: int = 0, compare_lockstep: bool = True,
                     repeats: int = 1, plan=None, fuse: bool = True,
                     draft_k: int = 0, draft_plan=None, draft_adc_bits=None,
                     paged: PagedLayout | None = None,
                     prefill_chunk: int | None = None,
                     prefix_sharing: bool = True,
                     adaptive_draft_k: bool = False,
                     metrics: bool | ObsConfig = False):
    """Continuous-batching driver: a mixed-length request queue served
    from a fixed pool of ``slots`` decode slots (launch/scheduler.py).

    Returns (tokens_by_rid, stats).  With ``compare_lockstep=True`` the
    same requests also run through the lock-step wave baseline on the SAME
    compiled executables and the per-request tokens are asserted
    bit-identical -- the scheduler may only reorder work, never change it.
    ``repeats`` reruns both drivers and keeps each one's best run for the
    headline numbers plus the per-run median (``tok_s_median``) for stable
    ratios -- host scheduler noise at smoke scale otherwise swamps any
    single-draw comparison.  ``plan`` serves a mixed-fidelity
    DeploymentPlan through the unchanged scheduler (implies cim).

    ``draft_k > 0`` turns on plan-cascade speculative rounds in the
    scheduler (``draft_plan`` or the derived all-analog shadow of the
    serving plan, same packed weights).  Greedy tokens stay bit-identical
    to the non-speculative lock-step baseline, so the parity assert is
    kept; at temperature > 0 speculative sampling is only
    distribution-identical and the lock-step comparison is skipped.

    ``metrics`` (True or an ObsConfig) compiles the scheduler's device-
    resident telemetry rings into the serve loop (launch/scheduler.py):
    the harvested snapshot lands in ``stats["telemetry"]`` and the
    process registry (``repro.obs.REGISTRY``), and the pack/compile/
    workload phases are span-traced.  Tokens are bit-identical with
    metrics on or off -- the rings only read values the loop already
    computes.
    """
    obs = (metrics if isinstance(metrics, ObsConfig)
           else (ObsConfig() if metrics else None))
    if obs is not None:
        get_tracer().record = True  # stats["spans"]: this call's spans
    if draft_k and temperature > 0:
        compare_lockstep = False
    compare_contiguous = paged is not None and compare_lockstep
    if paged is not None:
        compare_lockstep = False    # lock-step baseline is contiguous-only
    cfg = get_config(arch, smoke=smoke)
    if plan is not None:
        cim = True
        cfg = dataclasses.replace(cfg, cim_plan=plan)
    if not fuse:
        cfg = dataclasses.replace(cfg, cim_fuse=False)
    if cim:
        cfg = dataclasses.replace(cfg, cim_mode=True)
    pack = pack and cim
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    t_pack = 0.0
    if pack:
        t0 = time.time()
        with span("serve.pack", arch=arch):
            params = jax.block_until_ready(lm.pack_cim_params(params, cfg))
        t_pack = time.time() - t0

    if draft_k and draft_plan is None:
        from ..plan import draft_plan_for_model
        draft_plan = draft_plan_for_model(cfg, draft_adc_bits)

    requests = mixed_length_requests(n_requests, prompt_len, cfg.vocab_size,
                                     stop_lengths=stop_lengths, seed=seed)
    t0 = time.time()
    sched = ContinuousBatchingScheduler(
        params, cfg, slots=slots, prompt_len=prompt_len,
        max_new_cap=max(stop_lengths), temperature=temperature, seed=seed,
        draft_k=draft_k, draft_plan=draft_plan, paged=paged,
        prefill_chunk=prefill_chunk, prefix_sharing=prefix_sharing,
        adaptive_draft_k=adaptive_draft_k, obs=obs)
    with span("serve.compile", arch=arch, n_queue=n_requests):
        sched.compile_for(n_requests, lockstep=compare_lockstep)
    t_compile = time.time() - t0

    with span("serve.workload", arch=arch, n_requests=n_requests,
              repeats=repeats):
        runs = [sched.run(requests) for _ in range(repeats)]
    for other in runs[1:]:
        got, want = other.tokens_by_rid(), runs[0].tokens_by_rid()
        for rid in want:
            np.testing.assert_array_equal(got[rid], want[rid])
    report = max(runs, key=lambda r: r.tok_s)
    stats = dict(arch=arch, slots=slots, prompt_len=prompt_len,
                 n_requests=n_requests, stop_lengths=list(stop_lengths),
                 cim=cim, packed=pack, compile_s=round(t_compile, 4),
                 pack_s=round(t_pack, 4), repeats=repeats,
                 tok_s_median=round(
                     statistics.median(r.tok_s for r in runs), 2),
                 continuous=report.summary())
    if draft_k:
        stats["draft_k"] = draft_k
        stats["draft_plan"] = draft_plan.summary()["<default>"]
        if adaptive_draft_k:
            stats["adaptive_draft_k"] = True
    if paged is not None:
        stats["paged"] = dict(block_size=paged.block_size,
                              n_tbl=paged.n_tbl, n_blocks=paged.n_blocks,
                              prefill_chunk=sched.prefill_chunk,
                              prefix_sharing=prefix_sharing,
                              peak_blocks=report.peak_blocks,
                              kv_bytes_peak=sched.kv_bytes_paged(
                                  report.peak_blocks),
                              kv_bytes_contiguous=sched.kv_bytes_contiguous())
        plan = getattr(sched, "last_prefix_plan", None)
        if plan is not None:
            stats["paged"]["prefix_plan"] = plan.stats()
    if compare_contiguous:
        # paged vs contiguous parity: the paged pool may only change WHERE
        # KV rows live, never a single token
        ref = ContinuousBatchingScheduler(
            params, cfg, slots=slots, prompt_len=prompt_len,
            max_new_cap=max(stop_lengths), temperature=temperature,
            seed=seed, draft_k=draft_k, draft_plan=draft_plan)
        got, want = report.tokens_by_rid(), ref.run(requests).tokens_by_rid()
        for rid in want:
            np.testing.assert_array_equal(
                got[rid], want[rid],
                err_msg=f"request {rid}: paged KV changed tokens vs the "
                        "contiguous scheduler")
        stats["tokens_match_contiguous"] = True
    if compare_lockstep:
        base_runs = [sched.run_lockstep(requests) for _ in range(repeats)]
        base = max(base_runs, key=lambda r: r.tok_s)
        got, want = report.tokens_by_rid(), base.tokens_by_rid()
        for rid in want:
            np.testing.assert_array_equal(
                got[rid], want[rid],
                err_msg=f"request {rid}: continuous batching changed tokens "
                        "vs the lock-step baseline")
        base_median = statistics.median(r.tok_s for r in base_runs)
        stats["lockstep"] = base.summary()
        stats["lockstep_tok_s_median"] = round(base_median, 2)
        stats["tokens_match_lockstep"] = True
        stats["speedup_vs_lockstep"] = round(
            stats["tok_s_median"] / base_median, 2) if base_median > 0 \
            else float("nan")
    if obs is not None and report.obs is not None:
        report.obs.register(REGISTRY)
        REGISTRY.gauge("serve_decode_tok_s",
                       "continuous-batching throughput").set(report.tok_s)
        stats["telemetry"] = report.obs.to_dict()
        stats["metrics"] = REGISTRY.snapshot()
        stats["spans"] = get_tracer().drain()
        get_tracer().record = False
    mode = ("cim-packed" if pack else "cim-unpacked") if cim else "fp"
    if draft_k:
        mode += f"+spec-k{draft_k}"
        if adaptive_draft_k:
            mode += "-adaptive"
    if paged is not None:
        mode += f"+paged-bs{paged.block_size}"
    line = (f"[serve-cb] {arch} ({mode}): {n_requests} reqs x "
            f"stops{tuple(stop_lengths)} over {slots} slots | "
            f"{report.tok_s:.1f} tok/s, occupancy {report.occupancy:.0%}")
    if compare_lockstep:
        line += (f" | lock-step {stats['lockstep']['tok_s']:.1f} tok/s "
                 f"({stats['speedup_vs_lockstep']:.2f}x, tokens identical)")
    print(line)
    return report.tokens_by_rid(), stats


def serve_guarded(arch: str, smoke: bool = True, slots: int = 2,
                  prompt_len: int = 16, n_requests: int = 8,
                  stop_lengths=(4, 16, 8, 12), temperature: float = 0.0,
                  seed: int = 0, plan=None, fuse: bool = True,
                  draft_k: int = 0, paged: PagedLayout | None = None,
                  prefill_chunk: int | None = None,
                  prefix_sharing: bool = True, fault=None, watchdog=True,
                  probe: bool = True, segment_iters: int = 8,
                  start_rung: int | None = None):
    """Watchdog-guarded continuous serving with plan-degradation failover.

    The chaos-engineering driver: the workload runs through
    ``resilience.failover.GuardedServer`` -- one pack-compatible
    scheduler per ladder rung over ONE packed weight set, executed as
    budget-bounded device-resident segments with health read at each
    segment boundary (ADC clip rate, speculative acceptance, golden
    probe).  ``fault`` (a ``resilience.faults.FaultModel``, or its
    ``FaultModel.parse`` spec string like
    ``"gain_amp=0.5,schedule=ramp,onset=8,period=32"``) arms
    deterministic analog fault injection inside the compiled loop, so
    detection and failover can be demonstrated end-to-end.  Fault-free
    guarded serving emits tokens bit-identical to the plain scheduler
    and lowers byte-identical StableHLO (tests/test_resilience.py).

    Returns (tokens_by_rid, stats); ``stats["resilience"]`` carries the
    ladder / watchdog log (``ResilienceLog.to_dict``).
    """
    from ..resilience.failover import GuardedServer, default_probe
    from ..resilience.faults import FaultModel
    from ..resilience.watchdog import Watchdog, WatchdogConfig

    if isinstance(fault, str):
        fault = FaultModel.parse(fault)
    if watchdog is True:
        watchdog = Watchdog()
    elif isinstance(watchdog, WatchdogConfig):
        watchdog = Watchdog(watchdog)
    elif watchdog is False:
        watchdog = None
    cfg = get_config(arch, smoke=smoke)
    if plan is not None:
        cfg = dataclasses.replace(cfg, cim_plan=plan)
    if not fuse:
        cfg = dataclasses.replace(cfg, cim_fuse=False)
    # resilience is a macro feature: the ladder degrades between analog /
    # hybrid / digital executions of one packed weight set
    cfg = dataclasses.replace(cfg, cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    t0 = time.time()
    with span("serve.pack", arch=arch):
        params = jax.block_until_ready(lm.pack_cim_params(params, cfg))
    t_pack = time.time() - t0

    gp = default_probe(params, fault=fault) if probe else None
    server = GuardedServer(
        params, cfg, slots=slots, prompt_len=prompt_len,
        max_new_cap=max(stop_lengths), temperature=temperature, seed=seed,
        draft_k=draft_k, paged=paged, prefill_chunk=prefill_chunk,
        prefix_sharing=prefix_sharing, watchdog=watchdog, probe=gp,
        fault=fault, segment_iters=segment_iters, start_rung=start_rung)
    requests = mixed_length_requests(n_requests, prompt_len, cfg.vocab_size,
                                     stop_lengths=stop_lengths, seed=seed)
    t0 = time.time()
    with span("serve.compile", arch=arch, n_queue=n_requests):
        server.compile_for(n_requests)
    t_compile = time.time() - t0
    with span("serve.workload", arch=arch, n_requests=n_requests):
        report, log = server.run(requests)

    stats = dict(arch=arch, slots=slots, prompt_len=prompt_len,
                 n_requests=n_requests, stop_lengths=list(stop_lengths),
                 draft_k=draft_k, segment_iters=segment_iters,
                 fault=None if fault is None else dataclasses.asdict(fault),
                 pack_s=round(t_pack, 4), compile_s=round(t_compile, 4),
                 n_compiles=server.n_compiles,
                 continuous=report.summary(),
                 resilience=log.to_dict())
    state = watchdog.state if watchdog is not None else "(no watchdog)"
    line = (f"[serve-guarded] {arch}: {n_requests} reqs over {slots} slots "
            f"| {report.tok_s:.1f} tok/s | health {state}, serving rung "
            f"'{log.rung_labels[log.final_rung]}'")
    if log.actions:
        line += f", {len(log.actions)} failover action(s)"
    if fault is not None and log.detection_tokens is not None:
        line += f" | fault detected at {log.detection_tokens} tokens"
    print(line)
    return report.tokens_by_rid(), stats


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True, help="--no-smoke runs the full-size arch")
    ap.add_argument("--batch", type=int, default=4,
                    help="lock-step batch / continuous slot count")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cim", action="store_true")
    ap.add_argument("--no-pack", dest="pack", action="store_false",
                    help="legacy per-call weight conditioning (baseline)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a mixed-length queue")
    ap.add_argument("--requests", type=int, default=8,
                    help="(--continuous) queued request count")
    ap.add_argument("--speculative", action="store_true",
                    help="plan-cascade speculative decoding (analog draft "
                         "/ deployed verify from one packed weight set)")
    ap.add_argument("--draft-k", type=int, default=8,
                    help="draft block length per speculative round")
    ap.add_argument("--draft-adc-bits", type=int, default=None,
                    help="draft plan SAR width (default: smallest "
                         "non-clipping width per entry)")
    ap.add_argument("--adaptive-draft-k", action="store_true",
                    help="feed measured acceptance back into draft depth")
    ap.add_argument("--paged-blocks", type=int, default=0,
                    help="(--continuous) KV pool size in blocks; 0 keeps "
                         "the contiguous per-slot layout")
    ap.add_argument("--block-size", type=int, default=16,
                    help="(--paged-blocks) tokens per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="(--paged-blocks) prompt tokens prefilled per "
                         "scheduler iteration (default: whole prompt)")
    ap.add_argument("--no-prefix-sharing", dest="prefix_sharing",
                    action="store_false",
                    help="(--paged-blocks) disable shared-prefix reuse")
    ap.add_argument("--watchdog", action="store_true",
                    help="guarded continuous serving: drift watchdog + "
                         "plan-degradation failover ladder over one pack")
    ap.add_argument("--inject-fault", type=str, default=None, metavar="SPEC",
                    help="chaos: arm a deterministic analog FaultModel "
                         "inside the compiled loop, e.g. 'gain_amp=0.5,"
                         "schedule=ramp,onset=8,period=32' (see "
                         "resilience.faults.FaultModel; implies --watchdog)")
    ap.add_argument("--segment-iters", type=int, default=8,
                    help="(--watchdog) scheduler iterations per guarded "
                         "segment between health checks")
    ap.add_argument("--metrics", action="store_true",
                    help="device-resident telemetry rings + metrics registry")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the Prometheus text exposition here")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="append JSON-lines span traces here")
    args = ap.parse_args()
    metrics = args.metrics or bool(args.metrics_out)
    if args.trace_out:
        set_trace_path(args.trace_out)
    paged = None
    if args.paged_blocks:
        from .paging import cdiv
        max_seq = (args.prompt_len + 16
                   + (args.draft_k if args.speculative else 0))
        paged = PagedLayout(block_size=args.block_size,
                            n_tbl=cdiv(max_seq, args.block_size),
                            n_blocks=args.paged_blocks)
    if args.watchdog or args.inject_fault:
        serve_guarded(args.arch, smoke=args.smoke, slots=args.batch,
                      prompt_len=args.prompt_len, n_requests=args.requests,
                      temperature=args.temperature,
                      draft_k=args.draft_k if args.speculative else 0,
                      paged=paged, prefill_chunk=args.prefill_chunk,
                      prefix_sharing=args.prefix_sharing,
                      fault=args.inject_fault,
                      segment_iters=args.segment_iters)
    elif args.continuous:
        serve_continuous(args.arch, smoke=args.smoke, slots=args.batch,
                         prompt_len=args.prompt_len,
                         n_requests=args.requests, cim=args.cim,
                         pack=args.pack, temperature=args.temperature,
                         draft_k=args.draft_k if args.speculative else 0,
                         draft_adc_bits=args.draft_adc_bits,
                         adaptive_draft_k=args.adaptive_draft_k,
                         paged=paged, prefill_chunk=args.prefill_chunk,
                         prefix_sharing=args.prefix_sharing, metrics=metrics)
    elif args.speculative:
        serve_speculative(args.arch, smoke=args.smoke, batch=args.batch,
                          prompt_len=args.prompt_len, gen=args.gen,
                          draft_k=args.draft_k,
                          draft_adc_bits=args.draft_adc_bits,
                          temperature=args.temperature, cim=args.cim,
                          pack=args.pack, metrics=metrics)
    else:
        serve(args.arch, smoke=args.smoke, batch=args.batch,
              prompt_len=args.prompt_len, gen=args.gen, cim=args.cim,
              temperature=args.temperature, pack=args.pack, metrics=metrics)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(REGISTRY.export_prometheus())
        print(f"[serve] metrics exposition -> {args.metrics_out}")


if __name__ == "__main__":
    main()
