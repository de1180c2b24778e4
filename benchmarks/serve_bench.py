"""Serving benchmark: prepacked-weight CIM decode vs the legacy per-call
weight-conditioning path (and the fp/bf16 reference), the
continuous-batching scheduler vs the lock-step loop on a mixed-length
workload, and plan-cascade speculative decoding (analog draft / deployed
verify from one packed weight set), written to BENCH_serve.json for the
per-PR perf trajectory.

  PYTHONPATH=src python benchmarks/serve_bench.py --smoke

Measures pure-execution decode tok/s and prefill time (serve AOT-compiles
both steps, so jit compile never pollutes a throughput number) plus the
one-time pack cost.  The packed and unpacked CIM runs must emit
bit-identical tokens: packing is a caching transform of the weight
conditioning, not an approximation -- the benchmark asserts this before
recording any number.

Every serve-level RATIO is computed from the per-variant MEDIAN of
``repeats`` runs, not a single draw: at smoke scale host scheduler noise
swings single-run tok/s by 10-30%, which once produced a committed
fusion speedup of 1.02x while the kernel benchmark showed 1.31x for the
same fused shape.  Each row records the median and the raw per-run
values so the spread is visible in the JSON.

The continuous-batching rows (fp, packed-CIM, and a packed-unfused A/B)
report aggregate tok/s, slot occupancy and p50/p95 request latency for a
mixed-length queue against the lock-step wave baseline running on the
SAME compiled executables; serve_continuous asserts per-request tokens
are bit-identical between the two plans.

The speculative section is the acceptance-vs-D/A-split study: the draft
plan is the all-analog shadow of the serving plan (same packed weights),
and narrowing its SAR below the no-clip width drafts faster but clips
large accumulates, so the verify pass rejects more.  Each sweep point
records acceptance rate, tokens per scheduler step and tok/s; the
headline row is the serve-level lock-step driver, whose greedy tokens
are asserted bit-identical to the non-speculative baseline.
"""
import argparse
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

# absolute floor for the serve-level speculative/non-speculative decode
# ratio (the PR's acceptance gate), checked in addition to the committed-
# baseline-relative tolerance
_SPEC_SPEEDUP_FLOOR = 1.5
# the committed sweep point is the conservative no-clip draft; acceptance
# may not drop more than this (absolute) below the committed value
_ACCEPTANCE_SLACK = 0.05
# multi-tenant paged-KV acceptance gate: the paged scheduler must either
# beat the contiguous pool on throughput outright, or shrink peak
# resident KV bytes at near-iso throughput (both ratios are same-host,
# same-process, so they cancel machine speed)
_PAGED_TOK_S_FLOOR = 1.3
_PAGED_KV_REDUCTION_FLOOR = 2.0
# near-iso throughput bar for the KV-reduction arm of the gate: the
# measured paged/contiguous ratio at smoke scale swings 0.9-1.4x run to
# run on a noisy host (median ~1.0), so the floor sits below the
# observed spread rather than on top of it
_PAGED_ISO_TOK_S = 0.8
# chunked prefill bounds per-iteration admission work, so the paged
# pool's worst-iteration/median-decode-step stall factor may not exceed
# the contiguous pool's (whole-prompt admits) by more than this slack
_STALL_RATIO_SLACK = 1.25
# device-resident telemetry overhead gate: the metrics-on serve loop may
# not fall below this fraction of the metrics-off throughput (best of
# interleaved warm repeats, same process/host so the ratio cancels
# machine speed and one-sided scheduler noise)
_OBS_OVERHEAD_FLOOR = 0.95
# Prometheus exposition written next to BENCH_serve.json (uploaded as a
# CI artifact)
_METRICS_PROM = os.path.join(os.path.dirname(__file__), "..",
                             "OBS_metrics.prom")


def _median_rate(row: dict) -> float:
    """Median decode rate of a bench row (old baselines lack the field)."""
    return row.get("decode_tok_s_median", row.get("decode_tok_s", 0.0))


def check_regression(new: dict, baseline_path: str,
                     tolerance: float = 0.10) -> None:
    """CI gate: fail if a serving hot path regressed vs the committed
    BENCH_serve.json baseline.

    All gates compare RATIOS, not raw tok/s: CI machines are not the
    machine the baseline was committed on, and absolute tok/s comparisons
    across hosts would gate on hardware, not code.  Ratios cancel host
    speed (both sides run in the same process on the same box) and still
    catch exactly what matters -- one path losing ground relative to
    another.  Three gates:

      packed/fp decode ratio   >= (1 - tolerance) * committed ratio
      speculative speedup      >= max(_SPEC_SPEEDUP_FLOOR,
                                      (1 - tolerance) * committed)
      acceptance rate          >= committed - _ACCEPTANCE_SLACK on the
                                 conservative sweep point (acceptance is
                                 a pure function of the plan cascade, not
                                 host speed, so it gets an absolute gate)
    """
    try:
        with open(baseline_path) as f:
            base = json.load(f)
        base_ratio = (_median_rate(base["cim_packed"])
                      / _median_rate(base["fp"]))
    except (OSError, KeyError, ValueError, ZeroDivisionError):
        print("# no usable baseline -- regression gate skipped")
        return
    new_ratio = (_median_rate(new["cim_packed"])
                 / _median_rate(new["fp"]))
    print(f"# regression gate: packed/fp decode ratio {new_ratio:.3f} "
          f"(baseline {base_ratio:.3f}, tolerance -{tolerance:.0%})")
    if new_ratio < (1.0 - tolerance) * base_ratio:
        raise SystemExit(
            f"cim_packed decode regressed: packed/fp ratio {new_ratio:.3f} "
            f"is >{tolerance:.0%} below the committed baseline "
            f"{base_ratio:.3f} ({baseline_path})")

    spec = new.get("speculative", {})
    base_spec = base.get("speculative", {})
    speedup = spec.get("serve_level", {}).get("decode_speedup_speculative")
    if speedup is not None:
        floor = _SPEC_SPEEDUP_FLOOR
        committed = base_spec.get("serve_level", {}).get(
            "decode_speedup_speculative")
        if committed:
            floor = max(floor, (1.0 - tolerance) * committed)
        print(f"# regression gate: speculative decode speedup "
              f"{speedup:.2f}x (floor {floor:.2f}x)")
        if speedup < floor:
            raise SystemExit(
                f"speculative decode speedup {speedup:.2f}x fell below the "
                f"floor {floor:.2f}x (absolute {_SPEC_SPEEDUP_FLOOR}x / "
                f"committed-relative)")
        acc = spec.get("sweep", [{}])[0].get("acceptance_rate")
        base_acc = base_spec.get("sweep", [{}])[0].get("acceptance_rate")
        if acc is not None and base_acc is not None:
            print(f"# regression gate: conservative-draft acceptance "
                  f"{acc:.3f} (committed {base_acc:.3f}, "
                  f"slack {_ACCEPTANCE_SLACK})")
            if acc < base_acc - _ACCEPTANCE_SLACK:
                raise SystemExit(
                    f"draft acceptance on the conservative sweep point "
                    f"dropped to {acc:.3f} (committed {base_acc:.3f}): the "
                    f"plan cascade got lossier without a plan change")

    mt = new.get("multi_tenant")
    if mt is not None:
        if not mt.get("token_parity"):
            raise SystemExit("multi-tenant paged token parity failed")
        r_tok, r_kv = mt["paged_vs_contiguous_tok_s"], mt["kv_reduction"]
        print(f"# regression gate: paged/contiguous tok/s {r_tok:.2f}x, "
              f"peak-KV reduction {r_kv:.2f}x (need >= "
              f"{_PAGED_TOK_S_FLOOR}x tok/s OR >= "
              f"{_PAGED_KV_REDUCTION_FLOOR}x KV at >= "
              f"{_PAGED_ISO_TOK_S}x tok/s)")
        if not (r_tok >= _PAGED_TOK_S_FLOOR
                or (r_kv >= _PAGED_KV_REDUCTION_FLOOR
                    and r_tok >= _PAGED_ISO_TOK_S)):
            raise SystemExit(
                f"paged KV pool misses its acceptance gate: "
                f"{r_tok:.2f}x tok/s, {r_kv:.2f}x KV reduction")
        s_pg = mt["paged"]["stall_factor"]
        s_ct = mt["contiguous"]["stall_factor"]
        print(f"# regression gate: admission stall factor paged "
              f"{s_pg:.2f} vs contiguous {s_ct:.2f} "
              f"(slack {_STALL_RATIO_SLACK}x)")
        if s_pg > _STALL_RATIO_SLACK * s_ct:
            raise SystemExit(
                f"chunked prefill stopped bounding admission stalls: "
                f"paged worst-iteration factor {s_pg:.2f} > "
                f"{_STALL_RATIO_SLACK}x contiguous {s_ct:.2f}")
        base_mt = base.get("multi_tenant")
        if base_mt is not None:
            # committed-relative gates: kv_reduction is deterministic
            # (pure block accounting) so it always gets one; the tok/s
            # ratio only when the committed win is throughput-mode --
            # in KV-reduction mode the absolute near-iso bar above
            # already governs it and a committed 0.95x would otherwise
            # ratchet a noise floor into the gate
            keys = ["kv_reduction"]
            if (base_mt.get("paged_vs_contiguous_tok_s") or 0) \
                    >= _PAGED_TOK_S_FLOOR:
                keys.append("paged_vs_contiguous_tok_s")
            for key in keys:
                commit = base_mt.get(key)
                if commit and mt[key] < (1.0 - tolerance) * commit:
                    raise SystemExit(
                        f"multi-tenant {key} regressed: {mt[key]:.2f} is "
                        f">{tolerance:.0%} below committed {commit:.2f}")

    tel = new.get("telemetry")
    if tel is not None:
        from repro.obs import host_matches
        ratio = tel.get("on_off_tok_s_ratio")
        print(f"# regression gate: telemetry on/off tok/s ratio "
              f"{ratio:.3f} (floor {_OBS_OVERHEAD_FLOOR})")
        if ratio is not None and ratio < _OBS_OVERHEAD_FLOOR:
            raise SystemExit(
                f"device-resident telemetry overhead blew its budget: "
                f"metrics-on throughput is {ratio:.3f}x metrics-off "
                f"(floor {_OBS_OVERHEAD_FLOOR}x)")
        base_tel = base.get("telemetry")
        base_fps = (base_tel or {}).get("fingerprints_metrics_off", {})
        if base_fps and host_matches(tel.get("host"),
                                     (base_tel or {}).get("host")):
            moved = {k: (v, tel["fingerprints_metrics_off"].get(k))
                     for k, v in base_fps.items()
                     if tel["fingerprints_metrics_off"].get(k) != v}
            print(f"# regression gate: metrics-off HLO fingerprints "
                  f"{'MOVED: ' + str(sorted(moved)) if moved else 'stable'} "
                  f"({len(base_fps)} variants, host-matched)")
            if moved:
                raise SystemExit(
                    f"metrics-off serve loop stopped lowering "
                    f"byte-identically on a matching host -- some code "
                    f"path now pays for telemetry while it is off: "
                    f"{moved}")
        elif base_fps:
            print("# regression gate: metrics-off HLO fingerprints "
                  "skipped (baseline host differs -- StableHLO is only "
                  "comparable for a fixed backend/jax version)")


def multi_tenant_trace(n_requests: int, max_prompt: int, vocab: int,
                       block_size: int, n_tenants: int = 3, seed: int = 0,
                       arrival_rate: float = 0.5):
    """Open-loop multi-tenant workload: ``n_tenants`` tenants each with a
    shared system prompt (a block-aligned prefix, so the paged scheduler
    can deduplicate it), per-request tails of mixed length, per-request
    decode budgets, and Poisson arrivals (exponential inter-arrival times
    in scheduler-iteration units).  Prompts top out at ``max_prompt`` --
    the service's STATIC context limit is larger (run_multi_tenant's
    ``prompt_len``), which is the realistic serving shape: a contiguous
    pool must reserve and prefill the context limit for every slot, while
    actual traffic is mostly chat-sized with one long-prompt stressor
    (request 0).  Returns (requests, arrival_iters)."""
    import numpy as np
    from repro.launch.scheduler import Request

    rng = np.random.default_rng(seed)
    pre_blocks = [3, 1, 2, 4, 2, 3][:n_tenants]
    prefixes = [rng.integers(0, vocab, nb * block_size, dtype=np.int32)
                for nb in pre_blocks]
    reqs, arrivals = [], []
    t = 0.0
    for i in range(n_requests):
        tenant = i % n_tenants            # round-robin keeps tenants mixed
        pre = prefixes[tenant]
        if i == 0:                        # one long-prompt request: the
            tail = max_prompt - len(pre)  # admission-stall stressor
        else:                             # the rest are chat-sized
            tail = int(rng.integers(
                1, min(max_prompt - len(pre), 3 * block_size) + 1))
        prompt = np.concatenate(
            [pre, rng.integers(0, vocab, tail, dtype=np.int32)])
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(4, 13))))
        arrivals.append(int(t))
        t += rng.exponential(1.0 / arrival_rate)
    return reqs, arrivals


def run_multi_tenant(arch: str = "minicpm-2b", smoke: bool = True,
                     slots: int = 3, prompt_len: int = 128,
                     max_prompt: int = 64, n_requests: int = 10,
                     block_size: int = 8, prefill_chunk: int = 16,
                     repeats: int = 3, seed: int = 0) -> dict:
    """Paged vs contiguous KV on the multi-tenant trace.

    Three runs of the SAME workload: the contiguous pool (prompts padded
    to the static length -- all a contiguous layout can do with mixed
    lengths), the paged pool single-shot without sharing (the parity
    reference), and the paged pool with chunked prefill + shared-prefix
    reuse (the candidate).  Token parity between the two paged runs is
    asserted bit-exactly; throughput comes from the pure device loop
    (median of ``repeats``) and latency structure (TTFT, per-iteration
    stall factor) from the instrumented runner stepping the identical
    compiled iteration."""
    import statistics as _stats

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.paging import PagedLayout, cdiv
    from repro.launch.scheduler import ContinuousBatchingScheduler, Request
    from repro.models import lm

    cfg = get_config(arch, smoke=smoke)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    cap = 12
    reqs, arrivals = multi_tenant_trace(n_requests, max_prompt,
                                        cfg.vocab_size, block_size,
                                        seed=seed)
    # the contiguous pool can only serve mixed lengths by padding every
    # prompt to the static context limit -- full-length prefill AND
    # full-length KV reservation per slot
    padded = [Request(rid=r.rid,
                      prompt=np.concatenate(
                          [r.prompt, np.zeros(prompt_len - len(r.prompt),
                                              np.int32)]),
                      max_new_tokens=r.max_new_tokens,
                      stop_token=r.stop_token) for r in reqs]
    n_tbl = cdiv(prompt_len + cap, block_size)
    lay = PagedLayout(block_size=block_size, n_tbl=n_tbl,
                      n_blocks=2 * slots * cdiv(max_prompt + cap,
                                                block_size) + 8)

    contig = ContinuousBatchingScheduler(
        params, cfg, slots=slots, prompt_len=prompt_len, max_new_cap=cap,
        seed=seed)
    paged = ContinuousBatchingScheduler(
        params, cfg, slots=slots, prompt_len=prompt_len, max_new_cap=cap,
        seed=seed, paged=lay, prefill_chunk=prefill_chunk,
        prefix_sharing=True)
    paged_ref = ContinuousBatchingScheduler(
        params, cfg, slots=slots, prompt_len=prompt_len, max_new_cap=cap,
        seed=seed, paged=lay, prefix_sharing=False)

    # bit-exact parity: chunked + prefix-shared vs single-shot unshared
    want = paged_ref.run(reqs).tokens_by_rid()
    got = paged.run(reqs).tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"request {rid}: chunked/shared paged prefill changed "
                    "tokens vs single-shot paged")

    pg_runs = [paged.run(reqs, arrivals) for _ in range(repeats)]
    ct_runs = [contig.run(padded, arrivals) for _ in range(repeats)]
    pg_tok = _stats.median(r.tok_s for r in pg_runs)
    ct_tok = _stats.median(r.tok_s for r in ct_runs)
    pg_rep, pg_tl = paged.run_instrumented(reqs, arrivals)
    ct_rep, ct_tl = contig.run_instrumented(padded, arrivals)

    def stall(timeline, step_branch):
        """Worst-iteration / median-decode-step duration ratio: how long
        the slowest single iteration (a long-prompt admit, in the
        contiguous pool) starves every live decoder."""
        it = timeline["iter_s"]
        steps = it[timeline["branch"] == step_branch]
        med = float(np.median(steps)) if steps.size else float("nan")
        p95 = float(np.percentile(it, 95))
        return p95 / med if med and med > 0 else float("nan")

    peak = max(r.peak_blocks for r in pg_runs + [pg_rep])
    kv_paged = paged.kv_bytes_paged(peak)
    kv_contig = contig.kv_bytes_contiguous()
    out = dict(
        config=dict(arch=arch, slots=slots, prompt_len=prompt_len,
                    max_prompt=max_prompt, n_requests=n_requests,
                    block_size=block_size, n_blocks=lay.n_blocks,
                    prefill_chunk=prefill_chunk, repeats=repeats),
        token_parity=True,
        paged=dict(pg_rep.summary(), tok_s_median=round(pg_tok, 2),
                   **{k: round(v, 4) for k, v in
                      pg_rep.ttft_percentiles().items()},
                   stall_factor=round(stall(pg_tl, 3), 2)),
        contiguous=dict(ct_rep.summary(), tok_s_median=round(ct_tok, 2),
                        **{k: round(v, 4) for k, v in
                           ct_rep.ttft_percentiles().items()},
                        stall_factor=round(stall(ct_tl, 2), 2)),
        paged_vs_contiguous_tok_s=round(pg_tok / ct_tok, 2) if ct_tok
        else float("nan"),
        kv_bytes_paged_peak=kv_paged,
        kv_bytes_contiguous=kv_contig,
        kv_reduction=round(kv_contig / kv_paged, 2) if kv_paged
        else float("nan"),
    )
    print(f"# multi-tenant ({arch}, {n_requests} reqs, {slots} slots, "
          f"P<={prompt_len}): paged {pg_tok:.1f} tok/s vs contiguous "
          f"{ct_tok:.1f} ({out['paged_vs_contiguous_tok_s']}x), KV "
          f"{kv_paged / 1024:.0f}KiB peak vs {kv_contig / 1024:.0f}KiB "
          f"({out['kv_reduction']}x smaller), ttft p95 "
          f"{out['paged']['ttft_p95_s']}s vs {out['contiguous']['ttft_p95_s']}s,"
          f" stall {out['paged']['stall_factor']} vs "
          f"{out['contiguous']['stall_factor']}")
    return out


def run_telemetry(arch: str = "minicpm-2b", smoke: bool = True,
                  slots: int = 3, prompt_len: int = 128,
                  max_prompt: int = 64, n_requests: int = 10,
                  block_size: int = 8, prefill_chunk: int = 16,
                  repeats: int = 3, seed: int = 0,
                  prom_path: str = _METRICS_PROM) -> dict:
    """Device-resident telemetry section: zero-overhead-when-off proof.

    Runs the multi-tenant Poisson trace through the SAME paged scheduler
    twice -- metrics off and metrics on -- and records (a) the sha256
    StableHLO fingerprints of all three metrics-OFF serve-loop variants
    (the byte-identity artifact --check-regression gates on), (b) the
    on/off throughput ratio (the <=5%% overhead budget), (c) bit-exact
    token parity, and (d) the ring-derived TTFT against the instrumented
    runner's host-observed first_iter -- the rings must not merely look
    plausible, they must agree exactly with the per-iteration ground
    truth.  The metrics-on run's registry snapshot is exported as a
    Prometheus text exposition (the CI artifact)."""
    import statistics as _stats

    import jax
    from repro.configs import get_config
    from repro.launch.paging import PagedLayout, cdiv
    from repro.launch.scheduler import ContinuousBatchingScheduler
    from repro.models import lm
    from repro.obs import (REGISTRY, ObsConfig, host_fingerprint,
                           scheduler_fingerprint)
    from repro.obs.fingerprint import VARIANTS

    cfg = get_config(arch, smoke=smoke)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    cap = 12
    reqs, arrivals = multi_tenant_trace(n_requests, max_prompt,
                                        cfg.vocab_size, block_size,
                                        seed=seed)
    lay = PagedLayout(block_size=block_size,
                      n_tbl=cdiv(prompt_len + cap, block_size),
                      n_blocks=2 * slots * cdiv(max_prompt + cap,
                                                block_size) + 8)
    kw = dict(slots=slots, prompt_len=prompt_len, max_new_cap=cap,
              seed=seed, paged=lay, prefill_chunk=prefill_chunk,
              prefix_sharing=True)
    off = ContinuousBatchingScheduler(params, cfg, **kw)
    on = ContinuousBatchingScheduler(params, cfg, obs=ObsConfig(), **kw)

    # metrics-off serve loops must lower byte-identically forever: hash
    # the pre-optimization StableHLO of every variant (small queue -- the
    # fingerprint covers the program, not the workload size)
    def variant(name):
        if name == "paged":
            return off
        kw2 = dict(slots=2, prompt_len=16, max_new_cap=4, seed=seed)
        if name == "speculative":
            kw2["draft_k"] = 2
        return ContinuousBatchingScheduler(params, cfg, **kw2)
    fps = {name: scheduler_fingerprint(variant(name), 2)
           for name in VARIANTS}

    # one warm run each (pays compile outside the timed window), then
    # interleave the timed repeats so host drift hits both sides alike;
    # gate on best-of-repeats -- noise on a shared host only ever
    # subtracts throughput, so max-of-N estimates each loop's true rate
    off_runs = [off.run(reqs, arrivals)]
    on_runs = [on.run(reqs, arrivals)]
    for _ in range(repeats):
        off_runs.append(off.run(reqs, arrivals))
        on_runs.append(on.run(reqs, arrivals))
    want = off_runs[0].tokens_by_rid()
    for r in off_runs[1:] + on_runs:
        got = r.tokens_by_rid()
        for rid in want:
            np.testing.assert_array_equal(
                got[rid], want[rid],
                err_msg=f"request {rid}: telemetry rings changed tokens")
    off_med = _stats.median(r.tok_s for r in off_runs[1:])
    on_med = _stats.median(r.tok_s for r in on_runs[1:])
    off_best = max(r.tok_s for r in off_runs[1:])
    on_best = max(r.tok_s for r in on_runs[1:])
    ratio = on_best / off_best if off_best else float("nan")

    # ring truth: TTFT read back from the device event ring must equal
    # the instrumented runner's host-stepped first_iter, request by request
    ri, _ = on.run_instrumented(reqs, arrivals)
    ring_ttft = on_runs[0].obs.ttft_iters
    inst_ttft = {f.rid: f.first_iter for f in ri.finished}
    assert ring_ttft == inst_ttft, \
        f"ring TTFT diverged from instrumented: {ring_ttft} vs {inst_ttft}"

    snap = max(on_runs, key=lambda r: r.tok_s).obs
    snap.register(REGISTRY)
    from repro.kernels.ccim_matmul.autotune import cache_summary
    tuning = cache_summary()
    with open(prom_path, "w") as f:
        f.write(REGISTRY.export_prometheus())
    out = dict(
        fingerprints_metrics_off=fps,
        host=host_fingerprint(),
        tok_s_off_median=round(off_med, 2),
        tok_s_on_median=round(on_med, 2),
        tok_s_off_best=round(off_best, 2),
        tok_s_on_best=round(on_best, 2),
        on_off_tok_s_ratio=round(ratio, 3),
        tokens_bit_identical=True,
        ring_ttft_matches_instrumented=True,
        tuning_cache=tuning,
        snapshot=snap.to_dict(),
        prom_path=os.path.relpath(prom_path,
                                  os.path.join(os.path.dirname(__file__),
                                               "..")),
    )
    print(f"# telemetry: on/off tok/s ratio {ratio:.3f} "
          f"(best {on_best:.1f}/{off_best:.1f}, median {on_med:.1f}/"
          f"{off_med:.1f}), tokens identical, ring TTFT == "
          f"instrumented; metrics-off fingerprints "
          f"{ {k: v[:12] for k, v in fps.items()} }")
    print(f"# telemetry: Prometheus exposition -> {prom_path}")
    print(f"# {tuning}")
    return out


def run(arch: str = "minicpm-2b", smoke: bool = True, batch: int = 2,
        prompt_len: int = 16, gen: int = 48, repeats: int = 3,
        draft_k: int = 8, path: str = _BENCH_JSON, gate: bool = False,
        multi_tenant: bool = True) -> dict:
    from repro.launch.serve import serve, serve_continuous, serve_speculative

    def measure(cim: bool, pack: bool, fuse: bool = True):
        """Median-of-repeats decode rate; tokens asserted deterministic."""
        runs = [serve(arch, smoke=smoke, batch=batch, prompt_len=prompt_len,
                      gen=gen, cim=cim, pack=pack, fuse=fuse,
                      return_stats=True)
                for _ in range(repeats)]
        toks = runs[0][0]
        for t, _ in runs[1:]:
            assert (t == toks).all(), "greedy serving must be deterministic"
        rates = sorted(s["decode_tok_s"] for _, s in runs)
        stats = max((s for _, s in runs), key=lambda s: s["decode_tok_s"])
        stats = dict(stats, decode_tok_s_median=statistics.median(rates),
                     decode_tok_s_runs=rates)
        return toks, stats

    _, fp = measure(cim=False, pack=False)
    tok_u, unpacked = measure(cim=True, pack=False, fuse=False)
    tok_p, packed = measure(cim=True, pack=True)
    assert (tok_u == tok_p).all(), \
        "packed+fused CIM serving diverged from the unpacked unfused path"
    # fusion A/B on the same packed weights: tokens must also be identical
    tok_nf, packed_unfused = measure(cim=True, pack=True, fuse=False)
    assert (tok_nf == tok_p).all(), \
        "fused serving changed tokens vs the unfused packed path"

    # all ratios from the per-variant medians (single draws at smoke scale
    # are dominated by host scheduler noise, not the code under test)
    pack_speedup = (packed_unfused["decode_tok_s_median"]
                    / unpacked["decode_tok_s_median"])
    fusion_speedup = (packed["decode_tok_s_median"]
                      / packed_unfused["decode_tok_s_median"])
    total_speedup = (packed["decode_tok_s_median"]
                     / unpacked["decode_tok_s_median"])

    # continuous batching vs lock-step on a mixed-length queue; token
    # parity with the lock-step plan is asserted inside serve_continuous.
    # The packed_unfused row is the fusion A/B at the continuous-batching
    # level (same scheduler, cfg.cim_fuse off).
    cb = {}
    cb_tokens = {}
    cb_repeats = max(repeats, 3)
    for mode, cim, fuse in (("fp", False, True), ("cim_packed", True, True),
                            ("cim_packed_unfused", True, False)):
        toks, st = serve_continuous(arch, smoke=smoke, slots=batch,
                                    prompt_len=prompt_len,
                                    n_requests=4 * batch,
                                    stop_lengths=(4, 16, 8, 12), cim=cim,
                                    pack=cim, fuse=fuse, repeats=cb_repeats)
        cb_tokens[mode] = toks
        cb[mode] = dict(continuous=st["continuous"], lockstep=st["lockstep"],
                        tok_s_median=st["tok_s_median"],
                        lockstep_tok_s_median=st["lockstep_tok_s_median"],
                        tokens_match_lockstep=st["tokens_match_lockstep"],
                        speedup_vs_lockstep=st["speedup_vs_lockstep"])
    for rid, want in cb_tokens["cim_packed"].items():
        np.testing.assert_array_equal(
            cb_tokens["cim_packed_unfused"][rid], want,
            err_msg=f"request {rid}: fusion changed continuous-batching "
                    "tokens")
    cb["fusion_speedup"] = round(
        cb["cim_packed"]["tok_s_median"]
        / cb["cim_packed_unfused"]["tok_s_median"], 2)
    cb["fused_tokens_bit_identical"] = True

    # --- plan-cascade speculative decoding -------------------------------
    # Headline: the serve-level lock-step driver (one AOT dispatch per
    # draft/verify round); greedy tokens asserted bit-identical to the
    # non-speculative baseline inside serve_speculative.  Median-of-repeats
    # on both sides of the ratio.
    spec_runs = [serve_speculative(arch, smoke=smoke, batch=batch,
                                   prompt_len=prompt_len, gen=gen,
                                   draft_k=draft_k, return_stats=True)[1]
                 for _ in range(repeats)]
    spec_med = statistics.median(s["decode_tok_s"] for s in spec_runs)
    base_med = statistics.median(s["baseline_decode_tok_s"]
                                 for s in spec_runs)
    serve_level = dict(
        spec_runs[0], decode_tok_s_median=round(spec_med, 2),
        baseline_decode_tok_s_median=round(base_med, 2),
        decode_speedup_speculative=round(spec_med / base_med, 2))

    # Acceptance-vs-D/A-split sweep through the continuous-batching
    # scheduler: the draft plan's SAR width is the aggressiveness axis
    # (None = per-entry no-clip width; narrower widths clip large analog
    # accumulates, so verify rejects more and tokens/step shrinks).
    nonspec_med = cb["cim_packed"]["tok_s_median"]
    sweep = []
    for bits in (None, 7, 6, 5):
        _, st = serve_continuous(arch, smoke=smoke, slots=batch,
                                 prompt_len=prompt_len,
                                 n_requests=4 * batch,
                                 stop_lengths=(4, 16, 8, 12), cim=True,
                                 pack=True, draft_k=draft_k,
                                 draft_adc_bits=bits, repeats=cb_repeats)
        cont = st["continuous"]
        sweep.append(dict(
            draft_plan=st["draft_plan"], draft_k=draft_k,
            acceptance_rate=cont["acceptance_rate"],
            tokens_per_step=cont["tokens_per_step"],
            tok_s_median=st["tok_s_median"],
            speedup_vs_nonspec_cb=round(st["tok_s_median"] / nonspec_med, 2),
            tokens_match_lockstep=st["tokens_match_lockstep"]))

    try:
        from .common import bench_header
    except ImportError:
        from common import bench_header
    result = dict(
        **bench_header(),
        config=dict(arch=arch, smoke=smoke, batch=batch,
                    prompt_len=prompt_len, gen=gen, repeats=repeats,
                    draft_k=draft_k),
        fp=fp,
        cim_unpacked=unpacked,          # pre-refactor baseline dataflow
        cim_packed_unfused=packed_unfused,   # packing alone, no fusion
        cim_packed=packed,              # packed + fused + tuned (hot path)
        packed_tokens_bit_identical=True,
        fused_tokens_bit_identical=True,
        decode_speedup_packed_vs_unpacked=round(pack_speedup, 2),
        decode_speedup_fusion=round(fusion_speedup, 2),
        decode_speedup_vs_prerefactor=round(total_speedup, 2),
        continuous_batching=cb,
        speculative=dict(serve_level=serve_level, sweep=sweep),
    )
    if multi_tenant:
        result["multi_tenant"] = run_multi_tenant(
            arch, smoke=smoke, repeats=max(repeats, 3))
    # 8 timed repeats: the overhead gate compares best-of-N of two
    # ~100ms loops, and small N lets one lucky draw on either side move
    # the ratio past the floor (observed swing at N=3-5: 0.93-1.01 for
    # a true ratio of ~1.0)
    result["telemetry"] = run_telemetry(arch, smoke=smoke,
                                        repeats=max(repeats, 8))
    if gate:
        check_regression(result, path)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"# decode tok/s (median of {repeats}): "
          f"fp {fp['decode_tok_s_median']:.1f}, "
          f"cim unpacked {unpacked['decode_tok_s_median']:.1f}, "
          f"cim packed {packed['decode_tok_s_median']:.1f} "
          f"({total_speedup:.2f}x total: {pack_speedup:.2f}x packing, "
          f"{fusion_speedup:.2f}x fusion; pack cost {packed['pack_s']}s)")
    for mode in ("fp", "cim_packed", "cim_packed_unfused"):
        row = cb[mode]
        print(f"# continuous batching ({mode}): "
              f"{row['tok_s_median']} tok/s (median) at "
              f"{row['continuous']['occupancy']:.0%} occupancy vs lock-step "
              f"{row['lockstep_tok_s_median']} ({row['speedup_vs_lockstep']}x,"
              f" tokens identical)")
    print(f"# cb fusion speedup (median): {cb['fusion_speedup']}x")
    print(f"# speculative (serve-level, k={draft_k}): "
          f"{serve_level['decode_tok_s_median']} tok/s vs baseline "
          f"{serve_level['baseline_decode_tok_s_median']} "
          f"({serve_level['decode_speedup_speculative']}x, acceptance "
          f"{serve_level['acceptance_rate']:.0%}, tokens identical)")
    for pt in sweep:
        print(f"# speculative sweep {pt['draft_plan']}: acceptance "
              f"{pt['acceptance_rate']:.2f}, {pt['tokens_per_step']} tok/step,"
              f" {pt['tok_s_median']} tok/s "
              f"({pt['speedup_vs_nonspec_cb']}x vs non-spec cb)")
    print(f"# wrote {path}")
    return result


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True, help="--no-smoke runs the full-size arch")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=48)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--draft-k", type=int, default=8,
                    help="draft block length for the speculative rows")
    ap.add_argument("--check-regression", dest="gate", action="store_true",
                    help="fail if packed decode regressed >10%% vs the "
                         "committed BENCH_serve.json (packed/fp ratio), the "
                         "speculative speedup fell below its floor, draft "
                         "acceptance dropped on the committed sweep point, "
                         "the paged KV pool missed its multi-tenant "
                         "throughput/footprint/stall gates, a metrics-off "
                         "serve-loop HLO fingerprint moved on a matching "
                         "host, or telemetry overhead exceeded its budget")
    ap.add_argument("--multi-tenant", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="include the paged-vs-contiguous multi-tenant "
                         "trace section")
    args = ap.parse_args()
    run(args.arch, args.smoke, args.batch, args.prompt_len, args.gen,
        args.repeats, args.draft_k, gate=args.gate,
        multi_tenant=args.multi_tenant)


if __name__ == "__main__":
    main()
