"""Resilience benchmark: fault injection, drift detection, failover cost.

  PYTHONPATH=src python benchmarks/resilience_bench.py --smoke

Writes BENCH_resilience.json and enforces the PR's closed-loop
acceptance gates inline (the CI step fails on any breach):

  off-path identity   arming + disarming a FaultModel leaves the plain
                      serve loop's StableHLO fingerprints byte-identical
                      for every scheduler variant -- fault-free serving
                      never pays for the chaos machinery.  A fault-ON
                      segment lowering is ALSO fingerprinted and must
                      DIFFER, proving the injection is actually wired
                      into the compiled loop (an off-path gate that
                      passes because the feature is dead would be
                      meaningless).
  clean guarded       the watchdog-guarded serve of a fault-free
                      workload stays GREEN, takes zero failover
                      actions, and emits tokens bit-identical to the
                      plain continuous-batching scheduler.
  detection           a seeded mid-stream capacitor-drift ramp drives
                      the debounced state to RED within a bounded
                      token count, deterministically.
  fidelity recovery   end-to-end logits rel-RMS vs the float reference:
                      the drifted plan degrades, the failover rung
                      restores RMS to <= 2x the clean plan's RMS.
  zero-recompile      every rung's segment executable is compiled up
                      front; the census asserts failover never
                      compiles (and never repacks -- all rungs serve
                      one pack, enforced by the engine's pack guard).

Per-rung throughput cost (the price of each degradation level) is
recorded as median-of-repeats tok/s but NOT gated -- it is a same-host
trajectory number, everything above is a determinism property.
"""
import argparse
import dataclasses
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_resilience.json")

# the seeded chaos scenario: per-column capacitor gain/offset drift
# ramping in mid-workload.  onset/period are device loop iterations.
_DRIFT = dict(seed=3, gain_amp=0.6, offset_lsb=2.0, schedule="ramp",
              onset=4, period=16)
# tokens the debounced watchdog gets to leave GREEN (the workload emits
# ~40; detection typically lands around half that)
_DETECTION_BUDGET_TOKENS = 32
# failover must restore end-to-end RMS to within this factor of the
# clean plan's RMS vs the float reference
_RMS_RECOVERY_FACTOR = 2.0


def _workload(cfg, prompt_len, n_requests, seed):
    from repro.launch.scheduler import mixed_length_requests
    return mixed_length_requests(n_requests, prompt_len, cfg.vocab_size,
                                 stop_lengths=(4, 16, 8, 12), seed=seed)


def run_fingerprints(params, cfg, fault) -> dict:
    """Off-path byte-identity: plain-loop StableHLO before vs after an
    arm/disarm cycle, plus the wiring proof (fault-on segment differs)."""
    from repro.launch.scheduler import ContinuousBatchingScheduler
    from repro.obs import scheduler_fingerprint
    from repro.obs.fingerprint import VARIANTS, hlo_fingerprint
    from repro.launch.paging import PagedLayout
    from repro.resilience import faults as rfaults

    def make(name):
        kw = dict(slots=2, prompt_len=16, max_new_cap=4, seed=0)
        if name == "paged":
            kw["paged"] = PagedLayout(block_size=8, n_tbl=3, n_blocks=12)
        elif name == "speculative":
            kw["draft_k"] = 2
        return ContinuousBatchingScheduler(params, cfg, **kw)

    before = {v: scheduler_fingerprint(make(v), 2) for v in VARIANTS}
    # arm, lower a faulted segment (the wiring proof), disarm
    seg_off = hlo_fingerprint(make("contiguous").segment_hlo_text(2))
    with rfaults.inject(fault):
        seg_on = hlo_fingerprint(make("contiguous").segment_hlo_text(2))
    after = {v: scheduler_fingerprint(make(v), 2) for v in VARIANTS}

    identical = before == after
    wired = seg_on != seg_off
    print(f"# fingerprints: off-path {'identical' if identical else 'MOVED'}"
          f" across {len(before)} variants; fault-on segment "
          f"{'differs (wired)' if wired else 'UNCHANGED (dead feature!)'}")
    if not identical:
        moved = sorted(v for v in before if before[v] != after[v])
        raise SystemExit(f"arming a FaultModel changed the fault-free serve "
                         f"loop lowering: {moved}")
    if not wired:
        raise SystemExit("fault-armed segment lowered identically to the "
                         "clean segment -- injection is not wired in")
    return dict(plain_loop=before, identical_after_arm_cycle=True,
                segment_fault_off=seg_off, segment_fault_on=seg_on,
                fault_segment_differs=True)


def run_clean_guarded(params, cfg, prompt_len, n_requests, seed,
                      segment_iters) -> dict:
    """Fault-free guarded serving: GREEN, zero actions, token parity."""
    from repro.resilience.failover import GuardedServer, default_probe
    from repro.resilience.watchdog import GREEN, Watchdog

    server = GuardedServer(
        params, cfg, slots=2, prompt_len=prompt_len, max_new_cap=16,
        seed=seed, watchdog=Watchdog(), probe=default_probe(params),
        segment_iters=segment_iters)
    reqs = _workload(cfg, prompt_len, n_requests, seed)
    report, log = server.run(reqs)
    want = server.scheduler().run(reqs).tokens_by_rid()
    got = report.tokens_by_rid()
    parity = all(np.array_equal(got[r], want[r]) for r in want)
    print(f"# clean guarded: state {server.watchdog.state}, "
          f"{len(log.actions)} actions, token parity "
          f"{'OK' if parity else 'FAILED'}, {report.tok_s:.1f} tok/s, "
          f"{log.n_compiles} compiles ({len(server.ladder)} rungs)")
    if server.watchdog.state != GREEN or log.actions:
        raise SystemExit(
            f"clean workload tripped the watchdog: state "
            f"{server.watchdog.state}, {len(log.actions)} failover actions")
    if not parity:
        raise SystemExit("guarded serving changed tokens on a fault-free "
                         "workload vs the plain scheduler")
    if log.n_compiles != len(server.ladder):
        raise SystemExit(f"expected one compile per rung "
                         f"({len(server.ladder)}), got {log.n_compiles}")
    return dict(state=server.watchdog.state, n_actions=len(log.actions),
                token_parity=True, tok_s=round(report.tok_s, 2),
                n_compiles=log.n_compiles,
                probe_clean_floor=round(server.probe.clean_floor, 6),
                resilience=log.to_dict())


def run_detection(params, cfg, fault, prompt_len, n_requests, seed,
                  segment_iters) -> dict:
    """Seeded mid-stream drift: RED within the token budget, escalation
    to the immune rung, zero recompiles at failover time."""
    from repro.resilience.failover import GuardedServer, default_probe
    from repro.resilience.watchdog import RED, Watchdog, WatchdogConfig

    server = GuardedServer(
        params, cfg, slots=2, prompt_len=prompt_len, max_new_cap=16,
        seed=seed, fault=fault,
        watchdog=Watchdog(WatchdogConfig(debounce=1)),
        probe=default_probe(params, fault=fault),
        segment_iters=segment_iters)
    reqs = _workload(cfg, prompt_len, n_requests, seed)
    report, log = server.run(reqs)
    det = log.detection_tokens
    print(f"# detection: state {server.watchdog.state}, detected at "
          f"{det} tokens (budget {_DETECTION_BUDGET_TOKENS}), "
          f"{len(log.actions)} action(s), final rung "
          f"'{log.rung_labels[log.final_rung]}', {log.n_compiles} compiles")
    if server.watchdog.state != RED:
        raise SystemExit(f"seeded drift not escalated to RED "
                         f"(state {server.watchdog.state})")
    if det is None or det > _DETECTION_BUDGET_TOKENS:
        raise SystemExit(f"detection at {det} tokens blew the "
                         f"{_DETECTION_BUDGET_TOKENS}-token budget")
    if log.final_rung != len(server.ladder) - 1 or not log.actions:
        raise SystemExit("RED did not escalate to the top (digital) rung")
    if log.n_compiles != len(server.ladder):
        raise SystemExit(f"failover compiled mid-run: {log.n_compiles} "
                         f"compiles for {len(server.ladder)} rungs")
    return dict(fault=dataclasses.asdict(fault),
                state=server.watchdog.state, detection_tokens=det,
                budget_tokens=_DETECTION_BUDGET_TOKENS,
                final_rung=log.rung_labels[log.final_rung],
                n_actions=len(log.actions), n_compiles=log.n_compiles,
                tok_s=round(report.tok_s, 2), resilience=log.to_dict())


def run_rms(raw_params, packed_params, cfg, fault, t_drift: int = 48
            ) -> dict:
    """End-to-end logits RMS vs the float reference: clean plan, drifted
    plan (no failover), and the failover rung under the SAME drift."""
    from repro.core.ccim import DEFAULT_CONFIG
    from repro.plan.plan import DeploymentPlan, PlanEntry
    from repro.plan.profiler import (calibration_batch, planned_logits,
                                     reference_logits, rel_rms)
    from repro.resilience import faults as rfaults
    from repro.resilience.failover import derive_exact_plan

    plan = cfg.cim_plan or DeploymentPlan.uniform(
        PlanEntry(cfg=cfg.cim_cfg or DEFAULT_CONFIG,
                  fidelity=cfg.cim_fidelity))
    dig = derive_exact_plan(plan)
    toks = calibration_batch(cfg, batch=2, seq_len=8)
    ref = np.asarray(reference_logits(raw_params, cfg, toks), np.float64)

    def rms(p, armed):
        if armed:
            with rfaults.inject(fault), rfaults.clock(t_drift):
                y = planned_logits(packed_params, cfg, toks, p,
                                   noise_seed=None)
        else:
            y = planned_logits(packed_params, cfg, toks, p, noise_seed=None)
        return float(rel_rms(np.asarray(y, np.float64), ref))

    clean = rms(plan, armed=False)
    drift = rms(plan, armed=True)
    failover = rms(dig, armed=True)
    ratio = failover / clean if clean > 0 else float("inf")
    print(f"# rms (t={t_drift}): clean {clean:.4f}, drifted "
          f"{drift:.4f}, failover {failover:.4f} "
          f"({ratio:.2f}x clean, gate <= {_RMS_RECOVERY_FACTOR}x)")
    if failover > _RMS_RECOVERY_FACTOR * clean:
        raise SystemExit(
            f"failover rung RMS {failover:.4f} exceeds "
            f"{_RMS_RECOVERY_FACTOR}x the clean plan's {clean:.4f}")
    if drift <= failover:
        raise SystemExit(
            f"drifted plan RMS {drift:.4f} not worse than the failover "
            f"rung's {failover:.4f} -- the scenario exercises nothing")
    return dict(t_drift=t_drift, fault=dataclasses.asdict(fault),
                rms_clean=round(clean, 6),
                rms_drift_no_failover=round(drift, 6),
                rms_drift_failover=round(failover, 6),
                failover_vs_clean=round(ratio, 4),
                gate_factor=_RMS_RECOVERY_FACTOR)


def run_ladder_cost(params, cfg, prompt_len, n_requests, seed,
                    segment_iters, repeats) -> list:
    """Throughput at every rung of the ladder (the degradation price),
    clean runs, median of repeats -- trajectory numbers, not gated."""
    from repro.resilience.failover import GuardedServer

    server = GuardedServer(
        params, cfg, slots=2, prompt_len=prompt_len, max_new_cap=16,
        seed=seed, segment_iters=segment_iters)
    reqs = _workload(cfg, prompt_len, n_requests, seed)
    server.compile_for(n_requests)
    rows = []
    for i, rung in enumerate(server.ladder):
        server.start_rung = i          # every rung is precompiled above
        runs = [server.run(reqs)[0].tok_s for _ in range(repeats)]
        med = statistics.median(runs)
        rows.append(dict(rung=i, label=rung.label,
                         tok_s_median=round(med, 2),
                         tok_s_runs=[round(r, 2) for r in runs]))
        print(f"# ladder rung {i} ({rung.label}): {med:.1f} tok/s "
              f"(median of {repeats})")
    if server.n_compiles != len(server.ladder):
        raise SystemExit(f"ladder sweep recompiled: {server.n_compiles} "
                         f"compiles for {len(server.ladder)} rungs")
    return rows


def run(arch: str = "minicpm-2b", smoke: bool = True, prompt_len: int = 8,
        n_requests: int = 4, repeats: int = 3, seed: int = 0,
        segment_iters: int = 4, path: str = _BENCH_JSON) -> dict:
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.resilience.faults import FaultModel

    cfg = get_config(arch, smoke=smoke)
    cfg = dataclasses.replace(cfg, cim_mode=True)
    raw_params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    params = jax.block_until_ready(lm.pack_cim_params(raw_params, cfg))
    fault = FaultModel(**_DRIFT)

    try:
        from .common import bench_header
    except ImportError:
        from common import bench_header
    result = dict(
        **bench_header(),
        config=dict(arch=arch, smoke=smoke, prompt_len=prompt_len,
                    n_requests=n_requests, repeats=repeats, seed=seed,
                    segment_iters=segment_iters),
        fingerprints=run_fingerprints(params, cfg, fault),
        clean_guarded=run_clean_guarded(params, cfg, prompt_len,
                                        n_requests, seed, segment_iters),
        detection=run_detection(params, cfg, fault, prompt_len,
                                n_requests, seed, segment_iters),
        rms=run_rms(raw_params, params, cfg, fault),
        ladder=run_ladder_cost(params, cfg, prompt_len, n_requests, seed,
                               segment_iters, repeats),
    )
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"# wrote {path}")
    return result


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True, help="--no-smoke runs the full-size arch")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--segment-iters", type=int, default=4)
    args = ap.parse_args()
    run(args.arch, args.smoke, args.prompt_len, args.requests,
        args.repeats, segment_iters=args.segment_iters)


if __name__ == "__main__":
    main()
