"""Deployment-plan Pareto benchmark: global single-config vs planned-mixed.

  PYTHONPATH=src python benchmarks/plan_pareto.py --smoke

For each benchmarked arch (a dense and an SSM config, exercising both
projection families) this runs the full planner pipeline at smoke scale:

  1. profile per-projection output-RMS sensitivity over a candidate grid
     (D/A split 6..0, ADC width by the no-clip rule, accumulate length
     16/32) with deterministic analog-noise emulation on;
  2. evaluate three deployment points on (RMS error, modeled cost,
     measured decode tok/s):
       global_digital  -- all-digital CIM everywhere (accuracy/cost ceiling)
       global_hybrid   -- the paper's 28nm prototype config everywhere
       planned_mixed   -- greedy-knapsack plan at the global-hybrid
                          accuracy budget
     plus planned_tight (60% of the budget -- forces digital onto the
     sensitive projections, showing a genuinely mixed assignment);
  3. write BENCH_plan.json and FAIL (exit 1) if the planned-mixed point
     is dominated by the global-hybrid point (worse accuracy AND worse
     modeled cost) -- the planner must sit on the Pareto front.

Measured tok/s comes from the serve driver on the SAME plan (packed,
AOT-compiled, zero recompiles across decode steps); RMS and modeled cost
come from repro.plan's profiler/cost machinery.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

_BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_plan.json")

BENCH_ARCHS = ("minicpm-2b", "mamba2-130m")


def _bench_candidates():
    # trimmed grid (full sweep is (6..0) x (16, 32)): CI runs one forward
    # per (site, candidate), so candidate count is the smoke-runtime knob
    from repro import plan as P
    return P.default_candidates(n_dcim_sweep=(6, 3, 0),
                                acc_len_sweep=(16, 32))


def _measure_tok_s(arch, smoke, plan, batch, prompt_len, gen):
    from repro.launch.serve import serve
    _, stats = serve(arch, smoke=smoke, batch=batch, prompt_len=prompt_len,
                     gen=gen, plan=plan, pack=True, return_stats=True)
    return stats["decode_tok_s"]


def run_arch(arch: str, smoke: bool, batch: int, prompt_len: int, gen: int,
             seed: int = 0) -> dict:
    import jax

    from repro import plan as P
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config(arch, smoke=smoke)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    tokens = P.calibration_batch(cfg, batch=batch, seq_len=prompt_len,
                                 seed=seed)
    cands = _bench_candidates()
    ref = P.reference_logits(params, cfg, tokens)   # shared float reference
    profile = P.profile_sensitivities(params, cfg, tokens, cands, ref=ref)

    res = P.pareto_search(params, cfg, tokens, candidates=cands,
                          profile=profile, ref=ref)
    res_tight = P.pareto_search(params, cfg, tokens, candidates=cands,
                                profile=profile, ref=ref, budget_scale=0.6)

    plans = {
        "global_digital": P.DeploymentPlan.uniform(
            P.digital_candidate().entry),
        "global_hybrid": P.DeploymentPlan.uniform(
            P.prototype_candidate().entry),
        "planned_mixed": res.plan,
        "planned_tight": res_tight.plan,
    }
    points = {}
    for name, plan in plans.items():
        pt = P.evaluate_plan(params, cfg, tokens, plan, profile, ref=ref)
        if name != "planned_tight":      # tight point: rms/cost axes only
            pt["decode_tok_s"] = _measure_tok_s(arch, smoke, plan, batch,
                                                prompt_len, gen)
        points[name] = {k: round(float(v), 6) for k, v in pt.items()}
    points["planned_mixed"]["assignment"] = dict(res.assignment)
    points["planned_tight"]["assignment"] = dict(res_tight.assignment)

    pm, gh = points["planned_mixed"], points["global_hybrid"]
    dominated = (pm["measured_rms"] > gh["measured_rms"]
                 and pm["combined"] > gh["combined"])
    dominates = (pm["combined"] < gh["combined"]
                 and pm["measured_rms"] <= gh["measured_rms"])
    out = dict(
        sites={s: profile.macs_per_token(s) for s in profile.sites},
        sensitivity=profile.as_table(),
        points=points,
        planned_dominated_by_global_hybrid=dominated,
        planned_dominates_global_hybrid=dominates,
        search=dict(n_moves=len(res.moves), n_reverts=res.n_reverts,
                    budget_measured=round(res.budget_measured, 6)),
    )
    print(f"# {arch}: planned-mixed rms {pm['measured_rms']:.4f} @ cost "
          f"{pm['combined']:.3f} ({pm['decode_tok_s']} tok/s) vs "
          f"global-hybrid rms {gh['measured_rms']:.4f} @ cost "
          f"{gh['combined']:.3f} ({gh['decode_tok_s']} tok/s) -> "
          f"{'DOMINATES' if dominates else 'on front'}"
          f"{' [DOMINATED!]' if dominated else ''}")
    return out


def run(smoke: bool = True, batch: int = 2, prompt_len: int = 16,
        gen: int = 16, archs=BENCH_ARCHS, path: str = _BENCH_JSON) -> dict:
    try:
        from .common import bench_header
    except ImportError:
        from common import bench_header
    result = dict(**bench_header(),
                  config=dict(smoke=smoke, batch=batch,
                              prompt_len=prompt_len, gen=gen,
                              archs=list(archs)),
                  archs={})
    for arch in archs:
        result["archs"][arch] = run_arch(arch, smoke, batch, prompt_len, gen)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"# wrote {path}")
    bad = [a for a, r in result["archs"].items()
           if r["planned_dominated_by_global_hybrid"]]
    if bad:
        raise SystemExit(
            f"planned-mixed point DOMINATED by global-hybrid on {bad} "
            "(worse accuracy AND worse modeled cost) -- planner regression")
    return result


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--archs", nargs="*", default=list(BENCH_ARCHS))
    args = ap.parse_args()
    run(args.smoke, args.batch, args.prompt_len, args.gen, args.archs)


if __name__ == "__main__":
    main()
