"""Deployment-planner subsystem tests: plan pytree/resolution semantics,
mixed-fidelity execution parity (packed == unpacked, incl. noise), the
profiler/search contracts, cost-model anchoring, and the generalized
prepacked Pallas kernel serving every plan design point."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan as P
from repro.configs import get_config
from repro.core import DEFAULT_CONFIG, PackedCimWeights, cim_matmul, costmodel
from repro.core import pack_cim_weights
from repro.models import lm

D = DEFAULT_CONFIG


def _entry(label="h", **kw):
    fid = kw.pop("fidelity", "fast")
    return P.PlanEntry(cfg=dataclasses.replace(D, **kw), fidelity=fid,
                       label=label)


# ---------------------------------------------------------------------------
# plan semantics: static, hashable, path resolution
# ---------------------------------------------------------------------------


def test_plan_resolution_and_fallback():
    plan = P.DeploymentPlan.from_dict(
        {"attn/wq": P.DIGITAL_ENTRY, "w2": _entry("a", n_dcim_products=0,
                                                  adc_bits=8)},
        default=P.HYBRID_ENTRY)
    assert plan.resolve("attn/wq").fidelity == "exact"       # exact path
    assert plan.resolve("mlp/w2").label == "a"               # basename
    assert plan.resolve("shared/mlp/w2").label == "a"        # basename, deep
    assert plan.resolve("attn/wk") == P.HYBRID_ENTRY         # default
    assert plan.resolve(None) == P.HYBRID_ENTRY


def test_plan_hashable_and_order_independent():
    a = P.DeploymentPlan.from_dict({"x": P.DIGITAL_ENTRY,
                                    "y": P.HYBRID_ENTRY})
    b = P.DeploymentPlan.from_dict({"y": P.HYBRID_ENTRY,
                                    "x": P.DIGITAL_ENTRY})
    assert a == b and hash(a) == hash(b)
    # rides inside the frozen ModelConfig (jit-static packing requires it)
    cfg = dataclasses.replace(get_config("minicpm-2b", smoke=True),
                              cim_mode=True, cim_plan=a)
    hash(cfg)


def test_plan_rejects_unservable_fidelity():
    with pytest.raises(ValueError, match="fidelity"):
        P.PlanEntry(fidelity="bit_true")


# ---------------------------------------------------------------------------
# planned execution: bit-exact contracts through the model zoo
# ---------------------------------------------------------------------------


def _model(arch="minicpm-2b", seed=0):
    cfg = get_config(arch, smoke=True)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    toks = jnp.asarray(P.calibration_batch(cfg, batch=1, seq_len=8))
    return cfg, params, toks


def test_float_plan_is_bit_identical_to_fp():
    cfg, params, toks = _model()
    ref, _ = lm.forward(params, cfg, toks, remat=False)
    pcfg = dataclasses.replace(
        cfg, cim_mode=True, cim_plan=P.DeploymentPlan.uniform(P.FLOAT_ENTRY))
    out, _ = lm.forward(params, pcfg, toks, remat=False)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_uniform_prototype_plan_matches_global_cim():
    cfg, params, toks = _model()
    g, _ = lm.forward(params, dataclasses.replace(cfg, cim_mode=True), toks,
                      remat=False)
    pcfg = dataclasses.replace(
        cfg, cim_mode=True,
        cim_plan=P.DeploymentPlan.uniform(P.prototype_candidate().entry))
    u, _ = lm.forward(params, pcfg, toks, remat=False)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(u))


MIXED = P.DeploymentPlan.from_dict({
    "mlp/w2": P.DIGITAL_ENTRY,
    "attn/wq": _entry("analog0/adc8", n_dcim_products=0, adc_bits=8),
    "attn/wo": _entry("hybrid5/adc8", n_dcim_products=5, adc_bits=8),
    "mlp/w3": P.FLOAT_ENTRY,
}, default=_entry("hybrid3/adc8/L32", acc_len=32, adc_bits=8))


def test_mixed_pack_structure_and_config_meta():
    from repro.core import FusedPackedCimWeights
    cfg, params, _ = _model()
    pcfg = dataclasses.replace(cfg, cim_mode=True, cim_plan=MIXED)
    packed = lm.pack_cim_params(params, pcfg)
    blk = packed["layers"]
    # float-fidelity site stays a raw float matrix (and blocks w1+w3 fusion)
    assert not isinstance(blk["mlp"]["w3"], PackedCimWeights)
    assert "w1+w3" not in blk["mlp"]
    # every other site packs under ITS OWN entry's config (static meta)
    assert blk["mlp"]["w2"].cfg == D                      # digital: default
    assert blk["attn"]["wq"].cfg.n_dcim_products == 0
    # stacked pack: axis 0 is the scanned layer axis, axis 1 plane count
    assert blk["attn"]["wq"].pallas_planes.shape[1] == 0  # no folded planes
    assert blk["attn"]["wo"].cfg.n_dcim_products == 5
    # fusion is keyed by the plan: wq has its own entry, so only the
    # entry-compatible wk/wv fuse (the group SPLITS, it doesn't disappear)
    kv = blk["attn"]["wk+wv"]
    assert isinstance(kv, FusedPackedCimWeights)
    assert kv.packed.cfg.acc_len == 32                    # plan default
    assert blk["attn"]["wq"].pallas_w.shape[0] == cfg.n_layers  # scan axis


def test_planned_forward_packed_matches_unpacked_incl_noise():
    cfg, params, toks = _model()
    pcfg = dataclasses.replace(cfg, cim_mode=True, cim_plan=MIXED,
                               cim_noise_seed=11)
    packed = lm.pack_cim_params(params, pcfg)
    u, _ = lm.forward(params, pcfg, toks, remat=False)
    q, _ = lm.forward(packed, pcfg, toks, remat=False)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


def test_planned_serve_end_to_end_packed_parity():
    from repro.launch.serve import serve
    u = serve("minicpm-2b", batch=2, prompt_len=8, gen=3, plan=MIXED,
              pack=False, noise_seed=7)
    p = serve("minicpm-2b", batch=2, prompt_len=8, gen=3, plan=MIXED,
              pack=True, noise_seed=7)
    np.testing.assert_array_equal(u, p)


def test_planned_scheduler_serves_unchanged():
    """A planned+packed model through the continuous-batching scheduler:
    one AOT-compiled loop, zero recompiles, tokens identical to the
    lock-step baseline (asserted inside serve_continuous)."""
    from repro.launch.serve import serve_continuous
    _, st = serve_continuous("minicpm-2b", slots=2, prompt_len=8,
                             n_requests=4, stop_lengths=(3, 5, 4, 2),
                             plan=MIXED, pack=True)
    assert st["tokens_match_lockstep"]


def test_planned_ssm_family():
    cfg, params, toks = _model("mamba2-130m")
    plan = P.DeploymentPlan.from_dict(
        {"mamba/out_proj": P.DIGITAL_ENTRY},
        default=_entry("hybrid3/adc8/L32", acc_len=32, adc_bits=8))
    pcfg = dataclasses.replace(cfg, cim_mode=True, cim_plan=plan,
                               cim_noise_seed=3)
    packed = lm.pack_cim_params(params, pcfg)
    u, _ = lm.forward(params, pcfg, toks, remat=False)
    q, _ = lm.forward(packed, pcfg, toks, remat=False)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


# ---------------------------------------------------------------------------
# profiler + search contracts
# ---------------------------------------------------------------------------


def _small_candidates():
    return [P.digital_candidate(), P.prototype_candidate(),
            P.make_candidate("hybrid3/adc8/L32",
                             dataclasses.replace(D, acc_len=32, adc_bits=8))]


def test_profiler_and_search_contracts():
    cfg, params, toks = _model()
    cands = _small_candidates()
    sites = ["mlp/w2", "mlp/w3", "attn/wv"]
    prof = P.profile_sensitivities(params, cfg, toks, cands, sites=sites)
    # digital (quantization-only) is the accuracy ceiling at every site
    for s in sites:
        assert prof.rms[s]["digital"] < prof.rms[s][cands[1].label]
        assert prof.rms[s]["digital"] < 0.1
    # macs accounting matches the stacked leaf shapes
    assert prof.macs_per_token("mlp/w2") == cfg.n_layers * 256 * 128

    res = P.pareto_search(params, cfg, toks, candidates=cands,
                          profile=prof, sites=sites)
    # every profiled site is assigned, the plan serves it
    assert set(res.assignment) == set(sites)
    # budget respected end-to-end (validated measurement)
    assert res.measured_rms <= res.budget_measured * 1.02 + 1e-9
    # the knapsack only ever cheapens the all-digital starting point
    assert res.cost["combined"] <= res.cost_digital["combined"] + 1e-9
    # with the default (prototype) budget the planned point must not be
    # MORE expensive than running the prototype everywhere (domination
    # contract, enforced at bench scale by plan_pareto.py)
    assert res.cost["combined"] <= res.cost_budget_plan["combined"] + 1e-9
    # tightening the budget spends more digital, never less accuracy
    tight = P.pareto_search(params, cfg, toks, candidates=cands,
                            profile=prof, sites=sites, budget_scale=0.3)
    assert tight.measured_rms <= res.budget_measured * 0.3 * 1.02 + 1e-9
    assert tight.cost["combined"] >= res.cost["combined"] - 1e-9


def test_search_with_partial_precomputed_profile():
    """A precomputed profile that lacks the digital/budget columns gets
    them auto-profiled and merged (used to KeyError), and a ``sites``
    subset passed alongside a wider profile restricts the plan scope
    (used to be silently ignored)."""
    cfg, params, toks = _model()
    proto = P.prototype_candidate()
    sites = ["mlp/w2", "mlp/w3"]
    prof = P.profile_sensitivities(params, cfg, toks, [proto],
                                   sites=sites + ["attn/wv"])
    res = P.pareto_search(params, cfg, toks, candidates=[proto],
                          profile=prof, sites=sites)
    assert set(res.assignment) == set(sites)           # scope respected
    assert "digital" in res.profile.labels             # merged column
    with pytest.raises(ValueError, match="not in the precomputed profile"):
        P.pareto_search(params, cfg, toks, candidates=[proto], profile=prof,
                        sites=["attn/wq"])


def test_search_rejects_candidate_label_collisions():
    """Candidate identity is label-keyed (profile columns, assignments):
    a user candidate aliasing the reserved 'digital' label, or duplicate
    labels, must fail loudly instead of silently mixing rows."""
    cfg, params, toks = _model()
    impostor = P.make_candidate(
        "digital", dataclasses.replace(D, n_dcim_products=1))
    with pytest.raises(ValueError, match="reserved"):
        P.pareto_search(params, cfg, toks, candidates=[impostor])
    proto = P.prototype_candidate()
    dup = P.make_candidate(proto.label,
                           dataclasses.replace(D, adc_bits=6))
    with pytest.raises(ValueError, match="duplicate candidate labels"):
        P.pareto_search(params, cfg, toks, candidates=[proto, dup])


def test_profiler_unknown_site_rejected():
    cfg, params, toks = _model()
    with pytest.raises(ValueError, match="unknown projection site"):
        P.profile_sensitivities(params, cfg, toks, _small_candidates(),
                                sites=["attn/nope"])


def test_shared_block_macs_count_per_group_execution():
    """The zamba2 shared block's weights park once but EXECUTE once per
    layer group: energy/latency cost per token must scale with the group
    count while area (parked weights) must not."""
    cfg, params, toks = _model("zamba2-1.2b")
    sites = ["shared/attn/wq", "mamba/w_z"]
    prof = P.profile_sensitivities(params, cfg, toks,
                                   [P.prototype_candidate()], sites=sites)
    n_groups = cfg.n_layers // cfg.shared_attn_period
    assert n_groups > 1
    assert (prof.macs_per_token("shared/attn/wq")
            == n_groups * prof.weights_per_site("shared/attn/wq"))
    assert (prof.macs_per_token("mamba/w_z")
            == prof.weights_per_site("mamba/w_z"))


def test_serve_noise_seed_requires_cim():
    from repro.launch.serve import serve
    with pytest.raises(ValueError, match="needs\\s+cim=True"):
        serve("minicpm-2b", batch=2, prompt_len=8, gen=3, noise_seed=7)


# ---------------------------------------------------------------------------
# cost model anchoring (satellite: macro_cost + paper headline ratios)
# ---------------------------------------------------------------------------


def test_figS1_headline_ratios_reproduced():
    s = costmodel.figS1_comparison(D)["savings"]
    assert abs(s["area_pct_vs_duplicated"] - 35.0) < 5.0
    assert abs(s["latency_pct_vs_sequential"] - 54.0) < 1.5
    assert abs(s["power_pct_vs_duplicated"] - 24.0) < 1.0
    assert abs(costmodel.tops_per_watt(D) - 35.0) < 1.0


def test_macro_cost_defaults_and_orderings():
    hybrid = costmodel.macro_cost(D)
    digital = costmodel.macro_cost(D, "exact")
    analog = costmodel.macro_cost(
        dataclasses.replace(D, n_dcim_products=0, adc_bits=8))
    # per-MAC energy consistent with the conversion accounting
    e = costmodel.energy_per_conversion_pj(D)["total"]
    assert hybrid.energy_pj_per_mac == pytest.approx(e / D.acc_len)
    # the paper's premise: all-digital costs the most area AND energy,
    # the hybrid undercuts the all-analog design too (bigger ADC + DACs)
    assert digital.area_mm2_per_kb > analog.area_mm2_per_kb \
        > hybrid.area_mm2_per_kb
    assert digital.energy_pj_per_mac > analog.energy_pj_per_mac \
        > hybrid.energy_pj_per_mac
    # longer accumulates amortize per-conversion overhead
    l32 = costmodel.macro_cost(dataclasses.replace(D, acc_len=32,
                                                   adc_bits=8))
    assert l32.energy_pj_per_mac < hybrid.energy_pj_per_mac
    assert l32.latency_cyc_per_mac == hybrid.latency_cyc_per_mac / 2
    with pytest.raises(ValueError, match="no cost model"):
        costmodel.macro_cost(D, "float")


def test_min_adc_bits_matches_prototype():
    assert P.min_adc_bits(D) == D.adc_bits                     # top-3 -> 7b
    assert P.min_adc_bits(
        dataclasses.replace(D, n_dcim_products=0)) == 8        # all-analog


# ---------------------------------------------------------------------------
# generalized prepacked Pallas kernel: every plan design point, bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(),                                       # prototype top-3
    dict(n_dcim_products=1),
    dict(n_dcim_products=5, adc_bits=8),
    dict(n_dcim_products=0, adc_bits=8),          # all-analog, no planes
    dict(acc_len=32, adc_bits=8),                 # planner's long-accumulate
])
def test_prepacked_pallas_serves_all_plan_points(kw):
    cfg = dataclasses.replace(D, **kw)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (8, 100))
    w = jax.random.normal(k2, (100, 24))
    p = pack_cim_weights(w, cfg)
    ref = cim_matmul(x, w, cfg, use_pallas=False)        # unpacked fast GEMM
    y = cim_matmul(x, p, cfg, use_pallas=True)           # kernel (interpret)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(y))
