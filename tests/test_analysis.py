"""cimlint (repro.analysis): every rule class must FIRE on a seeded
violation and stay SILENT on the real package.

The seeded-violation half is the analyzer's own regression net: a rule
that stops firing is indistinguishable from a clean repo, so each rule
gets a minimal guilty fixture (trace, kernel/VMEM, grid-aliasing, AST)
and an innocent twin.  The clean-pass half pins the tier-1.5 CI gate:
``--strict`` passing on HEAD is an acceptance criterion, so a test
failure here means either a real regression in src/repro or an analyzer
false positive -- both block.
"""
import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import kernels as AK
from repro.analysis import lint as AL
from repro.analysis import obs_rules as OB
from repro.analysis import tracer as AT
from repro.analysis.report import AnalysisReport, Violation, load_baseline
from repro.kernels.ccim_matmul import autotune


def _rules(report):
    return {v.rule for v in report.violations}


# ---------------------------------------------------------------------------
# trace rules (seeded)
# ---------------------------------------------------------------------------


def test_trace_f64_fires():
    rep = AnalysisReport()
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.arange(4, dtype=jnp.float64))
        AT.check_no_f64("seeded", jaxpr, rep)
    assert "TRACE-F64" in _rules(rep)


def test_trace_f64_clean_on_f32():
    rep = AnalysisReport()
    jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
        jnp.arange(4, dtype=jnp.float32))
    AT.check_no_f64("clean", jaxpr, rep)
    assert rep.passed


def test_trace_host_sync_fires_inside_while_body():
    def guilty(x):
        def body(v):
            y = jax.pure_callback(
                lambda a: np.asarray(a) + 1, jax.ShapeDtypeStruct((), x.dtype),
                v)
            return y
        return jax.lax.while_loop(lambda v: v < 10, body, x)

    rep = AnalysisReport()
    AT.check_no_host_sync("seeded", jax.make_jaxpr(guilty)(jnp.float32(0)),
                          rep)
    viols = [v for v in rep.violations if v.rule == "TRACE-HOST-SYNC"]
    assert viols and "while" in viols[0].detail


def test_trace_donation_fires_when_alias_impossible():
    # the donated operand never reaches an output with a matching
    # shape/dtype, so XLA cannot alias it -> the donation is silently lost
    def f(x, dead):
        return x * 2.0

    rep = AnalysisReport()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # XLA warns about the lost donation
        AT.check_donation("seeded", f, (1,),
                          (jnp.zeros((4,)), jnp.zeros((8, 8))), rep)
    assert "TRACE-DONATION" in _rules(rep)


def test_trace_donation_clean_when_aliased():
    def f(x, cache):
        return x, cache + 1.0

    rep = AnalysisReport()
    AT.check_donation("clean", f, (1,),
                      (jnp.zeros((4,)), jnp.zeros((8, 8))), rep)
    assert rep.passed
    assert rep.census["donation"]["clean"]["aliased_buffers"] >= 1


@dataclasses.dataclass(frozen=True)
class _BadCfg:
    # a frozen dataclass whose hash dies at call time (list field)
    knobs: list
    cim_plan: object = None


def test_trace_static_hash_fires_on_unhashable_cfg():
    rep = AnalysisReport()
    AT.check_static_keys(_BadCfg(knobs=[1, 2]), {}, rep)
    assert "TRACE-STATIC-HASH" in _rules(rep)


def test_trace_static_leak_fires_on_array_in_meta():
    from repro.core.engine import PackedCimWeights
    z = jnp.zeros((2, 2), jnp.int8)
    leaky = PackedCimWeights(
        scale=jnp.ones((1, 2)), sign=z, mag=z, gemm_w=jnp.zeros((1, 2, 2)),
        gemm_planes=jnp.zeros((1, 2, 2)), pallas_w=z,
        pallas_planes=jnp.zeros((1, 2, 2)),
        k_dim=2, n_dim=2, cfg=jnp.zeros((1,)))   # <- array in a meta slot
    rep = AnalysisReport()
    AT.check_static_keys(_BadCfg(knobs=[]), {"w": leaky}, rep)
    # the array in the static slot trips the leak rule (and, being
    # unhashable, the hash rule too)
    assert "TRACE-STATIC-LEAK" in _rules(rep)


# ---------------------------------------------------------------------------
# kernel rules (seeded, via hand-built records)
# ---------------------------------------------------------------------------


def _record(grid, specs, scratch=0, name="seeded"):
    return AK.PallasCallRecord(name=name, grid=grid, specs=specs,
                               scratch_bytes=scratch,
                               num_scalar_prefetch=0, scalar_shapes=[])


def test_kernel_block_fires_on_misaligned_lane():
    spec = AK.SpecView((48, 100), lambda i: (i, 0), (96, 200), jnp.float32)
    rep = AnalysisReport()
    AK.check_blocking(_record((2,), [spec]), rep)
    assert "KERNEL-BLOCK" in _rules(rep)


def test_kernel_block_fires_on_int8_sublane():
    # 16 rows of int8: below the 32-sublane floor and not the whole axis
    spec = AK.SpecView((16, 128), lambda i: (i, 0), (64, 128), jnp.int8)
    rep = AnalysisReport()
    AK.check_blocking(_record((4,), [spec]), rep)
    assert any("sublane" in v.detail for v in rep.violations)


def test_kernel_block_clean_on_whole_axis():
    # lane dim 100 < 128 but spans the full axis: resident, no alignment
    spec = AK.SpecView((32, 100), lambda i: (i, 0), (64, 100), jnp.float32)
    rep = AnalysisReport()
    AK.check_blocking(_record((2,), [spec]), rep)
    assert rep.passed


def test_kernel_vmem_fires_over_budget():
    # 1024x4096 f32 double-buffered = 32 MiB > 16 MiB
    spec = AK.SpecView((1024, 4096), lambda i: (i, 0), (4096, 4096),
                       jnp.float32)
    rep = AnalysisReport()
    AK.check_vmem(_record((4,), [spec]), rep)
    assert "KERNEL-VMEM" in _rules(rep)
    assert rep.vmem_table and not rep.vmem_table[0]["ok"]


def test_kernel_vmem_resident_counts_once():
    # grid-invariant block: counted 1x (resident), stays under budget
    spec = AK.SpecView((1024, 2560), lambda i: (0, 0), (1024, 2560),
                       jnp.float32)
    rep = AnalysisReport()
    AK.check_vmem(_record((4,), [spec]), rep)
    assert rep.passed
    assert rep.vmem_table[0]["blocks"][0]["buffers"] == 1


def test_kernel_race_fires_on_noncontiguous_revisit():
    out = AK.SpecView((8, 8), lambda i: (i % 2, 0), (16, 8), jnp.float32,
                      is_output=True)
    rep = AnalysisReport()
    AK.check_grid_aliasing(_record((4,), [out]), rep)
    assert "KERNEL-RACE" in _rules(rep)


def test_kernel_race_clean_on_accumulation_order():
    # canonical GEMM: k innermost, output tile (i, j) revisited only by
    # the contiguous run of k steps
    out = AK.SpecView((8, 8), lambda i, j, k: (i, j), (16, 16, 8),
                      jnp.float32, is_output=True)
    rep = AnalysisReport()
    AK.check_grid_aliasing(_record((2, 2, 4), [out]), rep)
    assert rep.passed


def _spied(kernel, in_shape, out_shape):
    from jax.experimental import pallas as pl
    records = []
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
    with AK.record_pallas_calls(records):
        jax.eval_shape(lambda x: pl.pallas_call(
            kernel, out_shape=sds(out_shape), interpret=True)(x),
            sds(in_shape))
    return records[0]


def test_kernel_layout_fires_on_lane_split_reshape():
    # the pre-bring-up conversion split: (8, 128) -> (8, 8, 16) splits the
    # lane axis, which Mosaic refuses ("unsupported shape cast")
    def guilty(x_ref, o_ref):
        o_ref[...] = x_ref[...].reshape(8, 8, 16).sum(axis=1)
    rep = AnalysisReport()
    AK.check_layout(_spied(guilty, (8, 128), (8, 16)), rep)
    assert "KERNEL-LAYOUT" in _rules(rep)


def test_kernel_layout_clean_on_conversion_slices():
    # the same per-conversion sum from static lane slices
    def clean(x_ref, o_ref):
        x = x_ref[...]
        o_ref[...] = sum(x[:, c * 16:(c + 1) * 16] for c in range(8))
    rep = AnalysisReport()
    AK.check_layout(_spied(clean, (8, 128), (8, 16)), rep)
    assert rep.passed and rep.checked["KERNEL-LAYOUT"] == 1


def test_spy_captures_real_dispatch():
    records = []
    AK.capture_ccim_matmul(records, M=4, K=256, N=256,
                           cfg=AK.CCIMConfig())
    assert records, "spy saw no pallas_call on the skinny decode path"
    rec = records[0]
    assert rec.grid and rec.specs and rec.kernel_jaxpr is not None
    rep = AnalysisReport()
    AK.check_record(rec, rep)
    assert rep.passed


# ---------------------------------------------------------------------------
# tuning-cache validation (the autotune loader satellite)
# ---------------------------------------------------------------------------


def test_entry_violation_rules():
    bad_bn = {"bn": 96, "bk": 512}       # 96 not lane-aligned
    bad_bk = {"bn": 128, "bk": 100}      # 100 not sublane/acc aligned
    huge = {"bn": 512, "bk": 512}        # blows the 8 MiB residency budget
    key = "tpu|skinny_pallas|K8192|N1024|L16|P2"
    assert autotune.entry_violation(key, bad_bn)
    assert autotune.entry_violation(key, bad_bk)
    assert autotune.entry_violation(
        "tpu|skinny_pallas|K65536|N1024|L16|P4", huge)
    assert autotune.entry_violation(key, {"bn": 128, "bk": 512}) is None
    assert autotune.entry_violation(
        "cpu|fast_gemm|gemv|C16|N128|L16", {"chunk_block": 64})
    assert autotune.entry_violation(
        "cpu|fast_gemm|gemv|C16|N128|L16", {"chunk_block": 8}) is None


def test_entries_drop_illegal_cached_blocks(tmp_path, monkeypatch):
    cache = {"version": 1, "entries": {
        "tpu|skinny_pallas|K1024|N512|L16|P2": {"bn": 96, "bk": 512},
        "cpu|fast_gemm|gemv|C16|N128|L16": {"chunk_block": 8},
    }}
    p = tmp_path / "TUNING_CACHE.json"
    p.write_text(json.dumps(cache))
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(p))
    autotune._state["entries"] = None    # force reload from the new path
    try:
        with pytest.warns(UserWarning, match="illegal tuning cache"):
            entries = autotune._entries()
        assert "cpu|fast_gemm|gemv|C16|N128|L16" in entries
        assert "tpu|skinny_pallas|K1024|N512|L16|P2" not in entries
    finally:
        autotune._state["entries"] = None   # other tests reload the real one


# ---------------------------------------------------------------------------
# AST rules (seeded fixtures)
# ---------------------------------------------------------------------------


def _lint(src, relpath="pkg/mod.py"):
    rep = AnalysisReport()
    AL.lint_source(relpath, src, rep)
    return rep


def test_ast_import_config_fires():
    rep = _lint("import jax\njax.config.update('jax_enable_x64', True)\n")
    assert "AST-IMPORT-CONFIG" in _rules(rep)


def test_ast_import_config_allows_function_scope_and_main():
    rep = _lint(
        "import jax\n"
        "def setup():\n"
        "    jax.config.update('jax_enable_x64', True)\n"
        "if __name__ == '__main__':\n"
        "    jax.config.update('jax_platform_name', 'cpu')\n")
    assert rep.passed


def test_ast_impure_trace_fires():
    rep = _lint(
        "import jax, time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * time.time()\n")
    assert "AST-IMPURE-TRACE" in _rules(rep)


def test_ast_impure_trace_ignores_jax_random_and_host_fns():
    rep = _lint(
        "import jax, time\n"
        "@jax.jit\n"
        "def f(x, key):\n"
        "    return x + jax.random.normal(key, x.shape)\n"
        "def bench(f, x):\n"
        "    t0 = time.time()\n"
        "    f(x)\n"
        "    return time.time() - t0\n")
    assert rep.passed


def test_ast_host_sync_fires_in_while_body():
    rep = _lint(
        "import jax\nimport numpy as np\n"
        "def body(c):\n"
        "    return c + np.asarray([1])\n"
        "def run(c):\n"
        "    return jax.lax.while_loop(lambda c: c[0] < 3, body, c)\n")
    assert "AST-HOST-SYNC" in _rules(rep)


def test_ast_host_sync_fires_transitively_through_switch():
    rep = _lint(
        "import jax\n"
        "def helper(c):\n"
        "    return c.item()\n"
        "def branch(c):\n"
        "    return helper(c)\n"
        "def run(i, c):\n"
        "    return jax.lax.switch(i, [branch, lambda c: c], c)\n")
    assert "AST-HOST-SYNC" in _rules(rep)


def test_ast_host_sync_ignores_host_side_harvest():
    rep = _lint(
        "import jax\nimport numpy as np\n"
        "def run(c):\n"
        "    out = jax.lax.while_loop(lambda c: c[0] < 3,\n"
        "                             lambda c: c + 1, c)\n"
        "    return np.asarray(out)\n")
    assert rep.passed


def test_ast_static_meta_fires_on_unfrozen_dataclass():
    rep = _lint(
        "import dataclasses, jax\n"
        "@dataclasses.dataclass\n"
        "class Meta:\n"
        "    k: int\n"
        "jax.tree_util.register_dataclass(Meta, data_fields=[],\n"
        "                                 meta_fields=['k'])\n")
    assert "AST-STATIC-META" in _rules(rep)


def test_ast_static_meta_clean_on_frozen():
    rep = _lint(
        "import dataclasses, jax\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class Meta:\n"
        "    k: int\n"
        "jax.tree_util.register_dataclass(Meta, data_fields=[],\n"
        "                                 meta_fields=['k'])\n")
    assert rep.passed


def test_ast_noise_seed_fires_in_numerics_module():
    src = ("import jax\n"
           "def noisy(cfg):\n"
           "    return jax.random.PRNGKey(0)\n")
    rep = _lint(src, relpath="core/ccim.py")
    assert "AST-NOISE-SEED" in _rules(rep)
    # same code outside the numerics modules is fine (init-time seeding)
    assert _lint(src, relpath="models/lm.py").passed


def test_ast_noise_seed_clean_on_fold_in():
    rep = _lint(
        "import jax\n"
        "def noisy(cfg, tag):\n"
        "    return jax.random.fold_in(\n"
        "        jax.random.PRNGKey(cfg.cim_noise_seed), tag)\n",
        relpath="models/layers.py")
    assert rep.passed


# ---------------------------------------------------------------------------
# obs (telemetry) rules: seeded violation + innocent twin
# ---------------------------------------------------------------------------


def test_obs_ring_donation_fires_on_dropped_alias():
    # two leaves "donated" but the lowering only honored one alias
    rep = AnalysisReport()
    OB.check_ring_donation(
        "seeded", 'arg {tf.aliasing_output = 0 : i32} ...', 2, rep)
    viols = [v for v in rep.violations if v.rule == "OBS-RING-DONATION"]
    assert viols and "copied every" in viols[0].detail


def test_obs_ring_donation_clean_when_all_leaves_alias():
    rep = AnalysisReport()
    text = " ".join('{tf.aliasing_output = %d : i32}' % i for i in range(5))
    OB.check_ring_donation("clean", text, 5, rep)
    assert rep.passed
    assert rep.census["obs_donation"]["clean"]["aliased_buffers"] == 5


def test_obs_host_sync_fires_on_callback_metric():
    # a "telemetry" implementation that ships a counter through a host
    # callback inside the loop body -- exactly what the rings forbid
    def guilty(x):
        def body(v):
            jax.debug.callback(lambda a: None, v)   # the callback metric
            return v + 1
        return jax.lax.while_loop(lambda v: v < 8, body, x)

    rep = AnalysisReport()
    OB.check_obs_host_sync("seeded", jax.make_jaxpr(guilty)(jnp.int32(0)),
                           rep)
    viols = [v for v in rep.violations if v.rule == "OBS-HOST-SYNC"]
    assert viols and "while" in viols[0].detail


def test_obs_host_sync_clean_on_ring_push():
    # the innocent twin: the same counter kept on-device via a ring push
    from repro.obs.rings import ObsConfig, init_obs_state, ring_push

    def clean(x):
        obs = init_obs_state(ObsConfig(event_cap=4, iter_cap=4))

        def body(carry):
            v, ob = carry
            ob = ring_push(ob, 0, v, v, do=v % 2 == 0)
            return v + 1, ob
        return jax.lax.while_loop(lambda c: c[0] < 8, body, (x, obs))

    rep = AnalysisReport()
    OB.check_obs_host_sync("clean", jax.make_jaxpr(clean)(jnp.int32(0)), rep)
    assert rep.passed


def test_obs_audit_clean_on_real_scheduler():
    rep = AnalysisReport()
    OB.audit_obs(rep)
    assert rep.passed, rep.summary()
    don = rep.census["obs_donation"]["scheduler_loop[obs]"]
    assert don["aliased_buffers"] >= don["ring_leaves"] == 5


# ---------------------------------------------------------------------------
# report / baseline plumbing
# ---------------------------------------------------------------------------


def test_baseline_diff_waives_only_known_keys(tmp_path):
    rep = AnalysisReport()
    rep.add("KERNEL-VMEM", "k@a", "old")
    p = tmp_path / "ANALYSIS.json"
    rep.save(str(p))
    base = load_baseline(str(p))

    cur = AnalysisReport()
    cur.add("KERNEL-VMEM", "k@a", "still here")   # waived
    cur.add("KERNEL-VMEM", "k@b", "new")          # not waived
    new = cur.new_violations(base)
    assert [v.where for v in new] == ["k@b"]
    assert load_baseline(str(tmp_path / "missing.json")) == set()


def test_violation_str_and_counts():
    rep = AnalysisReport()
    rep.add("X", "y", "z")
    assert "X" in str(Violation("X", "y", "z"))
    assert rep.counts() == {"X": 1}
    assert not rep.passed


# ---------------------------------------------------------------------------
# clean pass over the real package (the CI gate's contract)
# ---------------------------------------------------------------------------


def test_lint_clean_on_real_package():
    rep = AnalysisReport()
    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    n = AL.lint_package(root, rep)
    assert n > 50
    assert rep.passed, rep.summary()


def test_kernel_sweep_clean_on_real_package():
    rep = AnalysisReport()
    recs = AK.sweep_kernels(rep)
    assert rep.passed, rep.summary()
    # all five kernel families dispatched
    names = {r.name for r in recs}
    assert len(names) >= 5, names
    # every design point (n_dcim 0-6 x adc 7-9 x L16/32) audited
    assert rep.census["design_points"] == 42
    assert len(recs) >= 42 * len(AK.SHAPE_CLASS_MS)


def test_trace_audit_clean_on_serve_path():
    rep = AnalysisReport()
    AT.audit_serve_path(rep, with_scheduler=False)
    assert rep.passed, rep.summary()
    assert rep.census["n_executables"] >= 4
    don = rep.census["donation"]
    assert all(d["aliased_buffers"] >= d["donated_leaves"]
               for d in don.values())
