"""Device-resident serving telemetry (repro.obs + scheduler rings).

The telemetry contract has three load-bearing clauses, each pinned here:

1. OBSERVER EFFECT = ZERO TOKENS: a metrics-on scheduler emits tokens
   bit-identical to the metrics-off one, across greedy and sampled
   decoding and all three loop variants (contiguous / paged /
   speculative).  Rings only read values the loop already computes.
2. RINGS TELL THE TRUTH: the TTFT read back from the device event ring
   equals the instrumented runner's host-observed ``first_iter`` exactly
   (iteration units, no estimation), and ring overflow saturates --
   counters stay exact, rows drop, tokens never corrupt.
3. THE OFF SWITCH IS REAL: metrics-off lowering is deterministic and
   contains no donation scaffolding; metrics-on compiles a separate
   executable (cross-commit byte-identity of the off program is gated in
   benchmarks/serve_bench.py --check-regression).

Plus the host half: the Prometheus exposition must parse; spans are
kept only while recording and land on the profiler's host plane on the
clock of their JSONL ``t0``; the compiled loop names the program's
scopes, which leave the fingerprinted lowering untouched; the compile
counter sees a run that compiles.
"""
import contextlib
import dataclasses
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.paging import PagedLayout
from repro.launch.scheduler import (ContinuousBatchingScheduler,
                                    mixed_length_requests)
from repro.models import lm
from repro.obs import (MetricsRegistry, ObsConfig, SpanTracer,
                       scheduler_fingerprint)
from repro.obs import rings as R

P, CAP = 8, 4
STOPS = (2, 4, 3, 4)


@pytest.fixture(scope="module")
def packed_cim():
    """Packed CIM params: the serving-shaped tree, so the metrics-on path
    exercises the ADC-clip taps through the packed GEMM."""
    cfg = get_config("minicpm-2b", smoke=True)
    cfg = dataclasses.replace(cfg, cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    packed = jax.jit(lambda p: lm.pack_cim_params(p, cfg))(params)
    return packed, cfg


def _variant_kwargs(variant):
    if variant == "paged":
        return dict(paged=PagedLayout(block_size=8, n_tbl=2, n_blocks=12))
    if variant == "speculative":
        return dict(draft_k=2)
    return {}


def _requests(cfg):
    return mixed_length_requests(4, P, cfg.vocab_size, stop_lengths=STOPS)


# ---------------------------------------------------------------------------
# 1. metrics on/off token bit-identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("variant", ["contiguous", "paged", "speculative"])
def test_tokens_bit_identical_on_off(packed_cim, variant, temperature):
    params, cfg = packed_cim
    kw = dict(slots=2, prompt_len=P, max_new_cap=CAP,
              temperature=temperature, **_variant_kwargs(variant))
    reqs = _requests(cfg)
    off = ContinuousBatchingScheduler(params, cfg, **kw).run(reqs)
    on = ContinuousBatchingScheduler(params, cfg, obs=ObsConfig(),
                                     **kw).run(reqs)
    assert off.obs is None and on.obs is not None
    want = off.tokens_by_rid()
    got = on.tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"request {rid}: telemetry rings changed tokens "
                    f"({variant}, T={temperature})")
    snap = on.obs
    assert snap.counters["tokens"] == sum(len(t) for t in want.values())
    # every request has a complete admit/first/finish span on the ring
    assert sorted(s["rid"] for s in snap.spans) == sorted(want)
    for s in snap.spans:
        assert s["admit_iter"] is not None
        assert s["first_iter"] is not None and s["finish_iter"] is not None
        assert s["admit_iter"] <= s["first_iter"] <= s["finish_iter"]
    if variant == "speculative":
        # draft plan == serve plan here, so greedy acceptance is total
        assert snap.acceptance_rate == snap.acceptance_rate  # not NaN
    if variant == "paged":
        assert snap.min_free_blocks is not None


# ---------------------------------------------------------------------------
# 2. ring truth: TTFT and overflow
# ---------------------------------------------------------------------------


def test_ring_ttft_equals_instrumented_first_iter(packed_cim):
    params, cfg = packed_cim
    sched = ContinuousBatchingScheduler(params, cfg, slots=2, prompt_len=P,
                                        max_new_cap=CAP, obs=ObsConfig())
    reqs = _requests(cfg)
    rep = sched.run(reqs)
    assert rep.obs.ttft_iters == {f.rid: f.first_iter
                                  for f in rep.finished}
    ri, _ = sched.run_instrumented(reqs)
    assert rep.obs.ttft_iters == {f.rid: f.first_iter
                                  for f in ri.finished}


def test_ring_overflow_saturates_without_corrupting_tokens(packed_cim):
    params, cfg = packed_cim
    kw = dict(slots=2, prompt_len=P, max_new_cap=CAP)
    reqs = _requests(cfg)
    want = ContinuousBatchingScheduler(params, cfg, **kw).run(
        reqs).tokens_by_rid()
    # 4 requests x 3 events each = 12 event rows into a 4-row ring, and
    # an iteration ring far smaller than the workload's n_iter
    tiny = ContinuousBatchingScheduler(
        params, cfg, obs=ObsConfig(event_cap=4, iter_cap=2), **kw)
    rep = tiny.run(reqs)
    snap = rep.obs
    got = rep.tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    assert snap.dropped_events == 3 * len(reqs) - 4
    assert len(snap.events) == 4          # the recorded prefix survives
    assert snap.recorded_iters == 2
    # counters are saturating scalars, not ring rows: still exact
    assert snap.counters["tokens"] == sum(len(t) for t in want.values())
    assert json.dumps(snap.to_dict())     # harvest stays JSON-able


def test_ring_push_saturation_unit():
    obs = R.init_obs_state(ObsConfig(event_cap=3, iter_cap=2))
    for i in range(5):
        obs = R.ring_push(obs, R.EV_ADMIT, i, 10 + i)
    assert int(obs["ev_n"]) == 5          # attempts keep counting
    np.testing.assert_array_equal(np.asarray(obs["ev"])[:, 1], [0, 1, 2])
    # a gated push neither writes nor advances the cursor
    obs2 = R.ring_push(obs, R.EV_FINISH, 9, 99, do=False)
    assert int(obs2["ev_n"]) == 5
    np.testing.assert_array_equal(np.asarray(obs2["ev"]),
                                  np.asarray(obs["ev"]))


# ---------------------------------------------------------------------------
# 3. the off switch
# ---------------------------------------------------------------------------


def test_metrics_off_lowering_deterministic_and_donation_free(packed_cim):
    params, cfg = packed_cim
    mk = lambda **kw: ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=P, max_new_cap=CAP, **kw)
    fp_off = scheduler_fingerprint(mk(), 2)
    assert scheduler_fingerprint(mk(), 2) == fp_off   # deterministic
    fp_on = scheduler_fingerprint(mk(obs=ObsConfig()), 2)
    assert fp_on != fp_off                # separate executables
    text_off = mk().loop_hlo_text(2)
    text_on = mk(obs=ObsConfig()).loop_hlo_text(2)
    # off: no donation scaffolding at all; on: every ring leaf aliases
    assert "tf.aliasing_output" not in text_off
    assert text_on.count("tf.aliasing_output") >= len(R.OBS_LEAVES)
    # capacities are part of the static shape: a different ring size is
    # a different executable, never a runtime reallocation
    assert fp_on != scheduler_fingerprint(
        mk(obs=ObsConfig(event_cap=8, iter_cap=8)), 2)


# ---------------------------------------------------------------------------
# host half: exposition format
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [^ ]+$")


def test_prometheus_exposition_parses(packed_cim):
    params, cfg = packed_cim
    sched = ContinuousBatchingScheduler(params, cfg, slots=2, prompt_len=P,
                                        max_new_cap=CAP, obs=ObsConfig())
    snap = sched.run(_requests(cfg)).obs
    reg = MetricsRegistry()
    snap.register(reg)
    text = reg.export_prometheus()
    assert text.endswith("\n")
    typed = set()
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert kind in ("counter", "gauge", "histogram")
            typed.add(name)
        elif not line.startswith("#"):
            assert _SAMPLE_RE.match(line), f"unparseable sample: {line!r}"
    assert {"serve_tokens_total", "serve_ttft_seconds",
            "serve_occupancy"} <= typed
    # histogram invariants: cumulative buckets, +Inf == _count
    h = reg.histogram("serve_ttft_seconds")
    cum = np.cumsum(h.counts)
    assert (np.diff(cum) >= 0).all()
    assert cum[-1] == h.count == len(snap.ttft_iters)

    # the JSON snapshot mirrors the same samples
    js = reg.snapshot()
    assert js["serve_tokens_total"][0]["value"] == snap.counters["tokens"]
    assert js["serve_ttft_seconds"][0]["count"] == len(snap.ttft_iters)


# ---------------------------------------------------------------------------
# 7. degenerate rings: empty workloads and fully-dropped event rings
# ---------------------------------------------------------------------------


def test_harvest_empty_workload_is_nan_safe():
    """Zero iterations, zero tokens: every derived statistic must come
    back NaN (never a ZeroDivisionError or an empty-percentile crash),
    serialize as None, and publish no NaN gauges."""
    cfg = ObsConfig()
    snap = R.harvest_obs(cfg, jax.device_get(R.init_obs_state(cfg)),
                         n_iter=0, wall_s=0.0, slots=2, n_steps=0)
    assert snap.counters["tokens"] == 0 and snap.ttft_iters == {}
    p = snap.ttft_percentiles_iters()
    assert p["ttft_p50_iters"] != p["ttft_p50_iters"]      # NaN, no crash
    d = snap.to_dict()
    json.dumps(d)                                          # NaN-free JSON
    assert d["ttft_p50_iters"] is None and d["ttft_p95_s"] is None
    assert d["occupancy_mean"] is None
    reg = MetricsRegistry()
    snap.register(reg)
    text = reg.export_prometheus()
    assert "serve_occupancy" not in text                   # NaN gauge skipped
    assert "serve_stall_factor_iters" not in text
    assert reg.histogram("serve_ttft_seconds").count == 0


def test_harvest_fully_dropped_event_ring():
    """A saturated event ring that lost every first-token row: spans are
    partial, TTFT is empty, percentiles are NaN -- and the snapshot
    still serializes and registers cleanly."""
    cfg = ObsConfig(event_cap=2)
    obs = R.init_obs_state(cfg)
    for rid in range(4):                  # 4 admits into a 2-row ring
        obs = R.ring_push(obs, R.EV_ADMIT, rid, rid)
    snap = R.harvest_obs(cfg, jax.device_get(obs), n_iter=4, wall_s=0.1,
                         slots=2, n_steps=4)
    assert snap.dropped_events == 2
    assert snap.ttft_iters == {}
    assert all(s["first_iter"] is None for s in snap.spans)
    d = snap.to_dict()
    json.dumps(d)
    assert d["ttft_p95_iters"] is None
    reg = MetricsRegistry()
    snap.register(reg)
    assert "NaN" not in reg.export_prometheus()


# ---------------------------------------------------------------------------
# 8. host spans: recording, the profiler's clock
# ---------------------------------------------------------------------------


def test_spans_kept_only_while_recording(tmp_path):
    tr = SpanTracer()
    for i in range(10_000):
        with tr.span("test.off", i=i):
            pass
    assert not tr.recording and tr.spans == []
    with tr.collect() as got:                 # handed over, not kept
        with tr.span("test.collected"):
            pass
    assert [s["name"] for s in got] == ["test.collected"] and tr.spans == []
    tr.record = True
    with tr.span("test.on", n=1) as attrs:
        attrs["m"] = 2
    assert [(s["name"], s["attrs"]) for s in tr.drain()] == [
        ("test.on", {"n": 1, "m": 2})]
    path = tmp_path / "spans.jsonl"
    to_file = SpanTracer(path=str(path))
    with to_file.span("test.file"):
        pass
    assert json.loads(path.read_text())["name"] == "test.file"
    assert len(to_file.spans) == 1


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    tr = SpanTracer(path=str(tmp_path / "spans.jsonl"))
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with tr.span("test.probe"):
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    rec = json.loads((tmp_path / "spans.jsonl").read_text())
    pd = ProfileData.from_file(str(next(tmp_path.glob("prof/**/*.xplane.pb"))))
    # host event starts are relative to the session's profile_start_time
    env = {k: v for p in pd.planes if p.name == "Task Environment"
           for k, v in dict(p.stats).items()}
    hits = [e for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name == "test.probe"]
    assert len(hits) == 1
    start = (env["profile_start_time"] + hits[0].start_ns) * 1e-9
    assert abs(start - rec["t0"]) < 1e-3
    assert abs(hits[0].duration_ns * 1e-9 - rec["dur_s"]) < 1e-3


# ---------------------------------------------------------------------------
# 9. device scopes in the compiled loop; the compile counter
# ---------------------------------------------------------------------------

SCOPES = ("sched.harvest", "sched.admit", "sched.prefill", "sched.step",
          "sched.sample", "lm.step", "lm.prefill", "lm.embed", "lm.cim",
          "lm.head")


@pytest.fixture(scope="module")
def packed_ssm():
    cfg = dataclasses.replace(get_config("mamba2-130m", smoke=True),
                              cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    return jax.jit(lambda p: lm.pack_cim_params(p, cfg))(params), cfg


def _paged_sched(params, cfg):
    return ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=16, max_new_cap=CAP,
        paged=PagedLayout(block_size=8, n_tbl=3, n_blocks=12),
        prefill_chunk=8)


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_compiled_loop_names_every_scope(packed_cim, packed_ssm, family,
                                         monkeypatch):
    params, cfg = packed_cim if family == "dense" else packed_ssm
    layer = "lm.attn" if family == "dense" else "lm.ssm"
    sched = _paged_sched(params, cfg)
    text = sched.compiled_loop_text(2)
    stacks = {c for n in re.findall(r'op_name="([^"]*)"', text)
              for c in n.split("/")}
    assert set(SCOPES) | {layer} <= stacks
    lowered = sched.loop_hlo_text(2)
    assert not any(s in lowered for s in SCOPES + (layer,))
    # without the scopes: the same lowering byte for byte, and a compiled
    # loop that names none of them
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _paged_sched(params, cfg)
    assert bare.loop_hlo_text(2) == lowered
    assert not any(s in bare.compiled_loop_text(2) for s in SCOPES)


@pytest.fixture
def no_persistent_cache():
    """Every compile a backend compile: nothing loads from disk."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def test_report_counts_compiles_iterations_and_host_spans(
        packed_cim, no_persistent_cache):
    params, cfg = packed_cim
    sched = ContinuousBatchingScheduler(params, cfg, slots=2, prompt_len=P,
                                        max_new_cap=CAP)
    reqs = _requests(cfg)
    first = sched.run(reqs)
    assert first.n_compiles > 0
    again = sched.run(reqs)
    assert again.n_compiles == 0
    # every request is harvested once; the other iterations are counted
    assert again.n_iter == (again.n_steps + again.n_admits + again.n_pf
                            + len(reqs))
    assert set(again.host_s) == {"sched.run", "sched.check", "sched.stage",
                                 "sched.carry", "sched.loop", "sched.fetch"}
    assert "launch.compile" in first.host_s
    assert again.host_s["sched.loop"] <= again.host_s["sched.run"]
    assert sched.run(reqs[:3]).n_compiles > 0          # a new queue length


def test_compile_counter_does_not_count_cache_loads(tmp_path):
    """An executable served from the persistent compile cache is no
    compile; the same program built anew is one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.obs import backend_compiles, compiles
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        f = lambda x: jnp.sin(x) * 3.0 + 0.25
        x = jnp.arange(7.0)
        n0, built0 = backend_compiles(), compiles._builds
        jax.jit(f).lower(x).compile()
        assert backend_compiles() == n0 + 1
        jax.clear_caches()                    # built again: from the disk
        jax.jit(f).lower(x).compile()
        assert compiles._builds == built0 + 2
        assert backend_compiles() == n0 + 1
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
