"""Resilience stack: fault injection, drift watchdog, failover ladder.

Four property groups:

  fault model      deterministic, seeded, schedule-correct severity; the
                   fault-OFF serve loop lowers byte-identical StableHLO
                   (arming + disarming leaves no trace), while a
                   fault-ON segment lowers DIFFERENTLY (the wiring
                   proof); the digital exact path is immune.
  watchdog         debounced escalation (can jump to RED), one-level
                   recovery, and NO false positives: clean guarded
                   serving stays GREEN with zero failover actions across
                   contiguous / paged / speculative variants, tokens
                   bit-identical to the plain scheduler.
  detection        a seeded mid-stream drift ramp reaches RED within a
                   bounded token count, deterministically across
                   independently-built servers.
  ladder           every rung serves the deployed pack without repacking
                   (core.engine.pack_compatible), and the guarded run's
                   compile census proves failover never compiles.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.ccim import DEFAULT_CONFIG
from repro.core.engine import pack_compatible, packed_cim_matmul_int
from repro.launch.paging import PagedLayout
from repro.launch.scheduler import (ContinuousBatchingScheduler,
                                    mixed_length_requests)
from repro.models import lm
from repro.obs import scheduler_fingerprint
from repro.obs.fingerprint import hlo_fingerprint
from repro.plan.plan import DeploymentPlan, PlanEntry
from repro.resilience import faults as F
from repro.resilience.failover import (GuardedServer, derive_exact_plan,
                                       derive_ladder, default_probe)
from repro.resilience.watchdog import (GREEN, RED, Watchdog, WatchdogConfig,
                                       first_packed_leaf)

P, CAP = 8, 4
STOPS = (2, 4, 3, 4)

# the canonical chaos scenario shared with benchmarks/resilience_bench.py:
# per-column capacitor gain/offset drift ramping in mid-workload
DRIFT = F.FaultModel(seed=3, gain_amp=0.6, offset_lsb=2.0,
                     schedule="ramp", onset=4, period=16)


@pytest.fixture(scope="module")
def packed_cim():
    cfg = get_config("minicpm-2b", smoke=True)
    cfg = dataclasses.replace(cfg, cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    packed = jax.jit(lambda p: lm.pack_cim_params(p, cfg))(params)
    return packed, cfg


def _requests(cfg):
    return mixed_length_requests(4, P, cfg.vocab_size, stop_lengths=STOPS)


def _variant_kwargs(variant):
    if variant == "paged":
        return dict(paged=PagedLayout(block_size=8, n_tbl=2, n_blocks=12))
    if variant == "speculative":
        return dict(draft_k=2)
    return {}


# ---------------------------------------------------------------------------
# 1. the fault model itself
# ---------------------------------------------------------------------------


def test_fault_model_parse_roundtrip():
    m = F.FaultModel.parse(
        "gain_amp=0.5,schedule=ramp,onset=8,period=32,"
        "stuck_frac=0.001,stuck_mode=sign,seed=7")
    assert m.gain_amp == 0.5 and m.schedule == "ramp" and m.onset == 8
    assert m.period == 32 and m.stuck_frac == 0.001
    assert m.stuck_mode == "sign" and m.seed == 7
    with pytest.raises((ValueError, TypeError)):
        F.FaultModel.parse("no_such_knob=1")
    with pytest.raises(ValueError):
        F.FaultModel(schedule="sinusoid")


def test_severity_schedules():
    step = F.FaultModel(schedule="step", onset=4)
    ramp = F.FaultModel(schedule="ramp", onset=4, period=8)
    assert float(step.severity(3)) == 0.0 and float(step.severity(4)) == 1.0
    assert float(ramp.severity(4)) == 0.0
    assert float(ramp.severity(8)) == pytest.approx(0.5)
    assert float(ramp.severity(100)) == 1.0
    burst = F.FaultModel(schedule="burst", onset=0, period=8, duty=0.5)
    on = [float(burst.severity(t)) for t in range(8)]
    assert on == [1.0] * 4 + [0.0] * 4


def test_severity_accepts_traced_clock():
    m = F.FaultModel(schedule="ramp", onset=2, period=4)
    got = jax.jit(m.severity)(jnp.int32(4))
    assert float(got) == pytest.approx(0.5)


def test_column_patterns_seeded():
    a1, o1 = DRIFT.column_patterns(16)
    a2, o2 = DRIFT.column_patterns(16)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    b1, _ = dataclasses.replace(DRIFT, seed=DRIFT.seed + 1).column_patterns(16)
    assert not np.array_equal(np.asarray(a1), np.asarray(b1))


# ---------------------------------------------------------------------------
# 2. off-path byte-identity and epilogue wiring
# ---------------------------------------------------------------------------


def test_fault_off_lowering_byte_identical(packed_cim):
    params, cfg = packed_cim

    def make():
        return ContinuousBatchingScheduler(params, cfg, slots=2,
                                           prompt_len=P, max_new_cap=CAP)

    before = scheduler_fingerprint(make(), 2)
    seg_off = hlo_fingerprint(make().segment_hlo_text(2))
    with F.inject(DRIFT):
        assert F.active()
        seg_on = hlo_fingerprint(make().segment_hlo_text(2))
    assert not F.active()
    after = scheduler_fingerprint(make(), 2)
    assert before == after, \
        "arming a FaultModel changed the fault-free serve loop lowering"
    assert seg_on != seg_off, \
        "fault-armed segment lowered identically -- injection not wired in"


def test_epilogue_fault_deterministic_and_clocked(packed_cim):
    params, cfg = packed_cim
    leaf = first_packed_leaf(params)
    xq = jax.random.randint(jax.random.PRNGKey(1), (4, leaf.k_dim),
                            -127, 128, jnp.int32)

    def fast(t=None):
        if t is None:
            return np.asarray(packed_cim_matmul_int(
                xq, leaf, None, leaf.cfg, fidelity="fast"))
        with F.inject(DRIFT), F.clock(t):
            return np.asarray(packed_cim_matmul_int(
                xq, leaf, None, leaf.cfg, fidelity="fast"))

    clean = fast()
    np.testing.assert_array_equal(fast(t=0), clean)      # pre-onset
    hot1, hot2 = fast(t=64), fast(t=64)
    np.testing.assert_array_equal(hot1, hot2)            # deterministic
    assert not np.array_equal(hot1, clean), \
        "full-severity drift left the analog epilogue unchanged"


def test_digital_exact_path_immune(packed_cim):
    params, cfg = packed_cim
    leaf = first_packed_leaf(params)
    xq = jax.random.randint(jax.random.PRNGKey(2), (4, leaf.k_dim),
                            -127, 128, jnp.int32)
    clean = np.asarray(packed_cim_matmul_int(xq, leaf, None, leaf.cfg,
                                             fidelity="exact"))
    with F.inject(DRIFT), F.clock(64):
        hot = np.asarray(packed_cim_matmul_int(xq, leaf, None, leaf.cfg,
                                               fidelity="exact"))
    np.testing.assert_array_equal(hot, clean)


def test_stuck_weight_faults_seeded(packed_cim):
    params, cfg = packed_cim
    m = F.FaultModel(seed=11, stuck_frac=0.01, stuck_mode="mag_msb")
    f1 = F.apply_weight_faults(m, params)
    f2 = F.apply_weight_faults(m, params)
    a, b = first_packed_leaf(f1), first_packed_leaf(f2)
    np.testing.assert_array_equal(np.asarray(a.wq()), np.asarray(b.wq()))
    orig = first_packed_leaf(params)
    wq0, wq1 = np.asarray(orig.wq()), np.asarray(a.wq())
    frac = np.mean(wq0 != wq1)
    assert 0 < frac < 0.05, \
        f"stuck_frac=0.01 flipped {frac:.3f} of weights (expected ~1%)"
    # the faulted pack serves: both fidelities see the SAME corrupt cells
    xq = jax.random.randint(jax.random.PRNGKey(3), (2, a.k_dim),
                            -127, 128, jnp.int32)
    ex = np.asarray(packed_cim_matmul_int(xq, a, None, a.cfg,
                                          fidelity="exact"))
    assert not np.array_equal(
        ex, np.asarray(packed_cim_matmul_int(xq, orig, None, orig.cfg,
                                             fidelity="exact")))


# ---------------------------------------------------------------------------
# 3. the watchdog state machine
# ---------------------------------------------------------------------------


def test_watchdog_debounce_blocks_single_outlier():
    wd = Watchdog(WatchdogConfig(debounce=2, recover=2))
    ob = lambda clip: wd.observe(n_tokens=0, n_iter=0, clip_rate=clip)
    assert ob(0.0) == GREEN
    assert ob(9.9) == GREEN          # first breach: debounced
    assert ob(0.0) == GREEN          # outlier forgotten
    assert ob(9.9) == GREEN
    assert ob(9.9) == RED            # persistent: jumps straight to RED


def test_watchdog_recovery_one_level_at_a_time():
    wd = Watchdog(WatchdogConfig(debounce=1, recover=2))
    ob = lambda clip: wd.observe(n_tokens=0, n_iter=0, clip_rate=clip)
    assert ob(9.9) == RED
    assert ob(0.0) == RED
    assert ob(0.0) == "AMBER"        # two clean windows: one step down
    assert ob(0.0) == "AMBER"
    assert ob(0.0) == GREEN


def test_watchdog_probe_and_acceptance_signals():
    wd = Watchdog(WatchdogConfig(debounce=1))
    assert wd.observe(n_tokens=0, n_iter=0, probe_ratio=1.0) == GREEN
    assert wd.observe(n_tokens=0, n_iter=0, probe_ratio=50.0) == RED
    wd2 = Watchdog(WatchdogConfig(debounce=1))
    assert wd2.observe(n_tokens=0, n_iter=0, accept_rate=0.9) == GREEN
    assert wd2.observe(n_tokens=0, n_iter=0, accept_rate=0.1) == RED


def test_watchdog_clean_snapshots_green(packed_cim):
    """False-positive guard at the snapshot level: real clean serve
    telemetry, classified as one window, must stay GREEN."""
    params, cfg = packed_cim
    from repro.obs import ObsConfig
    for variant in ("contiguous", "paged", "speculative"):
        sched = ContinuousBatchingScheduler(
            params, cfg, slots=2, prompt_len=P, max_new_cap=CAP,
            obs=ObsConfig(), **_variant_kwargs(variant))
        rep = sched.run(_requests(cfg))
        wd = Watchdog()
        assert wd.observe_snapshot(rep.obs) == GREEN, \
            f"clean {variant} snapshot tripped the watchdog: " \
            f"{wd.history[-1].reasons}"


# ---------------------------------------------------------------------------
# 4. the ladder and the guarded server
# ---------------------------------------------------------------------------


def test_ladder_is_pack_compatible():
    base = PlanEntry(cfg=DEFAULT_CONFIG, fidelity="fast")
    plan = DeploymentPlan.uniform(base)
    for spec in (False, True):
        rungs, start = derive_ladder(plan, speculative=spec)
        assert 0 <= start < len(rungs)
        assert rungs[-1].label == "digital"
        for rung in rungs:
            for plans in (rung.plan, rung.draft_plan):
                if plans is None:
                    continue
                for _, e in list(plans.entries) + [(None, plans.default)]:
                    if e.fidelity == "float":
                        continue
                    assert pack_compatible(base.cfg, e.cfg), \
                        f"rung {rung.label} entry not servable from the " \
                        f"deployed pack"
    dig = derive_exact_plan(plan)
    assert dig.default.fidelity == "exact"
    assert dig.default.cfg == base.cfg


@pytest.mark.parametrize("variant", ["contiguous", "paged", "speculative"])
def test_clean_guarded_green_and_token_parity(packed_cim, variant):
    """The false-positive gate: a fault-free workload through the full
    guarded stack (watchdog + probe + ladder) stays GREEN, takes zero
    failover actions, compiles once per rung, and emits tokens
    bit-identical to the plain scheduler."""
    params, cfg = packed_cim
    kw = _variant_kwargs(variant)
    server = GuardedServer(
        params, cfg, slots=2, prompt_len=P, max_new_cap=CAP,
        watchdog=Watchdog(), probe=default_probe(params),
        segment_iters=4, **kw)
    reqs = _requests(cfg)
    report, log = server.run(reqs)
    assert server.watchdog.state == GREEN, \
        f"{variant}: clean run left GREEN: {server.watchdog.to_dict()}"
    assert log.actions == [], f"{variant}: clean run took failover actions"
    assert log.n_compiles == len(server.ladder)
    want = ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=P, max_new_cap=CAP,
        **kw).run(reqs).tokens_by_rid()
    got = report.tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"request {rid}: guarded serving changed tokens "
                    f"({variant})")


def test_drift_detection_bounded_and_deterministic(packed_cim):
    """Seeded drift reaches RED within a bounded token count, escalates
    to the digital rung without compiling, and two independently-built
    servers agree window for window."""
    params, cfg = packed_cim
    # a longer workload than the GREEN-path tests: the drift ramp needs
    # iterations to develop before the debounced machine can trip
    reqs = mixed_length_requests(4, P, cfg.vocab_size,
                                 stop_lengths=(4, 16, 8, 12))

    def chaos_run():
        server = GuardedServer(
            params, cfg, slots=2, prompt_len=P, max_new_cap=16,
            fault=DRIFT, watchdog=Watchdog(WatchdogConfig(debounce=1)),
            probe=default_probe(params, fault=DRIFT), segment_iters=4)
        _, log = server.run(reqs)
        return server, log

    s1, log1 = chaos_run()
    s2, log2 = chaos_run()
    assert s1.watchdog.state == RED
    assert log1.detection_tokens is not None
    assert log1.detection_tokens <= 32, \
        f"detection at {log1.detection_tokens} tokens blew the budget"
    assert log1.final_rung == len(s1.ladder) - 1
    assert log1.actions and log1.n_compiles == len(s1.ladder)
    assert log1.to_dict() == log2.to_dict(), \
        "chaos runs are not deterministic across server instances"


def test_guarded_start_rung_validation(packed_cim):
    params, cfg = packed_cim
    with pytest.raises(ValueError):
        GuardedServer(params, cfg, slots=2, prompt_len=P, max_new_cap=CAP,
                      start_rung=7)
    with pytest.raises(ValueError):
        GuardedServer(params, cfg, slots=2, prompt_len=P, max_new_cap=CAP,
                      segment_iters=0)
