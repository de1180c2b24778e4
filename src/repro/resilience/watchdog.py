"""Online analog-health watchdog: a debounced GREEN/AMBER/RED machine.

The serving stack already measures the signals that degrade first when
the analog substrate drifts -- it just never *acted* on them:

  ADC clip rate      obs ring counter ``CTR_ADC_CLIP`` (taps in the
                     packed GEMM's conversion epilogue).  Gain/offset
                     drift pushes accumulates past the SAR range, so the
                     clip-per-token rate rises well before logits are
                     visibly wrong.
  acceptance rate    speculative serving's drafted-vs-accepted counters.
                     The draft plan is all-analog, so capacitor drift
                     hits the draft hardest and acceptance collapses --
                     a free, output-level drift detector (fidelity never
                     degrades; the verify pass still gates every token).
  golden probe       a seeded known-input GEMM through the REAL packed
                     weights, compared against the digital reference
                     recorded at deployment (``GoldenProbe``).  Catches
                     what rate signals cannot: slow offset drift that
                     never clips, and stuck-at cells corrupting the
                     stored weights themselves.

``Watchdog.observe`` folds one measurement window into the state
machine.  Both directions are debounced: a breach must persist for
``debounce`` consecutive windows to escalate (one clipped outlier window
is not a failing die), and recovery needs ``recover`` consecutive clean
windows to step back down one level (burst faults flap; the ladder must
not).  Escalation can jump straight to RED; recovery is always one
level at a time.

The watchdog runs ON THE HOST at segment boundaries of the guarded
serve loop (failover.GuardedServer): it reads counters the device
already maintains, so the compiled loop body gains no host callbacks --
the RES-HOST-SYNC lint walks the jaxpr to prove it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

GREEN, AMBER, RED = "GREEN", "AMBER", "RED"
_LEVEL = {GREEN: 0, AMBER: 1, RED: 2}
_STATE = {v: k for k, v in _LEVEL.items()}


@dataclasses.dataclass(frozen=True)
class WatchdogConfig:
    """Thresholds and debounce for the health state machine.

    Clip thresholds are per-token rates ABOVE the clean baseline
    (``Watchdog(baseline_clip_rate=...)``): a plan tuned near the SAR
    range clips a little when healthy, and that floor must not count as
    drift.  Probe thresholds are ratios of the probe's rel-RMS over the
    clean quantization floor measured at deployment -- the fast path is
    never bit-equal to the digital reference (ACIM residual rounding),
    so the floor, not zero, is the healthy reference.
    """

    clip_rate_amber: float = 0.05     # excess ADC clips per emitted token
    clip_rate_red: float = 0.50
    accept_amber: float = 0.50        # speculative acceptance below these
    accept_red: float = 0.20
    probe_amber: float = 3.0          # probe rel-RMS / clean floor above
    probe_red: float = 10.0
    debounce: int = 2                 # consecutive breaches to escalate
    recover: int = 4                  # consecutive clean windows per step-down
    probe_every: int = 1              # run the golden probe every N windows

    def __post_init__(self):
        if self.debounce < 1 or self.recover < 1 or self.probe_every < 1:
            raise ValueError("debounce/recover/probe_every must be >= 1")


@dataclasses.dataclass
class HealthSample:
    """One observation window, with the raw per-signal classification."""
    n_tokens: int                     # cumulative tokens at window end
    n_iter: int                       # cumulative loop iterations
    clip_rate: Optional[float]        # excess clips per token this window
    accept_rate: Optional[float]      # acceptance this window (spec only)
    probe_ratio: Optional[float]      # probe rms / clean floor
    raw: str                          # worst un-debounced level
    state: str                        # machine state AFTER this window
    reasons: List[str]

    def to_dict(self) -> Dict:
        rnd = lambda v: None if v is None else round(float(v), 5)
        return dict(n_tokens=self.n_tokens, n_iter=self.n_iter,
                    clip_rate=rnd(self.clip_rate),
                    accept_rate=rnd(self.accept_rate),
                    probe_ratio=rnd(self.probe_ratio),
                    raw=self.raw, state=self.state, reasons=self.reasons)


class Watchdog:
    """Debounced health-state machine over windowed serve telemetry."""

    def __init__(self, cfg: WatchdogConfig = WatchdogConfig(),
                 baseline_clip_rate: float = 0.0):
        self.cfg = cfg
        self.baseline_clip_rate = float(baseline_clip_rate)
        self.state = GREEN
        self.history: List[HealthSample] = []
        self._hot = 0                 # consecutive windows above state
        self._cool = 0                # consecutive windows below state
        self._pending = 0             # level the hot streak argues for

    # -- classification -------------------------------------------------

    def _classify(self, clip_rate, accept_rate, probe_ratio):
        c = self.cfg
        level, reasons = 0, []

        def breach(val, amber, red, name, below=False):
            nonlocal level
            if val is None or val != val:
                return
            hit = 0
            if below:
                hit = 2 if val < red else (1 if val < amber else 0)
            else:
                hit = 2 if val > red else (1 if val > amber else 0)
            if hit:
                reasons.append(f"{name}={val:.4g} ({_STATE[hit]})")
                level = max(level, hit)

        breach(clip_rate, c.clip_rate_amber, c.clip_rate_red, "clip_rate")
        breach(accept_rate, c.accept_amber, c.accept_red, "accept_rate",
               below=True)
        breach(probe_ratio, c.probe_amber, c.probe_red, "probe_ratio")
        return level, reasons

    # -- the state machine ----------------------------------------------

    def observe(self, *, n_tokens: int, n_iter: int,
                clip_rate: Optional[float] = None,
                accept_rate: Optional[float] = None,
                probe_ratio: Optional[float] = None) -> str:
        """Fold one measurement window in; returns the (possibly new)
        debounced state.  ``clip_rate`` should already be per-token for
        the window; the clean baseline is subtracted here."""
        if clip_rate is not None and clip_rate == clip_rate:
            clip_rate = max(0.0, clip_rate - self.baseline_clip_rate)
        raw, reasons = self._classify(clip_rate, accept_rate, probe_ratio)
        cur = _LEVEL[self.state]
        if raw > cur:
            # escalation streak: must argue for at least the same level
            # each window (a RED window refreshes an AMBER streak's count
            # -- it is still "above current state")
            self._pending = max(self._pending, raw) if self._hot else raw
            self._hot += 1
            self._cool = 0
            if self._hot >= self.cfg.debounce:
                self.state = _STATE[self._pending]
                self._hot = self._pending = 0
        elif raw < cur:
            self._cool += 1
            self._hot = self._pending = 0
            if self._cool >= self.cfg.recover:
                self.state = _STATE[cur - 1]   # one level at a time
                self._cool = 0
        else:
            self._hot = self._cool = self._pending = 0
        self.history.append(HealthSample(
            n_tokens=n_tokens, n_iter=n_iter, clip_rate=clip_rate,
            accept_rate=accept_rate, probe_ratio=probe_ratio,
            raw=_STATE[raw], state=self.state, reasons=reasons))
        return self.state

    def observe_snapshot(self, snap, probe_ratio: Optional[float] = None
                         ) -> str:
        """Offline variant: classify one whole-workload ``ObsSnapshot``
        (obs/rings.py) as a single window -- the false-positive tests
        drive clean serve reports through exactly this path."""
        tokens = snap.counters.get("tokens", 0)
        clip = snap.counters.get("adc_clip", 0)
        clip_rate = clip / tokens if tokens else None
        acc = snap.acceptance_rate
        return self.observe(
            n_tokens=tokens, n_iter=snap.n_iter, clip_rate=clip_rate,
            accept_rate=None if acc != acc else acc,
            probe_ratio=probe_ratio)

    # -- reporting ------------------------------------------------------

    @property
    def detection(self) -> Optional[HealthSample]:
        """First window the debounced state left GREEN (None if never)."""
        return next((s for s in self.history if s.state != GREEN), None)

    def to_dict(self) -> Dict:
        return dict(state=self.state,
                    baseline_clip_rate=round(self.baseline_clip_rate, 6),
                    windows=[s.to_dict() for s in self.history])


class GoldenProbe:
    """Known-input probe GEMM against the deployment-time digital
    reference.

    Built ONCE at deployment from one real packed projection: a seeded
    activation batch, the exact-fidelity reference output, and the clean
    fast-path rel-RMS floor (nonzero -- ACIM residual rounding).  Each
    call runs the fast path as currently served -- under whatever fault
    model ``fault`` emulates at clock ``t`` -- and returns the rel-RMS
    ratio over the clean floor, the unit ``WatchdogConfig.probe_*``
    thresholds are written in.

    The probe executable is jitted once with ``t`` as a TRACED argument
    (the fault context is armed around the trace), so repeated probes at
    different clocks never retrace.  ``serve_packed`` lets the harness
    probe a stuck-at-faulted pack against the clean pack's reference --
    the deployment-time recording is exactly what makes silent weight
    corruption visible.
    """

    def __init__(self, packed, *, fault=None, serve_packed=None,
                 m: int = 4, seed: int = 0):
        import jax
        import jax.numpy as jnp
        from ..core.engine import packed_cim_matmul_int
        from ..plan.profiler import rel_rms
        from . import faults as rfaults

        self.packed = packed
        serve = serve_packed if serve_packed is not None else packed
        cfg = packed.cfg
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 0x50524F42)  # "PROB"
        self.xq = jax.random.randint(key, (m, packed.k_dim), -127, 128,
                                     jnp.int32)
        self.ref = np.asarray(
            packed_cim_matmul_int(self.xq, packed, None, cfg,
                                  fidelity="exact"), np.float64)
        self._rel_rms = rel_rms

        def fwd(t):
            if fault is not None:
                with rfaults.inject(fault):
                    with rfaults.clock(t):
                        return packed_cim_matmul_int(self.xq, serve, None,
                                                     cfg, fidelity="fast")
            return packed_cim_matmul_int(self.xq, serve, None, cfg,
                                         fidelity="fast")

        self._fwd = jax.jit(fwd)
        clean = np.asarray(
            packed_cim_matmul_int(self.xq, packed, None, cfg,
                                  fidelity="fast"), np.float64)
        self.clean_floor = max(float(rel_rms(clean, self.ref)), 1e-9)

    def __call__(self, t: int = 0) -> float:
        """rel-RMS of the served fast path at clock ``t`` over the clean
        floor (1.0 == healthy)."""
        import jax.numpy as jnp
        y = np.asarray(self._fwd(jnp.int32(t)), np.float64)
        return float(self._rel_rms(y, self.ref)) / self.clean_floor


def first_packed_leaf(params):
    """The first PackedCimWeights leaf of a params tree (probe target);
    None when the tree holds no packed weights."""
    import jax
    from ..core.engine import FusedPackedCimWeights, PackedCimWeights

    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(
            x, (PackedCimWeights, FusedPackedCimWeights)))
    for leaf in leaves:
        if isinstance(leaf, FusedPackedCimWeights):
            leaf = leaf.packed
        if isinstance(leaf, PackedCimWeights):
            # scanned layer stacks pack with a leading depth axis; the
            # probe wants one physical array -- layer 0's
            if leaf.pallas_w.ndim == 3:
                leaf = jax.tree_util.tree_map(lambda a: a[0], leaf)
            return leaf
    return None
