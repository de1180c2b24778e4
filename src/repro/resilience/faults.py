"""Deterministic analog-substrate fault injection (the chaos half).

The paper's 0.435% RMS is a nominal-conditions number: a deployed
charge-domain macro degrades under capacitor mismatch drift, ADC
offset/gain drift and SRAM bit-cell faults.  This module emulates those
degradations DETERMINISTICALLY (seeded, schedulable) so the watchdog and
failover ladder can be tested in closed loop, exactly the way the obs
rings made telemetry testable.

Two injection surfaces, matching where faults live in silicon:

  weights   ``apply_weight_faults`` -- stuck-at sign/magnitude bit-cells.
            A pure host-side transform of the packed params tree: the
            faulted integer weights are RE-packed through the normal
            pack pipeline, so every serving path (fast GEMM, Pallas,
            exact ``wq()`` reconstruction) sees the SAME faulted cells,
            as they would in silicon.  No trace-time flag involved.
  epilogue  per-column capacitor gain/offset drift, ADC conversion
            offset and clip escalation, applied inside the analog
            conversion epilogue of ``core.ccim.hybrid_mac_fast_gemm_
            prepacked``.  These exist ONLY while an ``inject()`` context
            is open *at trace time* -- the same static-flag mechanism as
            ``obs.taps``: with no context open, not one extra op is
            traced and fault-free serving lowers byte-identical
            StableHLO (fingerprint-gated in benchmarks/resilience_bench
            and the RES-OFF-PATH cimlint rule).

Time. Drift is scheduled against an iteration clock ``t``: a concrete
int for one-shot measurements, or a TRACED scalar (the scheduler loop's
``n_iter``, bound via ``clock()``) so severity evolves mid-stream inside
ONE compiled executable -- mid-workload drift needs no retrace, no
recompile, preserving the serving stack's static-executable contract.

Every draw is keyed from ``FaultModel.seed`` alone (plus static shapes/
paths), never from global state: the same model produces the same fault
pattern in eager, jit, scan and across processes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import zlib
from typing import Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

Array = jax.Array

FAULT_SCHEDULES = ("step", "ramp", "burst")
STUCK_MODES = ("mag_msb", "sign")


@dataclasses.dataclass(frozen=True)
class FaultModel:
    """One seeded, schedulable fault scenario (hashable, static).

    Severity ``s(t)`` in [0, 1] follows ``schedule`` from ``onset``;
    every analog amplitude below scales linearly with it.  Stuck-at
    cell faults are time-invariant (a failed cell stays failed).
    """

    seed: int = 0
    # -- SRAM bit-cell faults (weights; applied via apply_weight_faults)
    stuck_frac: float = 0.0        # fraction of cells faulted
    stuck_mode: str = "mag_msb"    # "mag_msb": magnitude MSB stuck at 1;
                                   # "sign": sign bit flipped
    # -- capacitor-array drift (per output column, analog epilogue)
    gain_amp: float = 0.0          # relative per-column gain error amplitude
    offset_lsb: float = 0.0        # per-column conversion offset, ADC LSBs
    # -- ADC drift (analog epilogue)
    adc_offset_lsb: float = 0.0    # global conversion offset, ADC LSBs
    adc_clip_bits: float = 0.0     # clip escalation: effective SAR range
                                   # shrinks by up to this many bits
    # -- schedule
    schedule: str = "step"         # step | ramp | burst
    onset: int = 0                 # iteration the fault switches on
    period: int = 64               # ramp rise length / burst period, iters
    duty: float = 0.5              # burst: on-fraction of each period

    def __post_init__(self):
        if self.schedule not in FAULT_SCHEDULES:
            raise ValueError(f"schedule {self.schedule!r} not in "
                             f"{FAULT_SCHEDULES}")
        if self.stuck_mode not in STUCK_MODES:
            raise ValueError(f"stuck_mode {self.stuck_mode!r} not in "
                             f"{STUCK_MODES}")
        if not (0.0 <= self.stuck_frac <= 1.0):
            raise ValueError(f"stuck_frac {self.stuck_frac} outside [0, 1]")
        if self.period < 1:
            raise ValueError(f"period {self.period} < 1")

    @property
    def touches_epilogue(self) -> bool:
        """True when the model perturbs the analog conversion epilogue
        (zero-amplitude models trace no extra conversion ops)."""
        return any(v != 0.0 for v in (self.gain_amp, self.offset_lsb,
                                      self.adc_offset_lsb,
                                      self.adc_clip_bits))

    def severity(self, t) -> Array:
        """Schedule value s(t) in [0, 1]; ``t`` concrete or traced."""
        tf = jnp.asarray(t, jnp.float32)
        on = jnp.float32(self.onset)
        if self.schedule == "step":
            return (tf >= on).astype(jnp.float32)
        if self.schedule == "ramp":
            return jnp.clip((tf - on) / jnp.float32(self.period), 0.0, 1.0)
        # burst: full severity for the first duty*period of each period
        phase = jnp.mod(tf - on, jnp.float32(self.period))
        live = (tf >= on) & (phase < self.duty * self.period)
        return live.astype(jnp.float32)

    def column_patterns(self, n: int) -> Tuple[Array, Array]:
        """Deterministic per-column (gain, offset) unit patterns, shape
        (n,) each in [-1, 1] -- the frozen mismatch signature of one
        capacitor array.  Depends only on (seed, n)."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 0x44524946)  # "DRIF"
        kg, ko = jax.random.split(key)
        gain = jax.random.uniform(kg, (n,), jnp.float32, -1.0, 1.0)
        off = jax.random.uniform(ko, (n,), jnp.float32, -1.0, 1.0)
        return gain, off

    @classmethod
    def parse(cls, spec: str) -> "FaultModel":
        """Build from a CLI spec: comma-separated ``key=value`` pairs,
        e.g. ``schedule=ramp,gain_amp=0.3,onset=32,period=64,seed=7``.
        Unknown keys error with the known field list."""
        fields = {f.name: f.type for f in dataclasses.fields(cls)}
        kw = {}
        for part in filter(None, (p.strip() for p in spec.split(","))):
            if "=" not in part:
                raise ValueError(f"fault spec item {part!r} is not "
                                 "key=value")
            k, v = part.split("=", 1)
            if k not in fields:
                raise ValueError(f"unknown fault field {k!r}; known: "
                                 f"{sorted(fields)}")
            kw[k] = v if k in ("schedule", "stuck_mode") else (
                int(v) if k in ("seed", "onset", "period") else float(v))
        return cls(**kw)


@dataclasses.dataclass
class _Site:
    """One open injection frame: the model plus its current clock.  The
    clock may be rebound to a traced scalar (``clock()``) while tracing
    a loop body."""
    model: FaultModel
    t: Union[int, Array]


# stack of open injection frames (innermost last); trace-time only,
# exactly like obs.taps._STACK
_STACK: List[_Site] = []


def active() -> bool:
    """True while some ``inject()`` frame is open whose model perturbs
    the conversion epilogue (trace-time check; plain Python bool)."""
    return bool(_STACK) and _STACK[-1].model.touches_epilogue


def site() -> Optional[_Site]:
    """The innermost open injection frame (None when inactive)."""
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def inject(model: FaultModel, t: Union[int, Array] = 0) -> Iterator[_Site]:
    """Arm ``model`` for everything traced inside this context.

    ``t`` seeds the clock; kernels traced while the context is open bake
    fault ops whose severity is ``model.severity(t)``.  Pass a traced
    scalar (or rebind later with ``clock``) for in-executable schedules.
    """
    s = _Site(model, t)
    _STACK.append(s)
    try:
        yield s
    finally:
        _STACK.pop()


@contextlib.contextmanager
def clock(t: Union[int, Array]) -> Iterator[None]:
    """Rebind the innermost frame's clock for the enclosed trace region.

    The scheduler wraps its loop body with ``clock(carry['n_iter'])``
    when lowering the guarded (segmented) serve loop, so drift severity
    follows the DEVICE iteration counter -- one executable covers the
    whole schedule.  A no-op (no ops traced, no state touched) when no
    injection frame is open, so the fault-free lowering is untouched.
    """
    if not _STACK:
        yield
        return
    frame = _STACK[-1]
    old = frame.t
    frame.t = t
    try:
        yield
    finally:
        frame.t = old


def epilogue_terms(n_cols: int):
    """The fault terms the analog conversion epilogue folds in; called
    by ``core.ccim`` ONLY under ``active()``.

    Returns ``(gain, offset_lsb, adc_off_lsb, range_scale)``:

      gain         (n_cols,) multiplicative error on the analog partial
      offset_lsb   (n_cols,) additive conversion offset, in ADC LSBs
      adc_off_lsb  scalar global ADC offset, in ADC LSBs
      range_scale  scalar in (0, 1]: effective SAR range multiplier
                   (2**-(sev*adc_clip_bits) -- clip escalation)

    All four are severity-scaled by the frame's clock, so inside a loop
    trace they evolve with the device iteration counter.
    """
    frame = _STACK[-1]
    m = frame.model
    sev = m.severity(frame.t)
    gcol, ocol = m.column_patterns(n_cols)
    gain = 1.0 + sev * m.gain_amp * gcol
    off = sev * m.offset_lsb * ocol
    adc_off = sev * m.adc_offset_lsb
    range_scale = jnp.exp2(-sev * m.adc_clip_bits)
    return gain, off, adc_off, range_scale


# ---------------------------------------------------------------------------
# Weight-side stuck-at faults (host transform; no trace-time flag)
# ---------------------------------------------------------------------------


def _leaf_key(model: FaultModel, path_tag: int) -> Array:
    k = jax.random.fold_in(jax.random.PRNGKey(model.seed),
                           0x53545543)  # "STUC"
    return jax.random.fold_in(k, path_tag)


def stuck_mask(model: FaultModel, shape: Tuple[int, ...],
               path_tag: int) -> Array:
    """Deterministic boolean fault map for one (K, N) cell array."""
    return jax.random.bernoulli(_leaf_key(model, path_tag),
                                model.stuck_frac, shape)


def faulted_wq(model: FaultModel, sign: Array, mag: Array,
               path_tag: int, n_mag_bits: int = 7) -> Array:
    """Apply stuck-at cell faults to raw signed-magnitude storage and
    return the faulted integer weights."""
    mask = stuck_mask(model, sign.shape, path_tag)
    sign = sign.astype(jnp.int32)
    mag = mag.astype(jnp.int32)
    if model.stuck_mode == "mag_msb":
        msb = 1 << (n_mag_bits - 1)
        mag = jnp.where(mask, mag | msb, mag)
    else:                                  # "sign": cell flips polarity
        sign = jnp.where(mask, -sign, sign)
    return sign * mag


def apply_weight_faults(model: FaultModel, params):
    """Pure transform of a (packed) params tree: every PackedCimWeights
    leaf gets ``stuck_frac`` of its bit-cells faulted, deterministically
    keyed by (seed, leaf path), and is RE-packed from the faulted ints --
    the bit cells are ``split_sign_mag(leaf.wq())``, and every execution
    route derives its operands from the one re-packed layout, exactly like
    silicon where every path reads the same cells.  Non-packed leaves pass
    through untouched (stuck-at faults are a property of the CIM array).
    """
    # function-level import: core.ccim imports this module at load time
    from ..core.ccim import split_sign_mag
    from ..core.engine import (FusedPackedCimWeights, PackedCimWeights,
                               pack_quantized_cim_weights)

    if model.stuck_frac <= 0.0:
        return params

    def tag(path) -> int:
        return zlib.crc32("/".join(str(p) for p in path).encode())

    def fix(path, leaf):
        if isinstance(leaf, FusedPackedCimWeights):
            return dataclasses.replace(leaf, packed=fix(path, leaf.packed))
        if isinstance(leaf, PackedCimWeights):
            wq = faulted_wq(model, *split_sign_mag(leaf.wq()), tag(path),
                            n_mag_bits=leaf.cfg.n_mag_bits)
            repack = lambda w, s: pack_quantized_cim_weights(
                w, s, leaf.cfg)
            if wq.ndim == 3:      # scanned layer stack: (layers, K, N),
                repack = jax.vmap(repack)   # packed like models.lm does
            return repack(wq, leaf.scale)
        return leaf

    return jax.tree_util.tree_map_with_path(
        fix, params,
        is_leaf=lambda x: isinstance(x, (PackedCimWeights,
                                         FusedPackedCimWeights)))
