"""Bit-true behavioural model of the hybrid digital/analog complex-CIM macro.

Implements the arithmetic of the 28nm C-CIM prototype:

  * 8-bit signed-magnitude (SMF) operands:  v = (-1)^s * m,  m in [0,127].
  * Per ``acc_len``-element accumulate (one ADC conversion):
      - DCIM: the top-3 bit-products (6,6),(6,5),(5,6) -- 50.8% of the total
        contribution -- computed exactly with counting logic, range [-64,+64].
      - ACIM: the remaining 46 bit-products summed in charge domain on a 2-D
        binary-weighted capacitor array (unit cap 48 aF, 2.96% rms mismatch),
        digitised by a 7-bit SAR ADC (CDAC LSB = 16 C).
      - Post-digital adder: y8 = DCIM + ADC code, representing sum(I*W)/2^11.
  * Complex MAC: four real sub-MACs sharing one co-located (Re,Im) weight
    array; Re/Im outputs produced in parallel (see complex_mac.py).

Everything is jax.jit compatible.  Analog non-idealities are explicit
functions of a "fabricated" macro instance (frozen mismatch draws), so the
same die gives the same static error pattern -- as in silicon.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import taps
from ..resilience import faults as rfaults

Array = jax.Array

# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CCIMConfig:
    """Static configuration of the macro (defaults = the 28nm prototype)."""

    n_mag_bits: int = 7                 # SMF magnitude bits (MSB of 8b is sign)
    acc_len: int = 16                   # elements summed per ADC conversion
    n_dcim_products: int = 3            # top-k bit-products routed to DCIM
    adc_bits: int = 7                   # SAR ADC resolution
    sigma_unit: float = 0.0296          # 48aF M7-M7 fringe cap mismatch (rms)
    adc_lsb_units: int = 16             # CDAC LSB built from 16 unit caps
    # 'per_unit': independent eps per (row, j, k) cell  (16 local 2D arrays)
    # 'per_macro': one shared (j, k) eps  (fully correlated across rows)
    mismatch_granularity: str = "per_unit"
    # 'conservative': DNL = sigma_u * sqrt(2^N - 1)   (paper: 0.33 LSB rms)
    # 'averaged':     per-bit sigma improves as 1/sqrt(#unit caps)
    adc_mismatch_model: str = "conservative"
    # dynamic noise (comparator input-referred + supply), in ADC LSB rms;
    # 0.45 calibrates the model to the measured 0.435% rms C-MAC error
    # (mismatch + rounding alone give 0.29%). Applied only when a noise_key
    # is provided, so deterministic paths stay deterministic.
    comparator_noise_lsb: float = 0.45
    # VREF+/- polarity-path gain mismatch (the VREFCLK direction flip,
    # Fig. 3) -- puts the max INL step at the zero crossing as measured.
    sigma_vref_pol: float = 0.002
    use_split_dac: bool = True          # split-DAC halves the cap count (area)

    # ---- derived ----------------------------------------------------------
    @property
    def max_mag(self) -> int:
        return (1 << self.n_mag_bits) - 1  # 127

    @property
    def dcim_products(self) -> Tuple[Tuple[int, int], ...]:
        """The top-k (j, k) bit-product cells ordered by significance."""
        cells = [(j, k) for j in range(self.n_mag_bits) for k in range(self.n_mag_bits)]
        cells.sort(key=lambda jk: (-(jk[0] + jk[1]), -jk[0]))
        return tuple(cells[: self.n_dcim_products])

    @property
    def dcim_lsb(self) -> int:
        """Significance of the least weighted DCIM product (=2^11 for top-3).

        With no DCIM products (all-analog baseline) the ADC LSB stays at
        2^11 and the ADC must be wider instead (see baselines.py)."""
        if not self.dcim_products:
            return 1 << (2 * self.n_mag_bits - 3)
        return 1 << min(j + k for j, k in self.dcim_products)

    @property
    def adc_half_range(self) -> int:
        return 1 << (self.adc_bits - 1)  # 64 for 7b

    @property
    def fast_noise_correction(self) -> float:
        """Variance correction for the fast path under split-DAC.

        The fast path's matched variance assumes sigma_jk = sigma_u /
        sqrt(2^(j+k)); the split-DAC floors the effective unit count at
        2^ceil(s/2) (see fabricate).  For uniform bit statistics the
        aggregate variance scales by sum(2^2s/eff) / sum(2^s) over the
        ACIM cells -- a config-level scalar."""
        if not self.use_split_dac:
            return 1.0
        num = den = 0.0
        for j in range(self.n_mag_bits):
            for k in range(self.n_mag_bits):
                if (j, k) in self.dcim_products:
                    continue
                s = j + k
                eff = min(2.0 ** s, 2.0 ** math.ceil(s / 2))
                num += (2.0 ** (2 * s)) / eff
                den += 2.0 ** s
        return num / den

    def dcim_weight_table(self) -> np.ndarray:
        """(7,7) integer table: 2^(j+k)/dcim_lsb on DCIM cells, 0 elsewhere."""
        t = np.zeros((self.n_mag_bits, self.n_mag_bits), np.int32)
        for j, k in self.dcim_products:
            t[j, k] = (1 << (j + k)) // self.dcim_lsb
        return t

    def acim_weight_table(self) -> np.ndarray:
        """(7,7) integer table: 2^(j+k) on ACIM cells, 0 on DCIM cells."""
        t = np.zeros((self.n_mag_bits, self.n_mag_bits), np.int64)
        for j in range(self.n_mag_bits):
            for k in range(self.n_mag_bits):
                t[j, k] = 1 << (j + k)
        for j, k in self.dcim_products:
            t[j, k] = 0
        return t

    @property
    def dcim_max(self) -> int:
        """Max |DCIM| for a full accumulate: 16 * (2+1+1) = 64 (paper)."""
        per_elem = sum((1 << (j + k)) // self.dcim_lsb for j, k in self.dcim_products)
        return self.acc_len * per_elem


DEFAULT_CONFIG = CCIMConfig()


# ---------------------------------------------------------------------------
# Fabrication: draw the static analog error pattern of one die
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MacroInstance:
    """Frozen mismatch draws for one fabricated macro.

    eps_array : relative cap error of each 2-D array cell.
        shape (acc_len, 7, 7) for 'per_unit', (7, 7) for 'per_macro'.
        Cell (j,k) holds 2^(j+k) unit caps => sigma = sigma_u / sqrt(2^(j+k)).
    adc_cap_eps : relative error of each binary CDAC capacitor, shape (adc_bits,).
    """

    eps_array: Array
    adc_cap_eps: Array
    vref_pol_eps: Array  # scalar: +/- reference path gain asymmetry


def fabricate(key: Array, cfg: CCIMConfig = DEFAULT_CONFIG) -> MacroInstance:
    """Monte-Carlo 'tape-out': draw the static mismatch of one macro."""
    k1, k2, k3 = jax.random.split(key, 3)
    nb = cfg.n_mag_bits
    jk = jnp.arange(nb)
    # sigma of a cap built from 2^(j+k) unit caps scales as 1/sqrt(count)
    sig2d = cfg.sigma_unit / jnp.sqrt(
        (2.0 ** jk)[:, None] * (2.0 ** jk)[None, :]
    )  # (7,7)
    if cfg.use_split_dac:
        # Split-DAC: LSB section realised behind an attenuation cap, so the
        # *effective* unit count of low-significance cells stops growing --
        # their relative mismatch floors at sigma_unit (they are 1-2 physical
        # caps each).  Model: sigma = sigma_u / sqrt(min(2^(j+k), 2^ceil((j+k)/2)))
        eff = jnp.minimum(
            (2.0 ** jk)[:, None] * (2.0 ** jk)[None, :],
            2.0 ** jnp.ceil((jk[:, None] + jk[None, :]) / 2.0),
        )
        sig2d = cfg.sigma_unit / jnp.sqrt(eff)
    shape = (cfg.acc_len, nb, nb) if cfg.mismatch_granularity == "per_unit" else (nb, nb)
    eps_array = jax.random.normal(k1, shape) * sig2d  # broadcast over rows

    if cfg.adc_mismatch_model == "conservative":
        # paper's sizing rule: DNL = sigma_u*sqrt(2^N-1) = 0.33 LSB rms
        sig_bit = cfg.sigma_unit / jnp.sqrt(2.0 ** jnp.arange(cfg.adc_bits))
    else:
        n_units = cfg.adc_lsb_units * (2.0 ** jnp.arange(cfg.adc_bits))
        sig_bit = cfg.sigma_unit / jnp.sqrt(n_units)
    adc_cap_eps = jax.random.normal(k2, (cfg.adc_bits,)) * sig_bit
    vref_pol_eps = jax.random.normal(k3, ()) * cfg.sigma_vref_pol
    return MacroInstance(eps_array=eps_array, adc_cap_eps=adc_cap_eps,
                         vref_pol_eps=vref_pol_eps)


def ideal_macro(cfg: CCIMConfig = DEFAULT_CONFIG) -> MacroInstance:
    shape = (
        (cfg.acc_len, cfg.n_mag_bits, cfg.n_mag_bits)
        if cfg.mismatch_granularity == "per_unit"
        else (cfg.n_mag_bits, cfg.n_mag_bits)
    )
    return MacroInstance(
        eps_array=jnp.zeros(shape), adc_cap_eps=jnp.zeros((cfg.adc_bits,)),
        vref_pol_eps=jnp.zeros(()),
    )


# ---------------------------------------------------------------------------
# SMF quantization
# ---------------------------------------------------------------------------


def quantize_smf(x: Array, scale: Array, cfg: CCIMConfig = DEFAULT_CONFIG) -> Array:
    """float -> integer in [-127, 127] (signed-magnitude has no -128)."""
    q = jnp.round(x / scale)
    return jnp.clip(q, -cfg.max_mag, cfg.max_mag).astype(jnp.int32)


def smf_scale(x: Array, axis=None, keepdims: bool = False,
              cfg: CCIMConfig = DEFAULT_CONFIG) -> Array:
    """Symmetric max-abs scale so that max |q| = 127.

    The fold is written as a multiply by the precomputed reciprocal
    rather than ``amax / max_mag``: XLA's jit simplifier rewrites
    divide-by-constant into exactly this multiply, so the explicit form
    makes the scale BIT-IDENTICAL between eager and jit execution (it
    used to differ by one ulp, which could flip a rounded magnitude --
    the old PR-3 eager-vs-jit packing caveat).
    """
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims)
    inv = np.float32(1.0) / np.float32(cfg.max_mag)
    return jnp.maximum(amax, 1e-12) * inv


def split_sign_mag(q: Array) -> Tuple[Array, Array]:
    """SMF decomposition: sign in {-1,+1}, magnitude in [0,127]."""
    return jnp.where(q < 0, -1, 1).astype(jnp.int32), jnp.abs(q).astype(jnp.int32)


def bit_planes(mag: Array, n_bits: int) -> Array:
    """(...,) int magnitudes -> (..., n_bits) {0,1} planes, LSB first."""
    shifts = jnp.arange(n_bits, dtype=jnp.int32)
    return ((mag[..., None] >> shifts) & 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# 7-bit SAR ADC with CDAC mismatch (bipolar, samples mid-scale 0x40)
# ---------------------------------------------------------------------------


def sar_adc(
    v_lsb: Array,
    adc_cap_eps: Array,
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_key: Optional[Array] = None,
) -> Array:
    """Convert ``v_lsb`` (analog value in ideal-LSB units, signed) to a code.

    Successive approximation against *real* (mismatched) CDAC weights; the
    returned code is the ideal-binary interpretation of the decided bits --
    exactly how CDAC mismatch becomes DNL/INL in silicon.
    """
    half = cfg.adc_half_range
    x = jnp.clip(v_lsb, -half, half - 1) + half + 0.5  # unipolar, mid-tread
    real_w = (2.0 ** jnp.arange(cfg.adc_bits)) * (1.0 + adc_cap_eps)
    acc = jnp.zeros_like(x)
    code = jnp.zeros_like(x, dtype=jnp.int32)
    keys = (
        jax.random.split(noise_key, cfg.adc_bits) if noise_key is not None else None
    )
    for b in range(cfg.adc_bits - 1, -1, -1):
        trial = acc + real_w[b]
        cmp_in = x
        if keys is not None and cfg.comparator_noise_lsb > 0:
            cmp_in = x + cfg.comparator_noise_lsb * jax.random.normal(keys[b], x.shape)
        bit = (cmp_in >= trial).astype(jnp.int32)
        acc = acc + bit * real_w[b]
        code = code + bit * (1 << b)
    return code - half  # back to signed, in [-64, +63]


# ---------------------------------------------------------------------------
# Hybrid MAC -- bit-true path (the oracle; exact silicon arithmetic)
# ---------------------------------------------------------------------------


def _signed_bits(q: Array, cfg: CCIMConfig) -> Array:
    """(..., L) ints -> (..., L, n_bits) sign-carrying bit planes."""
    s, m = split_sign_mag(q)
    return s[..., None] * bit_planes(m, cfg.n_mag_bits)


def hybrid_mac_bit_true(
    x_q: Array,
    w_q: Array,
    macro: MacroInstance,
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_key: Optional[Array] = None,
) -> dict:
    """One macro conversion: MAC of ``x_q`` and ``w_q`` over the last axis.

    x_q, w_q : int arrays in [-127,127], trailing axis = acc_len (broadcast
               batch dims allowed).
    Returns dict(y8, dcim, adc_code, a_real, exact) where ``exact`` is the
    full-precision integer dot product and ``y8`` the macro's 8-bit output
    (y8 * 2^11 approximates ``exact``).
    """
    xb = _signed_bits(x_q, cfg)  # (..., L, 7) in {-1,0,1}
    wb = _signed_bits(w_q, cfg)
    # signed bit-product tensor: (..., L, 7, 7); entry = sigma_i * Ij * Wk
    bp = xb[..., :, :, None] * wb[..., :, None, :]

    dcim_w = jnp.asarray(cfg.dcim_weight_table())          # (7,7) small ints
    acim_w = jnp.asarray(cfg.acim_weight_table(), jnp.float32)
    eps = macro.eps_array                                   # (L,7,7) or (7,7)
    real_w = acim_w * (1.0 + eps)                           # broadcasts

    dcim = jnp.sum(bp * dcim_w, axis=(-3, -2, -1))          # exact int
    a_real = jnp.sum(bp.astype(jnp.float32) * real_w, axis=(-3, -2, -1))
    a_ideal = jnp.sum(bp.astype(jnp.int32) * acim_w.astype(jnp.int32), axis=(-3, -2, -1))

    # VREFCLK polarity-path asymmetry: +/- conversions see slightly
    # different reference gains (max INL lands at the zero crossing)
    a_real = a_real * (1.0 + macro.vref_pol_eps * jnp.sign(a_real))
    adc_code = sar_adc(a_real / cfg.dcim_lsb, macro.adc_cap_eps, cfg, noise_key)
    y8 = dcim + adc_code
    exact = jnp.sum(x_q.astype(jnp.int32) * w_q.astype(jnp.int32), axis=-1)
    return dict(y8=y8, dcim=dcim, adc_code=adc_code, a_real=a_real,
                a_ideal=a_ideal, exact=exact)


def hybrid_mac_ideal(x_q: Array, w_q: Array, cfg: CCIMConfig = DEFAULT_CONFIG) -> Array:
    """Mismatch-free macro output (only ADC rounding/clipping remains)."""
    xb = _signed_bits(x_q, cfg)
    wb = _signed_bits(w_q, cfg)
    bp = xb[..., :, :, None] * wb[..., :, None, :]
    dcim = jnp.sum(bp * jnp.asarray(cfg.dcim_weight_table()), axis=(-3, -2, -1))
    a = jnp.sum(bp.astype(jnp.int32) * jnp.asarray(cfg.acim_weight_table()),
                axis=(-3, -2, -1))
    half = cfg.adc_half_range
    code = jnp.clip(jnp.floor(a / cfg.dcim_lsb + 0.5), -half, half - 1)
    return dcim + code.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Hybrid MAC -- fast path (moment-matched; 0 bit-planes)
# ---------------------------------------------------------------------------
#
# For i.i.d. per-(row, j, k) cap mismatch, the analog error
#     A_real - A_ideal = sum_i sigma_i sum_jk B_ijk 2^(j+k) eps_ijk
# has variance  sigma_u^2 * sum_i sum_jk B_ijk 2^(j+k)  (since
# Var[2^(j+k) eps] = 2^(j+k) sigma_u^2), i.e. sigma_u^2 times the *unsigned*
# ACIM magnitude sum -- computable from |x| * |w| alone.  The fast path
# exploits this: exact integer arithmetic for DCIM + ideal-ACIM, plus one
# Gaussian with the exactly matched variance.  2 multiplies per element
# instead of 49 bit-products.  This is the TPU-deployable emulation; tests
# verify its first two error moments against the bit-true oracle.


def _dcim_terms(x_q: Array, w_q: Array, cfg: CCIMConfig) -> Tuple[Array, Array]:
    """Per-element DCIM value and unsigned ACIM magnitude (no bit planes)."""
    sx, mx = split_sign_mag(x_q)
    sw, mw = split_sign_mag(w_q)
    sig = sx * sw
    d_elem = jnp.zeros_like(mx)
    for j, k in cfg.dcim_products:
        d_elem = d_elem + ((mx >> j) & 1) * ((mw >> k) & 1) * (
            (1 << (j + k)) // cfg.dcim_lsb
        )
    prod = mx.astype(jnp.int32) * mw.astype(jnp.int32)
    acim_mag = prod - d_elem.astype(jnp.int32) * cfg.dcim_lsb  # unsigned, >= 0
    return sig * d_elem, sig.astype(jnp.int32) * acim_mag, acim_mag


def hybrid_mac_fast(
    x_q: Array,
    w_q: Array,
    noise_key: Optional[Array],
    cfg: CCIMConfig = DEFAULT_CONFIG,
) -> dict:
    """Moment-matched macro model: exact ints + one matched Gaussian + ADC."""
    d_elem, a_elem, a_mag = _dcim_terms(x_q, w_q, cfg)
    dcim = jnp.sum(d_elem, axis=-1)
    a_ideal = jnp.sum(a_elem, axis=-1)
    var = (cfg.sigma_unit**2 * cfg.fast_noise_correction
           * jnp.sum(a_mag, axis=-1).astype(jnp.float32))
    var = var + (cfg.comparator_noise_lsb * cfg.dcim_lsb) ** 2  # dynamic noise
    a_real = a_ideal.astype(jnp.float32)
    if noise_key is not None:
        a_real = a_real + jnp.sqrt(var) * jax.random.normal(noise_key, a_real.shape)
    half = cfg.adc_half_range
    code = jnp.clip(jnp.floor(a_real / cfg.dcim_lsb + 0.5), -half, half - 1).astype(
        jnp.int32
    )
    y8 = dcim + code
    exact = jnp.sum(x_q.astype(jnp.int32) * w_q.astype(jnp.int32), axis=-1)
    return dict(y8=y8, dcim=dcim, adc_code=code, a_real=a_real, a_ideal=a_ideal,
                exact=exact)


# ---------------------------------------------------------------------------
# Fast path, matmul-ized (the GEMM-shaped formulation of hybrid_mac_fast)
# ---------------------------------------------------------------------------
#
# hybrid_mac_fast applied to a broadcast (M,1,C,L) x (1,N,C,L) pair
# materializes O(M*N*C*L) intermediates elementwise -- memory-bound.  Every
# per-chunk quantity it needs is a sum over L of per-element products, so
# each is ONE batched (C,M,L)x(C,L,N) matmul instead:
#
#   exact_c    = x_c . w_c                         (signed int dot)
#   dcim_c     = sum_j xj_c . (sum_k 2^(j+k)/2^11 * wk_c)   (signed planes,
#                one dot per distinct x bit-plane -- 2 for the top-3 split)
#   a_ideal_c  = exact_c - 2^11 * dcim_c
#   |acim|_c   = |x|_c . |w|_c - 2^11 * dcim_mag_c (unsigned planes)
#
# The dots run in float32: every contraction is a sum of <= acc_len
# products of 7-bit magnitudes (< 2^24), so float32 accumulation is exact
# and the result is bit-identical to the broadcast formulation -- while the
# MXU / vector FMA units do the work.  The optimization_barrier keeps XLA
# from fusing the operand prep into the GEMM loops (which knocks the CPU
# backend off its fast GEMM path).  This is the default GEMM hot path.


_CHUNK_BLOCK = 16  # ADC conversions processed per scan step (cache-sized)
_SKINNY_M = 16     # at/below this M the scan collapses to one step (decode)
_UNROLL_BLOCKS = 4  # chunk loops at/below this length unroll (no while-op)


def _dcim_by_j(cfg: CCIMConfig) -> dict:
    """dcim_products grouped by the x bit-plane index j (insertion order)."""
    by_j: dict = {}
    for j, k in cfg.dcim_products:
        by_j.setdefault(j, []).append(k)
    return by_j


def fold_dcim_planes(wq: Array, cfg: CCIMConfig = DEFAULT_CONFIG) -> Array:
    """Folded signed DCIM planes of integer weights, (J, *wq.shape): one
    per distinct j (J = 0 for an all-analog split).

    Plane_j = sign(w) * sum_{k in ks(j)} (2^(j+k)/dcim_lsb) * bit_k(|w|):
    the k-planes of w fold into a single weighted plane per x bit-plane
    (dcim = x6 . (2*w6 + w5) + x5 . w6 for the top-3 split; values fit
    int8).  The ONE definition of the fold -- the fast GEMM, the Pallas
    prepacked kernels and engine packing all consume it.
    """
    sw, mw = split_sign_mag(wq)
    planes = []
    for j, ks in _dcim_by_j(cfg).items():
        wsum = jnp.zeros_like(mw)
        for k in ks:
            wsum = wsum + ((mw >> k) & 1) * ((1 << (j + k)) // cfg.dcim_lsb)
        planes.append(sw * wsum)
    return jnp.stack(planes) if planes else jnp.zeros((0,) + wq.shape,
                                                      sw.dtype)


def fast_gemm_weight_ops(
    w: Array,                        # (K', N') ints, K' >= C*L, N' >= N
    planes: Array,                   # (J, K', N') folded signed planes
    k_dim: int,
    n_dim: int,
    acc_len: int,
) -> Tuple[Array, Array]:
    """Weight-side operands of the fast GEMM, derived from integer tiles.

    Returns (wf, w_planes): the chunked (C, L, N) float32 weights and the
    folded DCIM planes as ONE float32 (C, J*L, N) array -- the per-j
    planes concatenate along L, so the whole DCIM term is a single
    batched dot against the matching concatenated x planes (decode-shaped
    calls are launch-bound: one dot instead of J).  Planes carry the
    weight sign; their abs() is the magnitude plane the noisy path needs.

    ``w`` and ``planes`` may be block-padded (the engine's int8 tiles):
    rows past ``k_dim`` are zero and only the first C*L rows and ``n_dim``
    columns are read.  Every value is a small integer, so the float32
    conversion is exact.
    """
    C = _pad_to_chunks(k_dim, acc_len)
    kc = C * acc_len
    J = planes.shape[0]
    wf = w[:kc, :n_dim].astype(jnp.float32).reshape(C, acc_len, n_dim)
    w_pl = jnp.transpose(
        planes[:, :kc, :n_dim].astype(jnp.float32).reshape(
            J, C, acc_len, n_dim), (1, 0, 2, 3)).reshape(C, J * acc_len, n_dim)
    return wf, w_pl


def hybrid_mac_fast_gemm(
    xq: Array,                       # (M, C, L) ints in [-127, 127]
    wq: Array,                       # (C, L, N) ints in [-127, 127]
    noise_key: Optional[Array],
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_segments: Optional[Tuple[int, ...]] = None,
) -> Array:
    """Chunked fast-path GEMM; returns sum_c y8_c as (M, N) int32 (unscaled).

    Bit-identical (including the noise draw) to summing hybrid_mac_fast's
    y8 over the (M,1,C,L) x (1,N,C,L) broadcast of the same operands.
    """
    C, L, N = wq.shape
    w = wq.reshape(C * L, N)
    wf, w_pl = fast_gemm_weight_ops(w, fold_dcim_planes(w, cfg), C * L, N, L)
    return hybrid_mac_fast_gemm_prepacked(xq, wf, w_pl, noise_key, cfg,
                                          noise_segments=noise_segments)


def _fast_gemm_noise(noise_key, M: int, N: int, C: int,
                     noise_segments: Optional[Tuple[int, ...]]) -> Array:
    """The fast path's (C, M, N) mismatch/comparator noise draw.

    Drawn in the broadcast path's (M, N, C) layout, then re-laid-out, so
    noisy results stay bit-identical to hybrid_mac_fast.  For a fused
    projection group (see models.layers._dense_group) ``noise_key`` is a
    tuple of per-segment keys and ``noise_segments`` the per-segment N
    sizes: each segment draws from ITS OWN stream -- exactly the draw the
    unfused per-projection call would make -- and the draws concatenate
    along N, so fusion stays bit-identical even under analog noise.
    """
    if noise_segments is not None:
        assert len(noise_segments) == len(noise_key), (
            noise_segments, len(noise_key))
        assert sum(noise_segments) == N, (noise_segments, N)
        draw = jnp.concatenate(
            [jax.random.normal(k, (M, n, C))
             for k, n in zip(noise_key, noise_segments)], axis=1)
    else:
        draw = jax.random.normal(noise_key, (M, N, C))
    return jnp.transpose(draw, (2, 0, 1))


def hybrid_mac_fast_gemm_prepacked(
    xq: Array,                       # (M, C, L) ints in [-127, 127]
    wf: Array,                       # (C, L, N) float32 weight copy
    w_pl: Array,                     # (C, J*L, N) concatenated folded planes
    noise_key: Optional[Array],
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_segments: Optional[Tuple[int, ...]] = None,
    chunk_block: Optional[int] = None,
) -> Array:
    """Fast-path GEMM on prepacked weight operands (see fast_gemm_weight_ops).

    Only activation-side quantities are derived here -- the weight side
    streams from storage exactly as bit-cells do in the silicon macro.
    The chunk axis is processed ``chunk_block`` conversions at a time
    inside a scan, so the (Cb, M, N) partials stay cache-resident instead
    of streaming O(C*M*N) intermediates through memory.  Noise-free runs
    need exactly TWO batched dots per step: the exact dot, plus one dot
    of the L-concatenated x bit-planes against the L-concatenated folded
    weight planes (bit-identical to per-j dots -- every partial is an
    exact integer in float32); the magnitude dots feeding the matched
    variance exist only when a noise_key is given.

    ``chunk_block`` is a pure scheduling knob: partials are summed in
    int32, so ANY block size gives bit-identical results.  None consults
    the persisted tuning cache (kernels.ccim_matmul.autotune) at trace
    time, falling back to one single step for skinny (decode-shaped) M --
    a scan over tiny (cb, M, L) x (cb, L, N) batched GEMMs is pure
    dispatch overhead when the (C, M, N) partials already fit in cache.
    At skinny M the single-step path also drops the chunk-axis blocking
    machinery and the operand-prep barrier entirely: decode is bound by
    kernel-launch count, and fusing the tiny prep/epilogue chains is a
    win there (the barrier exists to protect the LARGE-shape GEMM loops).
    """
    M, C, L = xq.shape
    sx, mx = split_sign_mag(xq)
    xT = lambda v: jnp.transpose(v, (1, 0, 2))              # -> (C, M, L)
    xf = xT(xq).astype(jnp.float32)
    sxf, mxT = xT(sx).astype(jnp.float32), xT(mx)

    # one x bit-plane per distinct j, concatenated along L to pair with
    # the (C, J*L, N) folded weight planes in ONE batched dot
    x_pl, xm_pl = [], []
    for j in _dcim_by_j(cfg):
        xbit = ((mxT >> j) & 1).astype(jnp.float32)
        x_pl.append(sxf * xbit)
        xm_pl.append(xbit)
    n_j = len(x_pl)
    xcat = (jnp.concatenate(x_pl, axis=-1) if n_j
            else jnp.zeros((C, M, 0), jnp.float32))

    noisy = noise_key is not None
    ops = [xf, wf, xcat, w_pl]
    if noisy:
        # |folded signed plane| == the magnitude plane (the fold weights
        # are non-negative), so the mags need no separate storage
        xmcat = (jnp.concatenate(xm_pl, axis=-1) if n_j
                 else jnp.zeros((C, M, 0), jnp.float32))
        ops += [jnp.abs(xf), jnp.abs(wf), xmcat, jnp.abs(w_pl)]
        ops.append(_fast_gemm_noise(noise_key, M, wf.shape[-1], C,
                                    noise_segments))

    if chunk_block is None:
        from ..kernels.ccim_matmul.autotune import tuned_chunk_block
        chunk_block = tuned_chunk_block(M, C, wf.shape[-1], cfg.acc_len)
    cb = min(chunk_block, C)
    n_blk = (C + cb - 1) // cb

    if M > _SKINNY_M:
        # barrier: keep XLA from fusing operand prep into the GEMM loops
        # (the CPU backend falls off its fast GEMM path otherwise).  At
        # skinny M the GEMMs are launch-bound, not loop-bound -- fusing
        # the tiny prep chains is strictly better, so no barrier there.
        ops = list(jax.lax.optimization_barrier(tuple(ops)))

    dyn_var = (cfg.comparator_noise_lsb * cfg.dcim_lsb) ** 2
    lsb, half = float(cfg.dcim_lsb), cfg.adc_half_range
    # telemetry tap (obs/taps.py): count ADC codes the clip saturates.
    # Trace-time flag -- with no collector open (telemetry off) the
    # lowered program is unchanged
    tap_clip = taps.active("adc_clip")
    # fault injection (resilience/faults.py): same static-flag contract.
    # Drift perturbs only the ANALOG quantities -- the a_real partial
    # before conversion and the SAR conversion itself -- never the exact
    # DCIM adder, matching where the physics lives.  Terms are severity-
    # scaled by the armed model's clock, which may be a traced loop
    # counter: one executable covers the whole drift schedule.
    fault_on = rfaults.active()
    if fault_on:
        f_gain, f_off, f_adc_off, f_scale = rfaults.epilogue_terms(
            wf.shape[-1])
        half_eff = jnp.maximum(1.0, jnp.floor(half * f_scale))

    def step(acc, inp, bmask=None):
        if noisy:
            bxf, bwf, bxc, bwc, bmx, bmw, bxmc, bwmc, bnoise = inp
        else:
            bxf, bwf, bxc, bwc = inp
        # float32 GEMMs and epilogue are exact: every value is an integer
        # well below 2^24 (|chunk dot| <= acc_len * 127^2)
        a_real = jnp.matmul(bxf, bwf)                       # (cb, M, N)
        dcim = jnp.matmul(bxc, bwc) if n_j else jnp.zeros_like(a_real)
        a_real = a_real - dcim * lsb                        # = ideal ACIM
        if noisy:
            a_mag = jnp.matmul(bmx, bmw) - lsb * (
                jnp.matmul(bxmc, bwmc) if n_j else 0.0)
            var = cfg.sigma_unit**2 * cfg.fast_noise_correction * a_mag
            a_real = a_real + jnp.sqrt(var + dyn_var) * bnoise
        if fault_on:
            # capacitor-array drift: per-column gain/offset on the analog
            # partial, then ADC conversion offset and clip escalation
            a_real = a_real * f_gain + f_off * lsb
            raw = jnp.floor(a_real / lsb + 0.5 + f_adc_off)
            code = jnp.clip(raw, -half_eff, half_eff - 1)
        else:
            raw = jnp.floor(a_real / lsb + 0.5)
            code = jnp.clip(raw, -half, half - 1)
        y8 = (dcim + code).astype(jnp.int32)
        if bmask is not None:
            y8 = y8 * bmask[:, None, None]
        clip = None
        if tap_clip:
            lo, hi = (-half_eff, half_eff - 1) if fault_on else \
                (-half, half - 1)
            over = ((raw < lo) | (raw > hi)).astype(jnp.int32)
            if bmask is not None:
                over = over * bmask[:, None, None]    # phantom chunks
            clip = jnp.sum(over)
        return acc + jnp.sum(y8, axis=0), clip

    acc0 = jnp.zeros((M, wf.shape[-1]), jnp.int32)
    if n_blk == 1:
        # single step (the decode shape): no chunk-axis padding, blocking
        # reshapes or phantom-chunk mask -- the step runs on the raw ops
        out, clip = step(acc0, tuple(ops))
        if tap_clip:
            taps.emit("adc_clip", clip)
        return out

    # pad the chunk axis to the scan block; phantom chunks are masked so
    # the noisy path sees exactly C conversions, as in silicon
    pad = n_blk * cb - C
    mask = jnp.ones((C,), jnp.int32)
    blk = lambda v: jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
        n_blk, cb, *v.shape[1:]
    )
    xs = jax.tree_util.tree_map(blk, tuple(ops))
    bmasks = blk(mask)
    if n_blk <= _UNROLL_BLOCKS:
        # short chunk loops unroll: lax.scan lowers to a while-op whose
        # loop-carry copies and trip machinery cost more than the math at
        # decode shapes (int32 partial sums -- order-identical to the scan)
        acc = acc0
        clip = jnp.zeros((), jnp.int32)
        for i in range(n_blk):
            acc, c = step(acc, jax.tree_util.tree_map(lambda v: v[i], xs),
                          bmasks[i])
            if tap_clip:
                clip = clip + c
        if tap_clip:
            taps.emit("adc_clip", clip)
        return acc
    out, clips = jax.lax.scan(lambda a, i: step(a, i[:-1], i[-1]), acc0,
                              xs + (bmasks,))
    if tap_clip:
        taps.emit("adc_clip", jnp.sum(clips))
    return out


# ---------------------------------------------------------------------------
# Macro-tiled integer matmul (the GEMM engine built from conversions)
# ---------------------------------------------------------------------------


def _pad_to_chunks(k: int, acc_len: int) -> int:
    return (k + acc_len - 1) // acc_len


def _kernel_numerics_match(cfg: CCIMConfig) -> bool:
    """True when ``cfg`` matches the constants the Pallas kernels hardcode
    (prototype accumulate length, SMF width, top-3 DCIM split, 7b ADC)."""
    d = DEFAULT_CONFIG
    return (cfg.acc_len == d.acc_len and cfg.n_mag_bits == d.n_mag_bits
            and cfg.dcim_products == d.dcim_products
            and cfg.adc_bits == d.adc_bits)


def cim_matmul_int(
    x_q: Array,
    w_q: Array,
    macro: Optional[MacroInstance],
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_key: Optional[Array] = None,
    fidelity: str = "fast",
    *,
    use_pallas: Optional[bool] = None,
    noise_segments: Optional[Tuple[int, ...]] = None,
) -> Array:
    """Integer GEMM through the macro:  (M,K) @ (K,N) -> (M,N) int64.

    K is tiled into acc_len-element chunks; each chunk is one ADC conversion
    producing an 8-bit partial, accumulated digitally at weight 2^11 --
    exactly how a compiler would tile a GEMM onto a bank of these macros.

    fidelity:
      'fast'            matmul-ized moment-matched path (the default hot path)
      'fast_broadcast'  legacy elementwise-broadcast fast path (reference)
      'bit_true'        per-bit-product oracle with the fabricated mismatch
      'exact'           full-precision integer dot (no macro arithmetic)

    use_pallas: route noise-free 'fast' GEMMs through the Pallas TPU kernel
    (kernels.ccim_matmul -- identical ideal-analog numerics).  None = auto
    (only on a TPU backend, with defaults-config numerics).

    ``w_q`` may be a ``engine.PackedCimWeights`` (weight-stationary
    execution: quantize/decompose once, serve many) -- bit-identical to
    passing the raw integer weights.
    """
    from .engine import PackedCimWeights, packed_cim_matmul_int
    if isinstance(w_q, PackedCimWeights):
        return packed_cim_matmul_int(x_q, w_q, macro, cfg, noise_key,
                                     fidelity, use_pallas=use_pallas,
                                     noise_segments=noise_segments)
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (K, K2)
    if noise_segments is not None and fidelity not in ("fast", "exact"):
        raise ValueError(
            "per-segment noise streams (fused projection groups) are only "
            f"defined for the 'fast'/'exact' fidelities, got {fidelity!r}")
    # an armed fault model (resilience/faults) lives in the XLA epilogue
    # only -- the Pallas kernel models the nominal macro
    if (fidelity == "fast" and noise_key is None
            and _kernel_numerics_match(cfg) and not rfaults.active()):
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        if use_pallas:
            from ..kernels.ccim_matmul import ops as _kops
            return _kops.ccim_matmul_int(x_q, w_q, use_pallas=True)
    C = _pad_to_chunks(K, cfg.acc_len)
    pad = C * cfg.acc_len - K
    xq = jnp.pad(x_q, ((0, 0), (0, pad)))
    wq = jnp.pad(w_q, ((0, pad), (0, 0)))
    xq = xq.reshape(M, C, cfg.acc_len)              # (M,C,L)
    wq = wq.reshape(C, cfg.acc_len, N)              # (C,L,N)

    if fidelity == "fast":
        # per-conversion partials are accumulated digitally inside the scan
        return hybrid_mac_fast_gemm(xq, wq, noise_key, cfg,
                                    noise_segments) * cfg.dcim_lsb
    elif fidelity == "fast_broadcast":
        xc = xq[:, None, :, :]                      # (M,1,C,L)
        wc = jnp.transpose(wq, (2, 0, 1))[None]     # (1,N,C,L)
        out = hybrid_mac_fast(xc, wc, noise_key, cfg)
    elif fidelity == "bit_true":
        assert macro is not None
        xc = xq[:, None, :, :]
        wc = jnp.transpose(wq, (2, 0, 1))[None]
        out = hybrid_mac_bit_true(xc, wc, macro, cfg, noise_key)
    elif fidelity == "exact":
        return jnp.einsum("mcl,cln->mn", xq.astype(jnp.int32), wq.astype(jnp.int32))
    else:
        raise ValueError(fidelity)
    # digital accumulation of per-conversion partials, each worth 2^11
    return jnp.sum(out["y8"].astype(jnp.int32), axis=-1) * cfg.dcim_lsb


# ---------------------------------------------------------------------------
# Float-in/float-out CIM linear (quantize -> macro GEMM -> dequantize)
# ---------------------------------------------------------------------------


def cim_matmul(
    x: Array,
    w: Array,
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_key: Optional[Array] = None,
    macro: Optional[MacroInstance] = None,
    fidelity: str = "fast",
    per_channel: bool = True,
    use_pallas: Optional[bool] = None,
    noise_segments: Optional[Tuple[int, ...]] = None,
) -> Array:
    """float (M,K) @ (K,N) through the emulated macro, dequantized.

    ``w`` may be a ``engine.PackedCimWeights``; activation quantization
    then runs per call while the weight conditioning is served prepacked.
    """
    from .engine import PackedCimWeights, packed_cim_matmul
    if isinstance(w, PackedCimWeights):
        return packed_cim_matmul(x, w, cfg, noise_key=noise_key, macro=macro,
                                 fidelity=fidelity, use_pallas=use_pallas,
                                 noise_segments=noise_segments)
    sx = smf_scale(x, axis=-1, keepdims=True, cfg=cfg)          # per row
    sw = (
        smf_scale(w, axis=0, keepdims=True, cfg=cfg)
        if per_channel
        else smf_scale(w, cfg=cfg)
    )
    xq = quantize_smf(x, sx, cfg)
    wq = quantize_smf(w, sw, cfg)
    y_int = cim_matmul_int(xq, wq, macro, cfg, noise_key, fidelity,
                           use_pallas=use_pallas,
                           noise_segments=noise_segments)
    return y_int.astype(jnp.float32) * sx * jnp.reshape(sw, (1, -1))


def contribution_table(cfg: CCIMConfig = DEFAULT_CONFIG) -> np.ndarray:
    """Fig. 2 analysis: fractional contribution of each (j,k) bit product."""
    nb = cfg.n_mag_bits
    w = np.array([[2.0 ** (j + k) for k in range(nb)] for j in range(nb)])
    return w / w.sum()
