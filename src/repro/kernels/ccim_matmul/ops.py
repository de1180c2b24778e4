"""Jitted public wrapper for the hybrid-CIM GEMM kernel.

Handles: float->SMF quantization, K padding to the accumulate length,
(bm,bn,bk) block selection, CPU fallback (interpret mode), and dequant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (ACC_LEN, DCIM_LSB, SKINNY_SUBLANE, ccim_matmul_pallas,
                     ccim_matmul_prepacked_pallas,
                     ccim_matmul_prepacked_skinny_pallas)
from .ref import ccim_matmul_ref

# VMEM budget (bytes) the skinny kernel's plane residency may claim; above
# this the dispatcher keeps the general streaming kernel (16 MiB VMEM on
# current TPUs; leave headroom for the double-buffered w stream + output)
SKINNY_VMEM_BUDGET = 8 * 1024 * 1024


def _pad_to(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _pick_block(dim: int, preferred: int) -> int:
    """Block size for ``dim``; the caller pads ``dim`` up to a multiple.

    Dims at or above the MXU-preferred block always get the full block (the
    ragged remainder is padded with zeros -- numerically free: all-zero
    chunks produce y8 = 0) instead of degrading to tiny non-lane-aligned
    blocks (the old divisor search turned e.g. M=160 into bm=32).  Smaller
    dims round up to the next power of two.
    """
    if dim >= preferred:
        return preferred
    return 1 << max(dim - 1, 0).bit_length()


def _pick_k_block(c: int, preferred: int = 32) -> int:
    """Chunk-count block for the K axis: largest power-of-two block whose
    zero-padding waste stays under 25%.  Unlike M/N, K has no lane-
    alignment constraint beyond the ACC_LEN chunking, so trading block
    size against padded-out MACs is free (e.g. C=33 takes an 8-chunk
    block over padding 33 -> 64 with a 32-chunk one)."""
    b = _pick_block(c, preferred)
    while b > 1 and (-c % b) * 4 > c:
        b //= 2
    return b


def _pick_k_tile(k_dim: int, acc_len: int) -> tuple[int, int]:
    """(bk, Kp): the K block and the padded K it tiles.

    bk is a whole number of conversions and, on the TPU, a multiple of the
    128-lane tile -- or, where no such block keeps the padding waste under
    25% (K under ~480), the whole padded K in one grid step, which a block
    spanning the full axis is allowed to be."""
    kc = _pad_to(k_dim, acc_len)
    bk = _pick_k_block(kc // acc_len) * acc_len
    if bk % 128:
        bk = kc
    return bk, _pad_to(kc, bk)


def pick_gemm_blocks(M: int, N: int, K: int,
                     acc_len: int = ACC_LEN) -> tuple[int, int, int]:
    """(bm, bn, bk) for an (M, K) x (K, N) macro GEMM; K in acc_len chunks."""
    return _pick_block(M, 128), _pick_block(N, 128), _pick_k_tile(K, acc_len)[0]


def pick_weight_blocks(K: int, N: int,
                       acc_len: int = ACC_LEN) -> tuple[int, int, int, int]:
    """(bn, bk, Np, Kp) weight-side block selection and padded dims.

    Deliberately M-independent (bm only shapes the activation tile), so a
    weight matrix can be padded ONCE at pack time and reused for every
    activation batch shape -- the weight-stationary contract.  ``acc_len``
    is the packed config's accumulate length (the deployment planner
    assigns non-prototype lengths per projection).
    """
    bn = _pick_block(N, 128)
    bk, Kp = _pick_k_tile(K, acc_len)
    return bn, bk, _pad_to(N, bn), Kp


def ccim_matmul_int_prepacked(
    x_q: jax.Array,           # (M, K) ints in [-127, 127]
    w_q: jax.Array,           # (Kp, Np) int8, block-padded at pack time
    planes: jax.Array,        # (n_planes, Kp, Np) int8 folded DCIM planes
    *,
    k_dim: int, n_dim: int,
    acc_len: int = ACC_LEN,
    x_bits: tuple = (6, 5),
    dcim_lsb: int = DCIM_LSB,
    adc_bits: int = 7,
    use_pallas: bool | None = None, interpret: bool | None = None,
    skinny_blocks: tuple | None = None,
) -> jax.Array:
    """Prepacked-weight macro GEMM: only the activations are padded and
    decomposed per call.  Bit-identical to ``cim_matmul_int`` (fast
    fidelity, noise-free) on the raw integer weights the pack was built
    from.  The packed D/A split rides in as static meta -- ``x_bits`` (one
    activation bit index per folded plane; the plane COUNT is the plan's
    ``n_dcim_products`` grouped by x bit), ``dcim_lsb``, ``adc_bits`` and
    ``acc_len`` -- so one kernel serves every deployment-plan design point.

    Decode-shaped calls (M <= SKINNY_SUBLANE) route to the skinny-M kernel
    -- M padded to the int8 sublane width instead of the 128-lane MXU
    block, folded planes VMEM-resident across the K-loop -- with (bn, bk)
    from the persisted tuning cache (autotune.tuned_skinny_blocks) when
    available; ``skinny_blocks`` forces a candidate (the autotuner's
    search hook).  All routes are bit-identical.
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu
    if interpret is None:
        interpret = not on_tpu
    M, K = x_q.shape
    assert K == k_dim, (K, k_dim)
    bn, bk, Np, Kp = pick_weight_blocks(k_dim, n_dim, acc_len)
    assert w_q.shape == (Kp, Np), (w_q.shape, Kp, Np)
    if not use_pallas:
        default = (acc_len == ACC_LEN and tuple(x_bits) == (6, 5)
                   and dcim_lsb == DCIM_LSB and adc_bits == 7)
        if not default:
            raise ValueError(
                "non-prototype D/A splits are served by the generalized "
                "Pallas kernel (interpret mode off-TPU); pass "
                "use_pallas=True")
        xp = jnp.pad(x_q, ((0, 0), (0, Kp - K)))
        return ccim_matmul_ref(xp.astype(jnp.int32),
                               w_q.astype(jnp.int32))[:, :n_dim]
    n_planes = len(x_bits)
    if M <= SKINNY_SUBLANE:
        from . import autotune
        if skinny_blocks is None:
            skinny_blocks = (autotune.tuned_skinny_blocks(
                Kp, Np, acc_len, n_planes) or (bn, bk))
        sbn, sbk = skinny_blocks
        fits = (max(n_planes, 1) * Kp * sbn <= SKINNY_VMEM_BUDGET
                and Np % sbn == 0 and Kp % sbk == 0
                and sbk % acc_len == 0 and sbk % SKINNY_SUBLANE == 0)
        if fits:
            xp = jnp.pad(x_q, ((0, SKINNY_SUBLANE - M), (0, Kp - K)))
            y = ccim_matmul_prepacked_skinny_pallas(
                xp.astype(jnp.int8), w_q, planes,
                bn=sbn, bk=sbk, acc_len=acc_len, x_bits=tuple(x_bits),
                dcim_lsb=dcim_lsb, adc_half=1 << (adc_bits - 1),
                interpret=interpret,
            )
            return y[:M, :n_dim]
    bm = _pick_block(M, 128)
    Mp = _pad_to(M, bm)
    xp = jnp.pad(x_q, ((0, Mp - M), (0, Kp - K)))
    y = ccim_matmul_prepacked_pallas(
        xp.astype(jnp.int8), w_q, planes,
        bm=bm, bn=bn, bk=bk, acc_len=acc_len, x_bits=tuple(x_bits),
        dcim_lsb=dcim_lsb, adc_half=1 << (adc_bits - 1), interpret=interpret,
    )
    return y[:M, :n_dim]


def ccim_matmul_int(
    x_q: jax.Array, w_q: jax.Array, *, use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(M,K) x (K,N) int8-range ints -> int32 macro GEMM (scale 2^11)."""
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = on_tpu
    if interpret is None:
        interpret = not on_tpu
    M, K = x_q.shape
    _, N = w_q.shape
    Kp = _pad_to(K, ACC_LEN)
    if Kp != K:
        x_q = jnp.pad(x_q, ((0, 0), (0, Kp - K)))
        w_q = jnp.pad(w_q, ((0, Kp - K), (0, 0)))
    if not use_pallas:
        return ccim_matmul_ref(x_q, w_q)
    bm, bn, bk = pick_gemm_blocks(M, N, Kp)
    Mp, Np, Kpp = _pad_to(M, bm), _pad_to(N, bn), _pad_to(Kp, bk)
    if (Mp, Np, Kpp) != (M, N, Kp):
        x_q = jnp.pad(x_q, ((0, Mp - M), (0, Kpp - Kp)))
        w_q = jnp.pad(w_q, ((0, Kpp - Kp), (0, Np - N)))
    y = ccim_matmul_pallas(
        x_q.astype(jnp.int8), w_q.astype(jnp.int8),
        bm=bm, bn=bn, bk=bk, interpret=interpret,
    )
    return y[:M, :N]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def ccim_matmul(
    x: jax.Array, w: jax.Array, *, use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """float GEMM through the (ideal-analog) macro numerics, dequantized."""
    amax_x = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
    amax_w = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12)
    sx, sw = amax_x / 127.0, amax_w / 127.0
    xq = jnp.clip(jnp.round(x / sx), -127, 127).astype(jnp.int32)
    wq = jnp.clip(jnp.round(w / sw), -127, 127).astype(jnp.int32)
    y = ccim_matmul_int(xq, wq, use_pallas=use_pallas, interpret=interpret)
    return y.astype(jnp.float32) * sx * sw
