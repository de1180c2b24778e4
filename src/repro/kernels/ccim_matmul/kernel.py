"""Pallas TPU kernel: hybrid DCIM/ACIM quantized GEMM (the macro's numerics).

TPU adaptation of the paper's dataflow (see DESIGN.md §2): the MXU plays the
role of the bit-parallel array.  Per 16-element K-chunk ("one ADC
conversion") we compute

    exact_c = x_c . w_c                       (MXU dot, exact in f32)
    dcim_c  = 2*x6.w6 + x6.w5 + x5.w6         (3 signed MSB bit-plane dots)
    acim_c  = exact_c - 2^11 * dcim_c         (the analog group's ideal sum)
    code_c  = clip(floor(acim_c/2^11 + 1/2), -64, 63)     (7b SAR ADC)
    y8_c    = dcim_c + code_c                 (post-digital adder)
    out    += 2^11 * sum_c y8_c               (digital partial accumulation)

i.e. the *ideal-analog* bit-true macro arithmetic (mismatch noise is a
training-time emulation feature injected at the jnp level, see core.qat;
the silicon itself has frozen mismatch -- the kernel models the design
arithmetic).

Conversion layout: each conversion is a STATIC slice of the tile -- an
acc_len-wide lane slice of the activations and an acc_len-row sublane
slice of the weights -- walked by an unrolled Python loop
(``_conversion_sum``).  Mosaic refuses the reshape that would split the
lane axis into (C, L), so no kernel here reshapes a tile.  Operands go to
the MXU as bf16 with f32 accumulation: they are integers with |v| <= 127
(exact in bf16) and one conversion sums at most 64 products, at most
64 * 127^2 < 2^24, so every dot is exact and the int32 epilogue
(floor-divide, clip) sees the same integers as the jnp oracle.

Block shapes are MXU/VMEM aligned: bm, bn multiples of 128 (lane dim), bk a
multiple of acc_len; VMEM working set = bm*bk + bk*bn (int8) + bm*bn
(int32 scratch) -- 128x512x128 => 128 KiB + 64 KiB well under 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACC_LEN = 16
DCIM_LSB = 2048  # 2^11
ADC_HALF = 64    # 7-bit bipolar


def _dot(x, w):
    """(bm, L) x (L, bn) bf16 -> (bm, bn) int32; exact (module docstring)."""
    return jax.lax.dot_general(
        x, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def signed_bit(v, j: int):
    """Signed bit-plane j of an int32 tile: sign(v) * bit_j(|v|)."""
    return jnp.where(v < 0, -1, 1) * ((jnp.abs(v) >> j) & 1)


def fold_planes(w):
    """int32 weight tile -> the two folded prototype DCIM planes,
    s*(2*b6+b5) (pairs with x bit 6) and s*b6 (pairs with x bit 5):
    2*x6.w6 + x6.w5 + x5.w6 == x6.(2*w6+w5) + x5.w6 in integers."""
    w6, w5 = signed_bit(w, 6), signed_bit(w, 5)
    return 2 * w6 + w5, w6


def _bf16(v):
    return v.astype(jnp.bfloat16)


def _dcim_pairs(x, planes, x_bits):
    """(activation bit-plane j, folded weight plane) bf16 tile pairs: the
    operands of the digital term, one pair per x bit in ``x_bits``."""
    return tuple((_bf16(signed_bit(x, j)), _bf16(p))
                 for p, j in zip(planes, x_bits))


def _conversion_sum(x, w, dcim_pairs=(), *, acc_len: int = ACC_LEN,
                    dcim_lsb: int = DCIM_LSB, adc_half: int = ADC_HALF):
    """sum_c y8_c over the acc_len-wide conversions of one tile -> int32.

    ``x`` (bm, bk) and ``w`` (bk, bn) are the integer operand tiles;
    ``dcim_pairs`` lists (activation bit-plane (bm, bk), folded weight
    plane (bk, bn)) tiles whose dots make up the digital term.  All
    operands are bf16 holding integers (see the module docstring).
    """
    total = None
    for c in range(x.shape[1] // acc_len):
        s = slice(c * acc_len, (c + 1) * acc_len)
        exact = _dot(x[:, s], w[s, :])
        dcim = jnp.zeros_like(exact)
        for xj, pj in dcim_pairs:
            dcim = dcim + _dot(xj[:, s], pj[s, :])
        code = jnp.clip(
            jnp.floor_divide(exact - dcim * dcim_lsb + dcim_lsb // 2,
                             dcim_lsb),
            -adc_half, adc_half - 1)
        y8 = dcim + code
        total = y8 if total is None else total + y8
    return total


def _ccim_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; grid axis 2 walks K in bk steps."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)            # (bm, bk)
    w = w_ref[...].astype(jnp.int32)            # (bk, bn)
    # sign / magnitude decomposition (SMF): signed MSB bit-planes, the
    # weight side folded so the digital term is two dots per conversion
    pairs = _dcim_pairs(x, fold_planes(w), (6, 5))
    acc_ref[...] += _conversion_sum(_bf16(x), _bf16(w), pairs) * DCIM_LSB

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _ccim_kernel_prepacked(*refs, n_k: int, acc_len: int,
                           x_bits: tuple, dcim_lsb: int, adc_half: int):
    """Prepacked-weight variant, generalized over the macro's D/A split.

    The folded signed DCIM planes of w arrive as ONE stacked kernel input
    (packed once, off the hot path -- weight-stationary, as bit-cells in
    the silicon array), so the per-step weight work drops to zero.  The
    split itself is STATIC META: ``x_bits`` lists the activation bit-plane
    index each folded weight plane pairs with (the deployment planner's
    per-projection ``n_dcim_products`` choice determines the plane count),
    and ``dcim_lsb``/``adc_half``/``acc_len`` carry the matching ADC
    geometry.  For the 28nm prototype (top-3 split) this is x_bits=(6, 5):

        plane 0 holds s_w * (2*b6(|w|) + b5(|w|))   (pairs with x bit 6)
        plane 1 holds s_w * b6(|w|)                 (pairs with x bit 5)

    and the arithmetic is bit-identical to ``_ccim_kernel``.  With
    x_bits=() (all-analog split) there is NO planes input and every
    bit-product goes through the ADC path.
    """
    if x_bits:
        x_ref, w_ref, planes_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)            # (bm, bk)
    # activation-side decomposition only (activations stream, as in silicon)
    planes = [planes_ref[i].astype(jnp.int32) for i in range(len(x_bits))]
    acc_ref[...] += _conversion_sum(
        _bf16(x), _bf16(w_ref[...].astype(jnp.int32)),
        _dcim_pairs(x, planes, x_bits),
        acc_len=acc_len, dcim_lsb=dcim_lsb, adc_half=adc_half) * dcim_lsb

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "acc_len", "x_bits",
                              "dcim_lsb", "adc_half", "interpret")
)
def ccim_matmul_prepacked_pallas(
    x_q: jax.Array,           # (M, K) int8, values in [-127, 127]
    w_q: jax.Array,           # (K, N) int8
    planes: jax.Array,        # (n_planes, K, N) int8 folded DCIM planes
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    acc_len: int = ACC_LEN,
    x_bits: tuple = (6, 5),
    dcim_lsb: int = DCIM_LSB,
    adc_half: int = ADC_HALF,
    interpret: bool = False,
) -> jax.Array:
    """Prepacked-weight hybrid-CIM GEMM -> (M, N) int32 at scale dcim_lsb.

    ``x_bits``/``dcim_lsb``/``adc_half``/``acc_len`` are static meta
    describing the packed D/A split (see ``_ccim_kernel_prepacked``); the
    defaults are the 28nm prototype's top-3 split.
    """
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    assert planes.shape == (len(x_bits), K, N), (planes.shape, x_bits)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % acc_len == 0
    n_k = K // bk

    kernel = functools.partial(
        _ccim_kernel_prepacked, n_k=n_k, acc_len=acc_len,
        x_bits=tuple(x_bits), dcim_lsb=dcim_lsb, adc_half=adc_half)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))]
    operands = [x_q, w_q]
    if x_bits:
        in_specs.append(pl.BlockSpec((len(x_bits), bk, bn),
                                     lambda i, j, k: (0, k, j)))
        operands.append(planes)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(*operands)


SKINNY_SUBLANE = 32   # int8 sublane tile: the skinny path pads M to this


def _ccim_kernel_prepacked_skinny(*refs, bk: int, n_k: int, acc_len: int,
                                  x_bits: tuple, dcim_lsb: int,
                                  adc_half: int):
    """Decode-shaped (skinny-M) prepacked variant.

    Same macro arithmetic as ``_ccim_kernel_prepacked``, different
    schedule, built for M of a decode batch (<= 32 rows):

      * M is padded ONCE to the int8 sublane width (32) instead of the
        128-lane MXU block -- a 4x cut in wasted rows at M=4;
      * the folded DCIM planes for the current N tile arrive as ONE
        full-K resident block (index map ignores the k grid axis), so
        they stay in VMEM across the whole K-loop and are sliced
        in-kernel per k step;
      * only the weight tile streams with k -- the grid's innermost axis
        -- which the Pallas pipeline double-buffers automatically.

    VMEM cost of the residency is n_planes * K * bn int8 bytes; the
    dispatcher (ops.ccim_matmul_int_prepacked) checks the budget and
    falls back to the general kernel when it does not fit.
    """
    if x_bits:
        x_ref, w_ref, planes_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)            # (Mp, bk)
    # K-resident planes: slice this k step's rows
    planes = [planes_ref[i, pl.ds(k_step * bk, bk), :].astype(jnp.int32)
              for i in range(len(x_bits))]
    acc_ref[...] += _conversion_sum(
        _bf16(x), _bf16(w_ref[...].astype(jnp.int32)),
        _dcim_pairs(x, planes, x_bits),
        acc_len=acc_len, dcim_lsb=dcim_lsb, adc_half=adc_half) * dcim_lsb

    @pl.when(k_step == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bn", "bk", "acc_len", "x_bits", "dcim_lsb",
                              "adc_half", "interpret")
)
def ccim_matmul_prepacked_skinny_pallas(
    x_q: jax.Array,           # (Mp, K) int8, Mp a SKINNY_SUBLANE multiple
    w_q: jax.Array,           # (K, N) int8
    planes: jax.Array,        # (n_planes, K, N) int8 folded DCIM planes
    *,
    bn: int = 128,
    bk: int = 512,
    acc_len: int = ACC_LEN,
    x_bits: tuple = (6, 5),
    dcim_lsb: int = DCIM_LSB,
    adc_half: int = ADC_HALF,
    interpret: bool = False,
) -> jax.Array:
    """Skinny-M prepacked hybrid-CIM GEMM -> (Mp, N) int32 at scale
    dcim_lsb; bit-identical to ``ccim_matmul_prepacked_pallas`` (see
    ``_ccim_kernel_prepacked_skinny`` for the schedule)."""
    Mp, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    assert planes.shape == (len(x_bits), K, N), (planes.shape, x_bits)
    assert Mp % SKINNY_SUBLANE == 0, Mp
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    assert bk % acc_len == 0 and bk % SKINNY_SUBLANE == 0, (bk, acc_len)
    n_k = K // bk

    kernel = functools.partial(
        _ccim_kernel_prepacked_skinny, bk=bk, n_k=n_k, acc_len=acc_len,
        x_bits=tuple(x_bits), dcim_lsb=dcim_lsb, adc_half=adc_half)
    # grid: N tiles outer, K inner (sequential accumulation); x streams
    # (Mp, bk), w streams (bk, bn) double-buffered, planes are RESIDENT
    # full-K blocks per N tile (their index map ignores k)
    in_specs = [pl.BlockSpec((Mp, bk), lambda j, k: (0, k)),
                pl.BlockSpec((bk, bn), lambda j, k: (k, j))]
    operands = [x_q, w_q]
    if x_bits:
        in_specs.append(pl.BlockSpec((len(x_bits), K, bn),
                                     lambda j, k: (0, 0, j)))
        operands.append(planes)
    return pl.pallas_call(
        kernel,
        grid=(N // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Mp, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.int32)],
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def ccim_matmul_pallas(
    x_q: jax.Array,           # (M, K) int8, values in [-127, 127]
    w_q: jax.Array,           # (K, N) int8
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Hybrid-CIM integer GEMM -> (M, N) int32 at product scale (already x2^11)."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % ACC_LEN == 0
    n_k = K // bk

    kernel = functools.partial(_ccim_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q)
