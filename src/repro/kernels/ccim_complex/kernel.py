"""Fused single-pass complex hybrid-CIM GEMM Pallas kernel.

The silicon's headline dataflow (see DESIGN.md §5): Re and Im of each
weight are co-located in one 6T array, so ONE weight residency serves all
four real sub-MACs of (a+bi)(c+di) and the Re/Im outputs are produced with
a single conversion pass.  The kernel mirrors that: per (bm, bn, bk) grid
step it loads the w_re / w_im tiles ONCE, decomposes their MSB bit-planes
ONCE, and emits BOTH the Re and the Im output tiles -- four per-chunk
hybrid y8 streams (ac, bd, ad, bc) combined digitally as

    y_re += 2^11 * sum_c (y8_ac - y8_bd)
    y_im += 2^11 * sum_c (y8_ad + y8_bc)

Each sub-MAC uses the same ideal-analog macro arithmetic as
kernels.ccim_matmul (exact MXU dot + folded MSB bit-plane dots + 7b
mid-tread ADC per 16-element conversion, each conversion a static slice
of the tile), so the result is bit-identical to four
independent ccim_matmul passes -- but with one weight fetch instead of
four and one kernel launch instead of four.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ccim_matmul.kernel import (ACC_LEN, DCIM_LSB, SKINNY_SUBLANE,
                                  _bf16, _conversion_sum, fold_planes,
                                  signed_bit)


def _sub_mac_sums(xr, xi, wr, wi, wr_planes, wi_planes):
    """The four per-tile conversion sums (ac, bd, ad, bc) of (a+bi)(c+di).

    ``xr``/``xi`` are int32 activation tiles (bm, bk); ``wr``/``wi`` bf16
    weight tiles (bk, bn); ``*_planes`` their two folded bf16 DCIM planes
    (s*(2*b6+b5), s*b6).  Each activation is decomposed ONCE and reused
    by the two sub-MACs that read it.
    """
    def parts(x):
        return _bf16(x), (_bf16(signed_bit(x, 6)), _bf16(signed_bit(x, 5)))

    def y8(xp, w, planes):
        x, bits = xp
        return _conversion_sum(x, w, tuple(zip(bits, planes)))

    a, b = parts(xr), parts(xi)
    return (y8(a, wr, wr_planes), y8(b, wi, wi_planes),
            y8(a, wi, wi_planes), y8(b, wr, wr_planes))


def _accumulate(acc_re, acc_im, sums):
    y_ac, y_bd, y_ad, y_bc = sums
    acc_re[...] += (y_ac - y_bd) * DCIM_LSB
    acc_im[...] += (y_ad + y_bc) * DCIM_LSB


def _ccim_complex_kernel(
    xr_ref, xi_ref, wr_ref, wi_ref, or_ref, oi_ref, acc_re, acc_im,
    *, n_k: int,
):
    """One (bm, bn) Re tile AND one Im tile; grid axis 2 walks K in bk steps."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_re[...] = jnp.zeros_like(acc_re)
        acc_im[...] = jnp.zeros_like(acc_im)

    # ONE residency of the co-located (Re, Im) weight tile + ONE bit-plane
    # decomposition, shared by all four sub-MACs below.
    wr = wr_ref[...].astype(jnp.int32)                          # (bk, bn)
    wi = wi_ref[...].astype(jnp.int32)
    planes = lambda w: tuple(map(_bf16, fold_planes(w)))
    _accumulate(acc_re, acc_im, _sub_mac_sums(
        xr_ref[...].astype(jnp.int32), xi_ref[...].astype(jnp.int32),
        _bf16(wr), _bf16(wi), planes(wr), planes(wi)))

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        or_ref[...] = acc_re[...]
        oi_ref[...] = acc_im[...]


def _ccim_complex_kernel_prepacked(
    xr_ref, xi_ref, wr_ref, wi_ref, wr6_ref, wr5_ref, wi6_ref, wi5_ref,
    or_ref, oi_ref, acc_re, acc_im, *, n_k: int,
):
    """Prepacked-weight fused complex kernel: the co-located (Re, Im)
    weight tiles AND their folded MSB planes stream in as inputs (packed
    once per deployment), so per-step weight decomposition drops to zero;
    only the activations are decomposed in-kernel.  Bit-identical to
    ``_ccim_complex_kernel`` on the same integer operands."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_re[...] = jnp.zeros_like(acc_re)
        acc_im[...] = jnp.zeros_like(acc_im)

    tile = lambda r: _bf16(r[...].astype(jnp.int32))
    _accumulate(acc_re, acc_im, _sub_mac_sums(
        xr_ref[...].astype(jnp.int32), xi_ref[...].astype(jnp.int32),
        tile(wr_ref), tile(wi_ref),
        (tile(wr6_ref), tile(wr5_ref)), (tile(wi6_ref), tile(wi5_ref))))

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        or_ref[...] = acc_re[...]
        oi_ref[...] = acc_im[...]


def _ccim_complex_kernel_prepacked_skinny(
    xr_ref, xi_ref, wr_ref, wi_ref, planes_ref, or_ref, oi_ref,
    acc_re, acc_im, *, bk: int, n_k: int,
):
    """Decode-shaped fused complex variant: M padded once to the int8
    sublane width (32) instead of the 128-lane MXU block, and the four
    folded weight planes arrive STACKED as one full-K resident block per N
    tile (sliced in-kernel per k step), so only the co-located (Re, Im)
    weight tiles stream with k -- double-buffered by the Pallas pipeline.
    Bit-identical to ``_ccim_complex_kernel_prepacked``."""
    k_step = pl.program_id(1)

    @pl.when(k_step == 0)
    def _init():
        acc_re[...] = jnp.zeros_like(acc_re)
        acc_im[...] = jnp.zeros_like(acc_im)

    tile = lambda v: _bf16(v.astype(jnp.int32))
    sl = lambda i: tile(planes_ref[i, pl.ds(k_step * bk, bk), :])
    _accumulate(acc_re, acc_im, _sub_mac_sums(
        xr_ref[...].astype(jnp.int32), xi_ref[...].astype(jnp.int32),
        tile(wr_ref[...]), tile(wi_ref[...]),
        (sl(0), sl(1)), (sl(2), sl(3))))

    @pl.when(k_step == n_k - 1)
    def _done():
        or_ref[...] = acc_re[...]
        oi_ref[...] = acc_im[...]


@functools.partial(
    jax.jit, static_argnames=("bn", "bk", "interpret")
)
def ccim_complex_matmul_prepacked_skinny_pallas(
    x_re: jax.Array, x_im: jax.Array,     # (Mp, K) int8, Mp % 32 == 0
    w_re: jax.Array, w_im: jax.Array,     # (K, N) int8
    planes: jax.Array,                    # (4, K, N) int8: wr6, wr5, wi6, wi5
    *,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Skinny-M prepacked fused complex CIM GEMM -> (y_re, y_im) int32 at
    x2^11; bit-identical to ``ccim_complex_matmul_prepacked_pallas``."""
    Mp, K = x_re.shape
    K2, N = w_re.shape
    assert K == K2 and x_im.shape == (Mp, K) and w_im.shape == (K, N)
    assert planes.shape == (4, K, N), planes.shape
    assert Mp % SKINNY_SUBLANE == 0, Mp
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    assert bk % ACC_LEN == 0 and bk % SKINNY_SUBLANE == 0, bk

    n_k = K // bk
    kernel = functools.partial(_ccim_complex_kernel_prepacked_skinny,
                               bk=bk, n_k=n_k)
    x_spec = pl.BlockSpec((Mp, bk), lambda j, k: (0, k))
    w_spec = pl.BlockSpec((bk, bn), lambda j, k: (k, j))
    p_spec = pl.BlockSpec((4, K, bn), lambda j, k: (0, 0, j))   # resident
    o_spec = pl.BlockSpec((Mp, bn), lambda j, k: (0, j))
    return pl.pallas_call(
        kernel,
        grid=(N // bn, n_k),
        in_specs=[x_spec, x_spec, w_spec, w_spec, p_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, N), jnp.int32),
            jax.ShapeDtypeStruct((Mp, N), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Mp, bn), jnp.int32),
            pltpu.VMEM((Mp, bn), jnp.int32),
        ],
        interpret=interpret,
        name="ccim_complex_matmul_prepacked_skinny_pallas",
    )(x_re, x_im, w_re, w_im, planes)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def ccim_complex_matmul_prepacked_pallas(
    x_re: jax.Array, x_im: jax.Array,     # (M, K) int8
    w_re: jax.Array, w_im: jax.Array,     # (K, N) int8 -- one co-located copy
    wr_p6: jax.Array, wr_p5: jax.Array,   # (K, N) int8 folded Re planes
    wi_p6: jax.Array, wi_p5: jax.Array,   # (K, N) int8 folded Im planes
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Prepacked fused complex CIM GEMM -> (y_re, y_im) int32 at x2^11."""
    M, K = x_re.shape
    K2, N = w_re.shape
    assert K == K2
    assert x_im.shape == (M, K)
    for w in (w_im, wr_p6, wr_p5, wi_p6, wi_p5):
        assert w.shape == (K, N)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % ACC_LEN == 0
    n_k = K // bk

    kernel = functools.partial(_ccim_complex_kernel_prepacked, n_k=n_k)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[x_spec, x_spec] + [w_spec] * 6,
        out_specs=[o_spec, o_spec],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int32),
            jax.ShapeDtypeStruct((M, N), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.int32),
        ],
        interpret=interpret,
        name="ccim_complex_matmul_prepacked_pallas",
    )(x_re, x_im, w_re, w_im, wr_p6, wr_p5, wi_p6, wi_p5)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def ccim_complex_matmul_pallas(
    x_re: jax.Array,          # (M, K) int8, values in [-127, 127]
    x_im: jax.Array,          # (M, K) int8
    w_re: jax.Array,          # (K, N) int8 -- ONE co-located copy
    w_im: jax.Array,          # (K, N) int8
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused complex CIM GEMM -> (y_re, y_im), each (M, N) int32 at x2^11."""
    M, K = x_re.shape
    K2, N = w_re.shape
    assert K == K2
    assert x_im.shape == (M, K) and w_im.shape == (K, N)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    assert bk % ACC_LEN == 0
    n_k = K // bk

    kernel = functools.partial(_ccim_complex_kernel, n_k=n_k)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[x_spec, x_spec, w_spec, w_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.int32),
            jax.ShapeDtypeStruct((M, N), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((bm, bn), jnp.int32),
        ],
        interpret=interpret,
        name="ccim_complex_matmul_pallas",
    )(x_re, x_im, w_re, w_im)
