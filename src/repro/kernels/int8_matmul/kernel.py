"""Pallas TPU kernel: W8A8 integer GEMM with fused per-channel dequant.

The all-digital CIM baseline [11] (and the framework's generic quantized
linear): y = (x_q @ w_q) * sx[m] * sw[n], int8 x int8 -> int32 on the MXU,
dequant fused into the epilogue so the int32 accumulator never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        sx = sx_ref[...]                     # (bm, 1) float32
        sw = sw_ref[...]                     # (1, bn) float32
        o_ref[...] = acc_ref[...].astype(jnp.float32) * sx * sw


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul_pallas(
    x_q: jax.Array,    # (M, K) int8
    w_q: jax.Array,    # (K, N) int8
    sx: jax.Array,     # (M, 1) float32 per-row scale
    sw: jax.Array,     # (1, N) float32 per-col scale
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = x_q.shape
    _, N = w_q.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk
    kernel = functools.partial(_int8_kernel, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int8_matmul_pallas",
    )(x_q, w_q, sx, sw)
