"""Pallas TPU kernel: paged-attention decode read (block-table gather
fused into the attention dot).

One grid step per (batch row, table entry): the scalar-prefetched block
table drives the k/v BlockSpec index maps, so each step DMAs exactly the
(bs, Hkv, Dh) pool block the row's table points at -- the gather never
materializes a dense (B, L, Hkv, Dh) view in HBM, which is the entire
point of the kernel (the XLA fallback in ref.py pays that gather).  The
inner loop is a standard online-softmax accumulation over the row's
blocks (grid axis 1 is innermost, so VMEM scratch carries m/l/acc across
a row's blocks exactly like the flash scan in models.layers).

Only the blocks a row's length covers are walked: the k/v index maps
clamp table entry j to the row's last needed entry, so the steps past it
keep their block index and the pipeline issues no DMA for them, and the
score/softmax/PV body runs under ``pl.when(j * bs < len)``.  A fully
masked block would leave m/l/acc bit-for-bit unchanged (m_new = m_prev,
corr = 1, p = 0), so skipping it changes no live row; a row of length 0
(a slot the step does not decode) writes zeros.  Blocks past a row's
length never reach its output, whatever they hold.

Skinny-M by construction: decode is M=1 per row, so the query block is a
single (Hq, Dh) tile resident in VMEM for the row's whole block walk.
VMEM working set per step = bs*Hkv*Dh*2 (k+v) + Hq*Dh bytes -- a few KiB
at serving shapes, far under budget; block_size and Dh should be lane
(128) / sublane multiples on real hardware (interpret mode, which CI
exercises, does not care).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(table_ref, len_ref, loc_ref,      # scalar prefetch
                       q_ref, k_ref, v_ref, o_ref,       # blocks
                       m_ref, l_ref, acc_ref,            # VMEM scratch
                       *, bs: int, n_tbl: int, hkv: int, g: int,
                       softcap: Optional[float], window: Optional[int]):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dh = q_ref.shape[-1]

    @pl.when(j * bs < len_ref[b])
    def _block():
        qg = (q_ref[0].astype(jnp.float32).reshape(hkv, g, dh)) * (dh ** -0.5)
        k = k_ref[0].astype(jnp.float32)                 # (bs, Hkv, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.einsum("hgd,khd->hgk", qg, k,
                       preferred_element_type=jnp.float32)  # (Hkv, G, bs)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        k_pos = j * bs + jax.lax.iota(jnp.int32, bs)
        q_pos = len_ref[b] - 1
        msk = k_pos <= q_pos
        if window is not None:
            msk_local = msk & (q_pos - k_pos < window)
            msk = jnp.where(loc_ref[0] != 0, msk_local, msk)
        s = jnp.where(msk[None, None, :], s, NEG_INF)

        m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_prev * corr[..., None] + jnp.einsum(
            "hgk,khd->hgd", p, v, preferred_element_type=jnp.float32)

    @pl.when(j == n_tbl - 1)
    def _done():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(hkv * g, dh).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "window", "interpret"))
def paged_attention_pallas(
    q: jax.Array,                 # (B, Hq, Dh)
    k_pool: jax.Array,            # (n_blocks, bs, Hkv, Dh)
    v_pool: jax.Array,            # (n_blocks, bs, Hkv, Dh)
    table: jax.Array,             # (B, n_tbl) int32
    lengths: jax.Array,           # (B,) int32
    is_local: jax.Array,          # () bool/int (traced ok)
    *,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused block-table-gather decode attention -> (B, Hq, Dh)."""
    B, Hq, Dh = q.shape
    _, bs, Hkv, Dh2 = k_pool.shape
    assert Dh == Dh2 and Hq % Hkv == 0, (q.shape, k_pool.shape)
    n_tbl = table.shape[1]
    G = Hq // Hkv

    kernel = functools.partial(
        _paged_attn_kernel, bs=bs, n_tbl=n_tbl, hkv=Hkv, g=G,
        softcap=softcap, window=window)

    def kv_block(b, j, tbl, lens, loc):
        # past the row's last needed entry, repeat it: no new DMA
        last = jnp.maximum((lens[b] + bs - 1) // bs - 1, 0)
        return (tbl[b, jnp.minimum(j, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_tbl),
        in_specs=[
            pl.BlockSpec((1, Hq, Dh), lambda b, j, tbl, lens, loc: (b, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Dh), kv_block),
            pl.BlockSpec((1, bs, Hkv, Dh), kv_block),
        ],
        out_specs=pl.BlockSpec((1, Hq, Dh),
                               lambda b, j, tbl, lens, loc: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dh), q.dtype),
        interpret=interpret,
        name="paged_attention_pallas",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(is_local, jnp.int32).reshape(1), q, k_pool, v_pool)
