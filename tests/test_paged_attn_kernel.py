"""Paged-attention decode kernel (kernels/paged_attn/kernel.py) against
the gather oracle (ref.py), in Pallas interpret mode on the CPU.

The kernel walks only the blocks a row's length covers: rows of length
0 (slots the step does not decode) return zeros, and whatever the pool
holds past a row's length never reaches its output.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attn.kernel import paged_attention_pallas
from repro.kernels.paged_attn.ref import paged_attention_ref

BS, N_TBL, DH = 16, 4, 128
LENGTHS = (0, 1, 15, 16, 17, N_TBL * BS)


def _problem(hq, hkv, seed=0):
    """One row per entry of LENGTHS, each with its own pool blocks
    (block 0, the trash block, belongs to no row)."""
    B = len(LENGTHS)
    n_blocks = 1 + B * N_TBL
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, hq, DH), jnp.float32)
    k_pool = jax.random.normal(kk, (n_blocks, BS, hkv, DH)).astype(jnp.bfloat16)
    v_pool = jax.random.normal(kv, (n_blocks, BS, hkv, DH)).astype(jnp.bfloat16)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_blocks))
    table = perm.reshape(B, N_TBL).astype(np.int32)
    table[0] = 0                    # a length-0 row parks on the trash block
    return (q, k_pool, v_pool, jnp.asarray(table),
            jnp.asarray(LENGTHS, jnp.int32))


def _kernel(q, k_pool, v_pool, table, lengths, is_local=False, **kw):
    return paged_attention_pallas(q, k_pool, v_pool, table, lengths,
                                  is_local, interpret=True, **kw)


@pytest.mark.parametrize("local", [False, True], ids=["global", "window8"])
@pytest.mark.parametrize("softcap", [None, 30.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa"])
def test_kernel_matches_ref(hq, hkv, softcap, local):
    q, k_pool, v_pool, table, lengths = _problem(hq, hkv)
    kw = dict(softcap=softcap, window=8 if local else None)
    got = np.asarray(_kernel(q, k_pool, v_pool, table, lengths, local, **kw))
    want = np.asarray(paged_attention_ref(q, k_pool, v_pool, table, lengths,
                                          is_local=local, **kw))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-2, atol=2e-2)
    assert (got[~live] == 0).all()


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa"])
def test_blocks_past_length_never_reach_the_output(hq, hkv):
    """NaN in every pool block past each row's length (and in every
    block no row owns): live rows stay bit-equal to their clean-pool
    outputs, rows of length 0 return exact zeros."""
    q, k_pool, v_pool, table, lengths = _problem(hq, hkv, seed=1)
    clean = np.asarray(_kernel(q, k_pool, v_pool, table, lengths))

    n_blocks = k_pool.shape[0]
    poison = np.ones(n_blocks, bool)
    for row, n in zip(np.asarray(table), LENGTHS):
        for j, blk in enumerate(row):
            poison[blk] = j * BS >= n
    bad = jnp.asarray(poison)[:, None, None, None]
    k_nan = jnp.where(bad, jnp.nan, k_pool).astype(k_pool.dtype)
    v_nan = jnp.where(bad, jnp.nan, v_pool).astype(v_pool.dtype)
    got = np.asarray(_kernel(q, k_nan, v_nan, table, lengths))

    live = np.asarray(lengths) > 0
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[live], clean[live])
    assert (got[~live] == 0).all() and (clean[~live] == 0).all()
