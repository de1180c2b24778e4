"""Compile the serving path's Pallas kernels for a TPU v5e at real widths.

No chip is needed: the TPU compiler that ships with JAX compiles for a
described ``v5e:2x2`` topology, and Mosaic refuses here exactly what it
would refuse on the chip (unsupported in-kernel layouts, unaligned
slices, VMEM overruns).  Interpret mode checks none of that, so these
tests guard every kernel change at zero chip time.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every pytest worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ccim_complex.ops import ccim_complex_matmul_int_prepacked
from repro.kernels.ccim_matmul.ops import (ccim_matmul_int_prepacked,
                                           pick_weight_blocks)
from repro.kernels.paged_attn.kernel import paged_attention_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _prepacked(M, K, N, one_chip):
    _, _, Np, Kp = pick_weight_blocks(K, N)
    fn = lambda x, w, p: ccim_matmul_int_prepacked(
        x, w, p, k_dim=K, n_dim=N, use_pallas=True, interpret=False)
    return _compile(fn, one_chip, ((M, K), jnp.int32), ((Kp, Np), jnp.int8),
                    ((2, Kp, Np), jnp.int8))


def test_skinny_prepacked_mamba2_in_proj(one_chip, no_persistent_cache):
    """Decode route (M <= 32): mamba2-130m's fused input projection."""
    _prepacked(4, 768, 3352, one_chip)


def test_general_prepacked_minicpm_qkv(one_chip, no_persistent_cache):
    """Prefill route (M = 128): minicpm-2b's fused QKV, 48 padded heads."""
    _prepacked(128, 2304, 9216, one_chip)


def test_paged_attention_minicpm(one_chip, no_persistent_cache):
    """Paged decode read as the minicpm-2b cells run it: 48 (padded) MHA
    heads of 64, 16-token blocks, 16 slots of 112 table entries over the
    default pool, rows at mixed lengths (0: a slot the step does not
    decode, up to the full table)."""
    B, H, Dh, bs, n_tbl = 16, 48, 64, 16, 112
    n_blocks = B * n_tbl + 1
    lens = jnp.asarray([0, 1, 15, 16, 17, 1024, 1100, n_tbl * bs] * 2,
                       jnp.int32)
    fn = lambda q, k, v, t: paged_attention_pallas(q, k, v, t, lens, False)
    _compile(fn, one_chip, ((B, H, Dh), jnp.bfloat16),
             ((n_blocks, bs, H, Dh), jnp.bfloat16),
             ((n_blocks, bs, H, Dh), jnp.bfloat16),
             ((B, n_tbl), jnp.int32))


@pytest.mark.parametrize("M", [4, 128])
def test_prepacked_complex(M, one_chip, no_persistent_cache):
    """Fused complex kernel, skinny (M=4) and general (M=128) routes."""
    K, N = 512, 512
    _, _, Np, Kp = pick_weight_blocks(K, N)
    fn = lambda xr, xi, wr, wi, a, b, c, d: ccim_complex_matmul_int_prepacked(
        xr, xi, wr, wi, a, b, c, d, k_dim=K, n_dim=N, use_pallas=True,
        interpret=False)
    _compile(fn, one_chip, ((M, K), jnp.int32), ((M, K), jnp.int32),
             *[((Kp, Np), jnp.int8)] * 6)
