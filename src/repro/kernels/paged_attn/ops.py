"""Dispatcher for the paged-attention decode read path.

Same conventions as ccim_matmul.ops: ``use_pallas`` defaults to "am I on
a TPU backend", the Pallas kernel runs in interpret mode off-TPU (CI
covers it that way), and the XLA fallback is the pure-jnp gather oracle
in ref.py.  models.layers routes S==1 paged reads here only when the
kernel path is enabled (TPU, or REPRO_PAGED_ATTN=1 to force interpret
mode) -- on CPU the scheduler's bit-identity contract rides the fallback,
which is exactly plain decode attention over the gathered view.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from .kernel import paged_attention_pallas
from .ref import paged_attention_ref, paged_gather_kv  # noqa: F401


def kernel_enabled() -> bool:
    """Should models.layers route paged decode reads through the Pallas
    kernel?  Default: only on a real TPU backend.  REPRO_PAGED_ATTN=1
    forces it (interpret mode off-TPU, for end-to-end kernel testing);
    REPRO_PAGED_ATTN=0 disables it everywhere."""
    env = os.environ.get("REPRO_PAGED_ATTN", "auto")
    if env == "1":
        return True
    if env == "0":
        return False
    return jax.default_backend() == "tpu"


def paged_attention_decode(
    q: jax.Array,                 # (B, Hq, Dh)
    k_pool: jax.Array,            # (n_blocks, bs, Hkv, Dh)
    v_pool: jax.Array,            # (n_blocks, bs, Hkv, Dh)
    table: jax.Array,             # (B, n_tbl) int32
    lengths: jax.Array,           # (B,) int32 valid kv rows (incl. current)
    is_local=False,               # scalar bool (traced ok)
    *,
    softcap: Optional[float] = None,
    window: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """A row of length 0 is one the step does not decode: the kernel
    walks none of its blocks and returns zeros for it (the fallback
    returns an unmasked average); either way its output is unused."""
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = kernel_enabled()
    if not use_pallas:
        return paged_attention_ref(q, k_pool, v_pool, table, lengths,
                                   softcap=softcap, window=window,
                                   is_local=is_local)
    return paged_attention_pallas(
        q, k_pool, v_pool, table, lengths, is_local,
        softcap=softcap, window=window,
        interpret=(not on_tpu) if interpret is None else interpret)
