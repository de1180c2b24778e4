"""Random weights from the seed, in the tree the program's model takes.

The benchmark makes the weights itself, so that the plain reference
(``reference.py``) can make the very same ones again after the program's
state is freed, and takes nothing the program made.  The tree has the
keys, shapes and dtypes of the program's raw (unpacked) parameters: the
program packs it for the CIM macro, the reference quantizes it itself.

This module makes the embedding, the final norm and the LM head; the
configuration's architecture (``arch/<name>.py``) makes the layers,
``params["layers"]``, from the key it is handed, with the helpers here.
One jitted call makes the whole tree on the device, in the dtype it is
served in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QK_GAIN = 2.0
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one above 2**32."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def dense(key, d_in, d_out, dtype):
    """A projection, N(0, 1/d_in)."""
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) / math.sqrt(d_in)
    return w.astype(dtype)


def gain(key, n, dtype):
    """Norm gain offset: the program scales by (1 + w)."""
    return (0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)


def uniform_layers(layer, key, m, dtype):
    """``n_layers`` layers of one kind, stacked on a leading axis:
    ``layer(key, m, dtype)`` vmapped over ``n_layers`` keys split from
    ``key``."""
    keys = jax.random.split(key, m["n_layers"])
    return jax.vmap(lambda k: layer(k, m, dtype))(keys)


@functools.partial(jax.jit,
                   static_argnames=("arch", "model_items"))
def _make(key, arch, model_items):
    m = dict(model_items)
    dtype = jnp.dtype(m.get("dtype", "bfloat16"))
    k_emb, k_layers, k_head, k_norm = jax.random.split(key, 4)
    params = {
        "embed": (EMBED_STD * jax.random.normal(
            k_emb, (m["vocab_size"], m["d_model"]), jnp.float32)).astype(dtype),
        "layers": arch.make_layers(k_layers, m, dtype),
        "ln_f": gain(k_norm, m["d_model"], dtype),
    }
    if not m.get("tie_embeddings", False):
        params["lm_head"] = dense(k_head, m["d_model"], m["vocab_size"], dtype)
    return params


def make_params(arch, model: dict, seed: int):
    """The raw parameter tree for ``model`` (a configuration file's
    ``model`` section) of architecture ``arch`` (its module), made on the
    default device from ``seed``."""
    return _make(seed_key(seed), arch, tuple(sorted(model.items())))
