"""Random weights from the seed, in the tree the program's model takes.

The benchmark makes the weights itself, so that the plain reference
(``reference.py``) can make the very same ones again after the program's
state is freed, and takes nothing the program made.  The tree has the
keys, shapes and dtypes of the program's raw (unpacked) parameters: the
program packs it for the CIM macro, the reference quantizes it itself.

One jitted call makes the whole tree on the device, in the dtype it is
served in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import work

QK_GAIN = 2.0
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one above 2**32."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _dense(key, d_in, d_out, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) / math.sqrt(d_in)
    return w.astype(dtype)


def _gain(key, n, dtype):
    """Norm gain offset: the program scales by (1 + w)."""
    return (0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)


def _attn_mlp_layer(key, m, dtype):
    d, dh, h, hp, f = (m["d_model"], m["d_head"], m["n_heads"],
                       work.heads(m, padded=True), m["d_ff"])
    ks = jax.random.split(key, 9)
    # pad heads hold zero weights, as the program's own init leaves them
    live = (jnp.arange(hp * dh) < h * dh).astype(jnp.float32)

    def proj(k, d_in, d_out):
        w = jax.random.normal(k, (d_in, d_out), jnp.float32) / math.sqrt(d_in)
        return w

    # q and k at QK_GAIN times the 1/sqrt(d) scale: attention scores with
    # a standard deviation of QK_GAIN**2 make attention peaked, so served
    # tokens depend on their context and position, as a trained model's do
    attn = {
        "wq": (QK_GAIN * proj(ks[0], d, hp * dh) * live[None, :]).astype(dtype),
        "wk": (QK_GAIN * proj(ks[1], d, hp * dh) * live[None, :]).astype(dtype),
        "wv": (proj(ks[2], d, hp * dh) * live[None, :]).astype(dtype),
        "wo": (jax.random.normal(ks[3], (hp * dh, d), jnp.float32)
               / math.sqrt(h * dh) * live[:, None]).astype(dtype),
    }
    mlp = {"w1": _dense(ks[4], d, f, dtype), "w3": _dense(ks[5], d, f, dtype),
           "w2": _dense(ks[6], f, d, dtype)}
    return {"attn": attn, "mlp": mlp, "ln1": _gain(ks[7], d, dtype),
            "ln2": _gain(ks[8], d, dtype)}


def _mamba_layer(key, m, dtype):
    d = m["d_model"]
    di = m.get("ssm_expand", 2) * d
    n, p, w = m["ssm_state"], m.get("ssm_head_dim", 64), m.get("ssm_conv_width", 4)
    h = di // p
    ks = jax.random.split(key, 12)
    mamba = {
        "w_z": _dense(ks[0], d, di, dtype),
        "w_x": _dense(ks[1], d, di, dtype),
        "w_bc": _dense(ks[2], d, 2 * n, dtype),
        "w_dt": _dense(ks[3], d, h, dtype),
        "conv_x": (jax.random.normal(ks[4], (w, di), jnp.float32) / w).astype(dtype),
        "conv_b": (jax.random.normal(ks[5], (w, 2 * n), jnp.float32) / w).astype(dtype),
        "conv_bias_x": _gain(ks[6], di, dtype),
        "conv_bias_b": _gain(ks[7], 2 * n, dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "D_skip": 1.0 + 0.1 * jax.random.normal(ks[8], (h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            ks[9], (h,), jnp.float32, math.log(1e-3), math.log(1e-1))))),
        "gate_norm": _gain(ks[10], di, dtype),
        "out_proj": _dense(ks[11], di, d, dtype),
    }
    return {"mamba": mamba, "ln1": _gain(jax.random.fold_in(key, 99), d, dtype)}


@functools.partial(jax.jit, static_argnames=("model_items",))
def _make(key, model_items):
    m = dict(model_items)
    dtype = jnp.dtype(m.get("dtype", "bfloat16"))
    k_emb, k_layers, k_head, k_norm = jax.random.split(key, 4)
    layer = _mamba_layer if m["family"] == "ssm" else _attn_mlp_layer
    keys = jax.random.split(k_layers, m["n_layers"])
    params = {
        "embed": (EMBED_STD * jax.random.normal(
            k_emb, (m["vocab_size"], m["d_model"]), jnp.float32)).astype(dtype),
        "layers": jax.vmap(lambda k: layer(k, m, dtype))(keys),
        "ln_f": _gain(k_norm, m["d_model"], dtype),
    }
    if not m.get("tie_embeddings", False):
        params["lm_head"] = _dense(k_head, m["d_model"], m["vocab_size"], dtype)
    return params


def make_params(model: dict, seed: int):
    """The raw parameter tree for ``model`` (a configuration file's
    ``model`` section), made on the default device from ``seed``."""
    if model["family"] not in ("dense", "ssm") or (
            model["family"] == "dense"
            and model["n_kv_heads"] != model["n_heads"]):
        raise ValueError("weights are made for mamba2 and MHA dense models")
    return _make(seed_key(seed), tuple(sorted(model.items())))
