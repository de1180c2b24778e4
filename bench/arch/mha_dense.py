"""mha_dense: a uniform stack of pre-norm transformer layers with
multi-head attention (as many KV heads as query heads) and a SwiGLU MLP.

Each layer: RMS norm with gain (1 + w); Q, K and V projections on the
macro; RoPE on the two halves of each head; causal softmax attention
over the published (unpadded) heads; the output projection on the
macro; the residual add; RMS norm; gate and up projections on the
macro, SiLU(gate) * up, the down projection on the macro; the residual
add.

The program pads the heads to a multiple of ``tp_head_pad``: the pad
heads hold zero weights, as its own init leaves them, and the reference
reads only the published heads.  Grouped KV heads are refused.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference
import weights
import work
from reference import HI


def _check(m):
    if m["n_kv_heads"] != m["n_heads"]:
        raise ValueError("mha_dense takes as many KV heads as query heads")


# ---- weights ----------------------------------------------------------------

def _layer_params(key, m, dtype):
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
    qk = weights.QK_GAIN
    attn = {
        "wq": (qk * proj(ks[0], d, hp * dh) * live[None, :]).astype(dtype),
        "wk": (qk * proj(ks[1], d, hp * dh) * live[None, :]).astype(dtype),
        "wv": (proj(ks[2], d, hp * dh) * live[None, :]).astype(dtype),
        "wo": (jax.random.normal(ks[3], (hp * dh, d), jnp.float32)
               / math.sqrt(h * dh) * live[:, None]).astype(dtype),
    }
    dense, gain = weights.dense, weights.gain
    mlp = {"w1": dense(ks[4], d, f, dtype), "w3": dense(ks[5], d, f, dtype),
           "w2": dense(ks[6], f, d, dtype)}
    return {"attn": attn, "mlp": mlp, "ln1": gain(ks[7], d, dtype),
            "ln2": gain(ks[8], d, dtype)}


def make_layers(key, m, dtype):
    _check(m)
    return weights.uniform_layers(_layer_params, key, m, dtype)


# ---- reference --------------------------------------------------------------

def _layer(x, p, m, r, lin):
    S = x.shape[0]
    H, dh, eps = m["n_heads"], m["d_head"], m["norm_eps"]
    rms_norm, rope = reference.rms_norm, reference.rope
    a = p["attn"]
    h = r(rms_norm(x, p["ln1"], eps))
    q = r(lin(h, a["wq"][:, :H * dh])).reshape(S, H, dh)
    k = r(lin(h, a["wk"][:, :H * dh])).reshape(S, H, dh)
    v = r(lin(h, a["wv"][:, :H * dh])).reshape(S, H, dh)
    q = r(rope(q, m["rope_theta"]))
    k = r(rope(k, m["rope_theta"]))
    s = jnp.einsum("qhd,khd->hqk", q * dh ** -0.5, k, precision=HI)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    o = r(o).reshape(S, H * dh)
    x = r(x + r(lin(o, a["wo"][:H * dh])))
    h = r(rms_norm(x, p["ln2"], eps))
    g = r(lin(h, p["mlp"]["w1"]))
    u = r(lin(h, p["mlp"]["w3"]))
    f = r(jax.nn.silu(g) * u)
    return r(x + r(lin(f, p["mlp"]["w2"])))


def forward(x, layers, m, r, lin):
    return reference.uniform_stack(_layer, x, layers, m, r, lin)


# ---- work counts ------------------------------------------------------------

def cim_sites(m, padded=False):
    """QKV and gate/up fused as the program fuses them, wo and down, in
    every layer; ``padded`` counts the program's padded heads."""
    _check(m)
    d, f = m["d_model"], m["d_ff"]
    hd = work.heads(m, padded) * m["d_head"]
    layer = [("qkv", d, 3 * hd, 1.0), ("wo", hd, d, 1.0),
             ("gate_up", d, 2 * f, 1.0), ("down", f, d, 1.0)]
    return layer * m["n_layers"]


def kv_bytes_per_token(m, padded=False):
    """K and V of every layer and head, in bf16."""
    return m["n_layers"] * work.heads(m, padded) * m["d_head"] * 4


def attn_ops_rows(m, first, last, padded=False):
    """QK and PV of rows at positions first..last: 4*H*dh a context
    position a layer."""
    if last < first:
        return 0
    ctx = (last + 1) * (last + 2) // 2 - first * (first + 1) // 2
    return 4 * m["n_layers"] * work.heads(m, padded) * m["d_head"] * ctx


def state_ops(m, rows):
    return 0


def prompt_multiple(m, serving):
    return 1
