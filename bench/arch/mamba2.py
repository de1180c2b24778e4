"""mamba2: a uniform stack of Mamba-2 (SSD) mixer layers, attention-free.

Each layer: RMS norm with gain (1 + w); four input projections (z, x,
B and C together, dt) on the macro; two causal depthwise convolutions
with SiLU (x, and B with C); the selective-state recurrence run one step
at a time, with a skip D and the SiLU gate of z; the gated RMS norm; the
output projection on the macro; the residual add.

The configuration's ``model`` keys: ``d_model``, ``ssm_state``,
``ssm_expand`` (d_inner = expand * d_model), ``ssm_head_dim``,
``ssm_conv_width``, ``norm_eps``, ``n_layers``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference
import weights
from reference import HI


# ---- weights ----------------------------------------------------------------

def _layer_params(key, m, dtype):
    d = m["d_model"]
    di = m.get("ssm_expand", 2) * d
    n, p, w = m["ssm_state"], m.get("ssm_head_dim", 64), m.get("ssm_conv_width", 4)
    h = di // p
    ks = jax.random.split(key, 12)
    dense, gain = weights.dense, weights.gain
    mamba = {
        "w_z": dense(ks[0], d, di, dtype),
        "w_x": dense(ks[1], d, di, dtype),
        "w_bc": dense(ks[2], d, 2 * n, dtype),
        "w_dt": dense(ks[3], d, h, dtype),
        "conv_x": (jax.random.normal(ks[4], (w, di), jnp.float32) / w).astype(dtype),
        "conv_b": (jax.random.normal(ks[5], (w, 2 * n), jnp.float32) / w).astype(dtype),
        "conv_bias_x": gain(ks[6], di, dtype),
        "conv_bias_b": gain(ks[7], 2 * n, dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "D_skip": 1.0 + 0.1 * jax.random.normal(ks[8], (h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            ks[9], (h,), jnp.float32, math.log(1e-3), math.log(1e-1))))),
        "gate_norm": gain(ks[10], di, dtype),
        "out_proj": dense(ks[11], di, d, dtype),
    }
    return {"mamba": mamba, "ln1": gain(jax.random.fold_in(key, 99), d, dtype)}


def make_layers(key, m, dtype):
    return weights.uniform_layers(_layer_params, key, m, dtype)


# ---- reference --------------------------------------------------------------

def _causal_conv(u, w, b):
    W = w.shape[0]
    up = jnp.pad(u, ((W - 1, 0), (0, 0)))
    S = u.shape[0]
    out = sum(up[i:i + S] * w[i].astype(jnp.float32) for i in range(W))
    return jax.nn.silu(out + b.astype(jnp.float32))


def _layer(x, p, m, r, lin):
    S = x.shape[0]
    mb = p["mamba"]
    n, P, eps = m["ssm_state"], m["ssm_head_dim"], m["norm_eps"]
    rms_norm = reference.rms_norm
    h = r(rms_norm(x, p["ln1"], eps))
    z = r(lin(h, mb["w_z"]))
    xc = r(lin(h, mb["w_x"]))
    bc = r(lin(h, mb["w_bc"]))
    dtr = r(lin(h, mb["w_dt"]))
    xc = r(_causal_conv(xc, mb["conv_x"], mb["conv_bias_x"]))
    bc = r(_causal_conv(bc, mb["conv_b"], mb["conv_bias_b"]))
    B, C = bc[:, :n], bc[:, n:]
    H = xc.shape[1] // P
    xh = xc.reshape(S, H, P)
    dt = jax.nn.softplus(dtr + mb["dt_bias"])                      # (S, H)
    A = -jnp.exp(mb["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        y_t = jnp.einsum("hpn,n->hp", state, c_t, precision=HI)
        return state, y_t + mb["D_skip"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, n), jnp.float32),
                        (xh, B, C, dt))
    y = r(r(y.reshape(S, H * P)) * jax.nn.silu(z))
    y = r(rms_norm(y, mb["gate_norm"], eps))
    return r(x + r(lin(y, mb["out_proj"])))


def forward(x, layers, m, r, lin):
    return reference.uniform_stack(_layer, x, layers, m, r, lin)


# ---- work counts ------------------------------------------------------------

def _inner(m):
    di = m.get("ssm_expand", 2) * m["d_model"]
    return di, m["ssm_state"], m.get("ssm_head_dim", 64)


def cim_sites(m, padded=False):
    """The four input projections fused as the program fuses them, and
    the output projection, in every layer."""
    d = m["d_model"]
    di, n, p = _inner(m)
    layer = [("in_proj", d, 2 * di + 2 * n + di // p, 1.0),
             ("out_proj", di, d, 1.0)]
    return layer * m["n_layers"]


def kv_bytes_per_token(m, padded=False):
    return 0


def attn_ops_rows(m, first, last, padded=False):
    return 0


def state_ops(m, rows):
    """The recurrence, 5*H*P*N a row (decay, input outer product, add;
    output contraction), and the two convolutions, 2*W*(d_inner + 2N)."""
    di, n, p = _inner(m)
    w = m.get("ssm_conv_width", 4)
    per = 5 * (di // p) * p * n + 2 * w * (di + 2 * n)
    return m["n_layers"] * per * rows


def prompt_multiple(m, serving):
    """The program prefills recurrent state in whole chunks."""
    return serving["prefill_chunk"]
