"""nemotron_h: Nemotron-H's hybrid stack, one pre-norm residual block
x + mixer(rms_norm(x)) per character of the configuration's
``layer_types`` (the published ``hybrid_override_pattern``):

* "M", Mamba-2: the z, x, B and C, dt projections on the macro; causal
  depthwise convolutions with bias and SiLU over x and over B with C;
  the selective-state recurrence with ``ssm_n_groups`` groups of B and C
  (group g serves heads g*H/G .. (g+1)*H/G - 1), dt = softplus(dt +
  dt_bias), A = -exp(A_log) and the skip D; y * SiLU(z) through an RMS
  norm over each group's d_inner/G channels (mamba_ssm's RMSNormGated);
  the output projection on the macro.
* "E", mixture of experts: a float32 router over ``moe_router_experts``
  experts at full precision; scores sigmoid(logits); the top ``top_k``
  of scores + ``e_bias`` chosen, their plain scores normalised to sum 1
  and scaled by ``moe_routed_scaling``; this chip's ``n_experts`` held
  experts from ``moe_first_expert`` on, each down(relu(up(x))^2) on the
  macro, weighted by its gate (0 for a row that did not choose it); and
  the shared expert of the same form, added.
* "*", attention: Q, K and V on the macro, grouped KV heads (query head
  i reads KV head i // (n_heads / n_kv_heads)), no rotary embedding,
  causal softmax, the output projection on the macro.

Each kind's layers are stacked on their own leading axis under its
character in ``params["layers"]``; a layer is found at its rank among
the layers of its kind, as the program keeps them.  Each E layer's
``e_bias`` (the published ``e_score_correction_bias``) is fit, as the
published load balancing would leave it, on a seeded calibration
sequence, so that every one of the router's experts takes its share of
the rows whatever the seed.

The configuration's ``model`` keys: ``d_model``, ``layer_types``,
``ssm_state``, ``ssm_n_heads``, ``ssm_head_dim``, ``ssm_n_groups``,
``ssm_conv_width``, ``n_heads``, ``n_kv_heads``, ``d_head``,
``n_experts`` (held), ``moe_router_experts``, ``moe_first_expert``,
``top_k``, ``moe_d_ff``, ``shared_expert_d_ff``, ``moe_routed_scaling``,
``norm_eps``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import reference
import weights
from reference import HI

KINDS = ("M", "E", "*")
# the published initialisation (rescale_prenorm_residual) divides the
# residual branches' output projections by sqrt(num_hidden_layers), 52
RESCALE = 1.0 / math.sqrt(52)
# the router's choice bias is fit on a calibration sequence of this many
# rows, in this many steps of the balancing update
CALIB_ROWS = 512
BALANCE_STEPS = 256


def _ssm(m):
    """(d_inner, heads, head dim, state, groups, conv width)."""
    p = m["ssm_head_dim"]
    return (m["ssm_n_heads"] * p, m["ssm_n_heads"], p, m["ssm_state"],
            m["ssm_n_groups"], m["ssm_conv_width"])


# ---- weights ----------------------------------------------------------------

def _mamba_params(key, m, dtype):
    d = m["d_model"]
    di, h, _, n, g, w = _ssm(m)
    bc = 2 * g * n
    ks = jax.random.split(key, 13)
    dense, gain = weights.dense, weights.gain
    mamba = {
        "w_z": dense(ks[0], d, di, dtype),
        "w_x": dense(ks[1], d, di, dtype),
        "w_bc": dense(ks[2], d, bc, dtype),
        "w_dt": dense(ks[3], d, h, dtype),
        "conv_x": (jax.random.normal(ks[4], (w, di), jnp.float32) / w).astype(dtype),
        "conv_b": (jax.random.normal(ks[5], (w, bc), jnp.float32) / w).astype(dtype),
        "conv_bias_x": gain(ks[6], di, dtype),
        "conv_bias_b": gain(ks[7], bc, dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "D_skip": 1.0 + 0.1 * jax.random.normal(ks[8], (h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            ks[9], (h,), jnp.float32, math.log(1e-3), math.log(1e-1))))),
        "gate_norm": gain(ks[10], di, dtype),
        "out_proj": (jax.random.normal(ks[11], (di, d), jnp.float32)
                     * RESCALE / math.sqrt(di)).astype(dtype),
    }
    return {"mamba": mamba, "ln1": gain(ks[12], d, dtype)}


def _mlp_params(key, d, f, dtype):
    ku, kd = jax.random.split(key)
    return {"w_up": weights.dense(ku, d, f, dtype),
            "w_down": weights.dense(kd, f, d, dtype)}


def _moe_params(key, m, dtype):
    d, r = m["d_model"], m["moe_router_experts"]
    ks = jax.random.split(key, 5)
    experts = jax.vmap(lambda k: _mlp_params(k, d, m["moe_d_ff"], dtype))(
        jax.random.split(ks[0], m["n_experts"]))
    moe = {"router": jax.random.normal(ks[1], (d, r), jnp.float32)
                     / math.sqrt(d),
           "e_bias": jnp.zeros((r,), jnp.float32),     # fit in make_layers
           "experts": experts,
           "shared": _mlp_params(ks[3], d, m["shared_expert_d_ff"], dtype)}
    return {"moe": moe, "ln1": weights.gain(ks[4], d, dtype)}


def _attn_params(key, m, dtype):
    d, dh = m["d_model"], m["d_head"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    ks = jax.random.split(key, 5)
    # q and k at QK_GAIN times the 1/sqrt(d) scale: peaked attention, so
    # served tokens depend on their context, as a trained model's do
    qk = weights.QK_GAIN
    dense = weights.dense
    attn = {"wq": (qk * dense(ks[0], d, hq * dh, jnp.float32)).astype(dtype),
            "wk": (qk * dense(ks[1], d, hkv * dh, jnp.float32)).astype(dtype),
            "wv": dense(ks[2], d, hkv * dh, dtype),
            "wo": dense(ks[3], hq * dh, d, dtype)}
    return {"attn": attn, "ln1": weights.gain(ks[4], d, dtype)}


_PARAMS = {"M": _mamba_params, "E": _moe_params, "*": _attn_params}


def make_layers(key, m, dtype):
    out = {}
    keys = jax.random.split(key, len(KINDS) + 1)
    for kind, k in zip(KINDS, keys):
        n = m["layer_types"].count(kind)
        if n:
            out[kind] = jax.vmap(lambda kk: _PARAMS[kind](kk, m, dtype))(
                jax.random.split(k, n))
    if "E" in out:
        out["E"]["moe"]["e_bias"] = _balanced_bias(keys[-1], out, m)
    return out


def _fit_bias(scores, k):
    """A choice bias under which each expert is among the top k of
    ``scores + bias`` in about k/E of the rows of scores (R, E): the
    published aux-loss-free balancing update (raise the bias of an expert
    chosen less often than its share, lower it otherwise), its step
    shrinking to rest on this one batch."""
    R, E = scores.shape
    share = R * k / E

    def step(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        rate = 0.01 * (1.0 - i / BALANCE_STEPS) + 1e-4
        return b + rate * jnp.sign(share - load)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((E,), jnp.float32))


def _balanced_bias(key, layers, m):
    """Each E layer's ``e_score_correction_bias`` (the stack (n_E, E)),
    as the published balancing would leave it: fit on the router scores
    of a calibration sequence of embedding-like rows run through the
    layers before it, in float32 with plain float projections."""
    def lin(a, w):
        return jnp.dot(a, w.astype(jnp.float32), precision=HI)

    def ident(t):
        return t

    x = (weights.EMBED_STD * jax.random.normal(
        key, (CALIB_ROWS, m["d_model"]), jnp.float32))
    rank, biases = dict.fromkeys(KINDS, 0), []
    for kind in m["layer_types"]:
        p = jax.tree.map(lambda v: v[rank[kind]], layers[kind])
        rank[kind] += 1
        if kind == "E":
            h = reference.rms_norm(x, p["ln1"], m["norm_eps"])
            scores = jax.nn.sigmoid(jnp.dot(h, p["moe"]["router"],
                                            precision=HI))
            biases.append(_fit_bias(scores, m["top_k"]))
            p = dict(p, moe=dict(p["moe"], e_bias=biases[-1]))
        x = _LAYER[kind](x, p, m, ident, lin)
    return jnp.stack(biases)


# ---- reference --------------------------------------------------------------

def _causal_conv(u, w, b):
    W = w.shape[0]
    up = jnp.pad(u, ((W - 1, 0), (0, 0)))
    S = u.shape[0]
    out = sum(up[i:i + S] * w[i].astype(jnp.float32) for i in range(W))
    return jax.nn.silu(out + b.astype(jnp.float32))


def _mamba(x, p, m, r, lin):
    S = x.shape[0]
    mb, eps = p["mamba"], m["norm_eps"]
    di, H, P, n, G, _ = _ssm(m)
    rms_norm = reference.rms_norm
    h = r(rms_norm(x, p["ln1"], eps))
    z = r(lin(h, mb["w_z"]))
    xc = r(lin(h, mb["w_x"]))
    bc = r(lin(h, mb["w_bc"]))
    dtr = r(lin(h, mb["w_dt"]))
    xc = r(_causal_conv(xc, mb["conv_x"], mb["conv_bias_x"]))
    bc = r(_causal_conv(bc, mb["conv_b"], mb["conv_bias_b"]))
    B = bc[:, :G * n].reshape(S, G, n)
    C = bc[:, G * n:].reshape(S, G, n)
    group = jnp.arange(H) // (H // G)                             # head -> group
    xh = xc.reshape(S, H, P)
    dt = jax.nn.softplus(dtr + mb["dt_bias"])                      # (S, H)
    A = -jnp.exp(mb["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[group][:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, c_t[group], precision=HI)
        return state, y_t + mb["D_skip"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, n), jnp.float32),
                        (xh, B, C, dt))
    y = r(r(y.reshape(S, di)) * jax.nn.silu(z))
    y = r(rms_norm(y.reshape(S, G, di // G),
                   mb["gate_norm"].reshape(G, di // G), eps).reshape(S, di))
    return r(x + r(lin(y, mb["out_proj"])))


def _mlp(h, p, r, lin):
    return r(lin(r(jnp.square(jax.nn.relu(r(lin(h, p["w_up"]))))),
                 p["w_down"]))


def moe_mixer(h, mo, m, r, lin):
    """The E layer's mixer on normed rows h (S, D): the held experts' part
    of the routed sum (summed in float32), plus the shared expert."""
    logits = jnp.dot(h, mo["router"], precision=HI)          # float32 router
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + mo["e_bias"], m["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * m["moe_routed_scaling"]

    def expert(y, inp):
        pe, e = inp
        g = jnp.sum(jnp.where(idx == e, w, 0.0), axis=1)     # 0: not chosen
        return y + _mlp(h, pe, r, lin) * g[:, None], None

    held = m["moe_first_expert"] + jnp.arange(m["n_experts"])
    y, _ = jax.lax.scan(expert, jnp.zeros(h.shape, jnp.float32),
                        (mo["experts"], held))
    return r(r(y) + _mlp(h, mo["shared"], r, lin))


def _moe(x, p, m, r, lin):
    h = r(reference.rms_norm(x, p["ln1"], m["norm_eps"]))
    return r(x + moe_mixer(h, p["moe"], m, r, lin))


def _attn(x, p, m, r, lin):
    S, dh = x.shape[0], m["d_head"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    a = p["attn"]
    h = r(reference.rms_norm(x, p["ln1"], m["norm_eps"]))
    q = r(lin(h, a["wq"])).reshape(S, hq, dh)
    k = r(lin(h, a["wk"])).reshape(S, hkv, dh)
    v = r(lin(h, a["wv"])).reshape(S, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q * dh ** -0.5, k, precision=HI)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    return r(x + r(lin(r(o).reshape(S, hq * dh), a["wo"])))


_LAYER = {"M": _mamba, "E": _moe, "*": _attn}


def forward(x, layers, m, r, lin):
    rank = dict.fromkeys(KINDS, 0)
    for kind in m["layer_types"]:
        p = jax.tree.map(lambda v: v[rank[kind]], layers[kind])
        rank[kind] += 1
        x = _LAYER[kind](x, p, m, r, lin)
    return x


# ---- work counts ------------------------------------------------------------

def cim_sites(m, padded=False):
    """Every CIM call of one pass, in layer order: the four mamba input
    projections fused as the program fuses them and out_proj; QKV fused
    and wo; each held expert's up and down, which a share top_k /
    moe_router_experts of the rows needs, and the shared expert's."""
    d = m["d_model"]
    di, h, _, n, g, _ = _ssm(m)
    dh, hq, hkv = m["d_head"], m["n_heads"], m["n_kv_heads"]
    f, fs = m["moe_d_ff"], m["shared_expert_d_ff"]
    share = m["top_k"] / m["moe_router_experts"]
    kind_sites = {
        "M": [("in_proj", d, 2 * di + 2 * g * n + h, 1.0),
              ("out_proj", di, d, 1.0)],
        "*": [("qkv", d, (hq + 2 * hkv) * dh, 1.0), ("wo", hq * dh, d, 1.0)],
        "E": [("expert_up", d, f, share), ("expert_down", f, d, share)]
             * m["n_experts"]
             + [("shared_up", d, fs, 1.0), ("shared_down", fs, d, 1.0)],
    }
    return [s for kind in m["layer_types"] for s in kind_sites[kind]]


def kv_bytes_per_token(m, padded=False):
    """K and V of every attention layer and KV head, in float32: the
    program keeps a ``layer_types`` stack's KV cache in its float32
    activation dtype."""
    return m["layer_types"].count("*") * m["n_kv_heads"] * m["d_head"] * 8


def attn_ops_rows(m, first, last, padded=False):
    """QK and PV of rows at positions first..last: 4*Hq*dh a context
    position an attention layer."""
    if last < first:
        return 0
    ctx = (last + 1) * (last + 2) // 2 - first * (first + 1) // 2
    return (4 * m["layer_types"].count("*") * m["n_heads"] * m["d_head"]
            * ctx)


def state_ops(m, rows):
    """The recurrence, 5*H*P*N a row (decay, input outer product, add;
    output contraction), and the two convolutions, 2*W*(d_inner + 2GN),
    in every M layer."""
    di, h, p, n, g, w = _ssm(m)
    per = 5 * h * p * n + 2 * w * (di + 2 * g * n)
    return m["layer_types"].count("M") * per * rows


def prompt_multiple(m, serving):
    """The program prefills recurrent state in whole chunks."""
    return serving["prefill_chunk"]
