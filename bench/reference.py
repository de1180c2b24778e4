"""Plain reference of the served models, independent of the program.

It computes what the configuration states, written out directly:

* every projection on the emulated macro: weights quantized per output
  column and activations per row to 8-bit sign-magnitude (round half to
  even, clip at 127, scale = max|v| * (1/127)); each 16-element
  conversion sums its three most significant bit-products exactly (the
  digital part: bits (6,6) weigh 2, (6,5) and (5,6) weigh 1, in units of
  2**11) and rounds the rest of its exact integer dot to the nearest
  2**11 with a 7-bit clip to [-64, 63] (the analog part and its ADC);
  the codes of all conversions add up, times 2**11, times both scales;
* everything else as the configuration states it: each operation's
  arithmetic in float32 at ``highest`` matmul precision, and its result
  rounded to the configuration's dtype (bfloat16) wherever a model of
  that dtype keeps a tensor: after each norm, projection, RoPE,
  attention, gate and residual add, and the logits.  The embedding, the
  final RMS norm with gain (1 + w) and the LM head are here, the head
  the embedding's transpose where the configuration ties them; the
  layers between them are the configuration's architecture's
  (``arch/<name>.py`` ``forward``), written with the helpers here.

``act`` names another dtype for every one of those roundings but the
logits'.  A configuration's ``check.act`` is the precision the program
keeps its activations in on the chip (``float32`` on the v5e: no
rounding), and its ``check.control``, one precision below it, gives the
control.

It imports nothing of the program and is given only the weights that
``weights.make_params`` makes from the seed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ACC_LEN = 16          # elements per ADC conversion
MAX_MAG = 127         # 7 magnitude bits
LSB = 2048            # 2**11: weight of the least digital bit-product
ADC_HALF = 64         # 7-bit ADC: codes in [-64, 63]
CHUNK_BLOCK = 8       # conversions per reference scan step (memory only)


def _rounder(act: str):
    dt = jnp.dtype(act)
    big = float(jnp.finfo(dt).max)
    return lambda t: jnp.clip(t, -big, big).astype(dt).astype(jnp.float32)


def quantize(v, axis):
    """Sign-magnitude quantization along ``axis`` to the macro's 8-bit
    full scale: (ints, scale)."""
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) * np.float32(1.0 / MAX_MAG)
    q = jnp.clip(jnp.round(v / scale), -MAX_MAG, MAX_MAG)
    return q.astype(jnp.int32), scale


def macro_int(xq, wq):
    """(M, K) x (K, N) sign-magnitude ints -> (M, N) int32 macro output."""
    M, K = xq.shape
    N = wq.shape[1]
    C = -(-K // ACC_LEN)
    nb = -(-C // CHUNK_BLOCK)
    kp = nb * CHUNK_BLOCK * ACC_LEN
    xq = jnp.pad(xq, ((0, 0), (0, kp - K)))
    wq = jnp.pad(wq, ((0, kp - K), (0, 0)))

    def split(v):
        s = jnp.where(v < 0, -1, 1)
        m = jnp.abs(v)
        return s, (m >> 6) & 1, (m >> 5) & 1

    sx, x6, x5 = split(xq)
    sw, w6, w5 = split(wq)
    i8 = lambda v: v.astype(jnp.int8)
    # x operands (nb, cb, M, L); w operands (nb, cb, L, N)
    xs = [i8(v).reshape(M, nb, CHUNK_BLOCK, ACC_LEN).transpose(1, 2, 0, 3)
          for v in (xq, sx * x6, sx * x5)]
    ws = [i8(v).reshape(nb, CHUNK_BLOCK, ACC_LEN, N)
          for v in (wq, sw * (2 * w6 + w5), sw * w6)]

    def dot(a, b):
        return jnp.einsum("cml,cln->cmn", a, b,
                          preferred_element_type=jnp.int32)

    def step(acc, blk):
        x, x6b, x5b, w, wd6, wd5 = blk
        digital = dot(x6b, wd6) + dot(x5b, wd5)
        rest = dot(x, w) - LSB * digital
        code = jnp.clip(jnp.floor_divide(rest + LSB // 2, LSB),
                        -ADC_HALF, ADC_HALF - 1)
        return acc + jnp.sum(digital + code, axis=0), None

    acc, _ = jax.lax.scan(step, jnp.zeros((M, N), jnp.int32),
                          (*xs, *ws))
    return acc * LSB


def cim_linear(x, w):
    """float (M, K) through the macro with raw weights w (K, N)."""
    wq, sw = quantize(w.astype(jnp.float32), axis=0)
    xq, sx = quantize(x, axis=-1)
    return macro_int(xq, wq).astype(jnp.float32) * sx * sw


def rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def rope(x, theta):
    """x (S, H, D): rotate the (i, i + D/2) pairs by pos / theta**(2i/D)."""
    S, _, D = x.shape
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def uniform_stack(layer, x, layers, m, r, lin):
    """``layer(x, p, m, r, lin)`` over the layers stacked on the leading
    axis of ``layers``, in order."""
    def body(x, p):
        return layer(x, p, m, r, lin), None

    x, _ = jax.lax.scan(body, x, layers)
    return x


@functools.partial(jax.jit,
                   static_argnames=("arch", "model_items", "n_out", "act"))
def logits(params, tokens, start, *, arch, model_items, n_out: int,
           act: Optional[str] = None):
    """Logits (n_out, V) of rows start .. start+n_out-1 of the sequence
    ``tokens`` (S,), run causally from position 0 through the layers of
    ``arch`` (the architecture's module)."""
    m = dict(model_items)
    r = _rounder(act or m["dtype"])
    x = r(params["embed"][tokens].astype(jnp.float32))
    x = arch.forward(x, params["layers"], m, r, cim_linear)
    h = r(rms_norm(x, params["ln_f"], m["norm_eps"]))
    h = jax.lax.dynamic_slice_in_dim(h, start, n_out, axis=0)
    head = (params["embed"].T if m["tie_embeddings"]
            else params["lm_head"])
    out = jnp.dot(h, head.astype(jnp.float32), precision=HI)
    return _rounder(m["dtype"])(out)


def model_items(model: dict) -> tuple:
    """The hashable static form of a configuration's ``model`` section,
    with the defaults the reference needs filled in."""
    m = dict(model)
    m.setdefault("norm_eps", 1e-6)
    m.setdefault("rope_theta", 10000.0)
    m.setdefault("ssm_head_dim", 64)
    m.setdefault("tie_embeddings", False)
    m.setdefault("dtype", "bfloat16")
    return tuple(sorted(m.items()))
