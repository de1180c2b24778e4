"""Operations and bytes of the served work, from a configuration's
published widths (its ``model`` section), never from the program.

Counting conventions (the yardstick; later changes to the program do
not move them):

* A CIM projection of an (M, K) activation by a (K, N) weight is
  2*M*K*N operations at the chip's int8 peak.  Projections that share
  an input count as one call of the summed N (QKV; gate and up; the
  four mamba2 input projections), as the program fuses them.
* Its bytes are 1 per weight per model pass (every decode step and
  every prefill chunk reads all weights once), plus 1 per activation
  element in and 4 per int32 element out.
* Heads are the published ones (36 for minicpm-2b); ``padded=True``
  counts the program's ``tp_head_pad`` heads instead, for comparison.
* Rows the model must compute for a request of prompt length P and n
  served tokens: P - S + n - 1, where S prompt tokens come from shared
  cache blocks (the last served token is never fed back).
* Attention at context c costs 4*H*dh*c operations per layer per row
  (QK and PV) at the bf16 peak; a decode row at context c reads
  c * L * H * dh * 2 (K and V) * 2 (bf16) bytes of cache.
* The LM head is 2*D*V operations per served token at the bf16 peak.
* The mamba2 recurrence is 5*H*P*N operations per row per layer (decay,
  input outer product, add; output contraction) and the two causal
  convolutions 2*W*(d_inner + 2N), at the bf16 peak.
"""
from __future__ import annotations

from typing import List, Tuple


def heads(model: dict, padded: bool = False) -> int:
    """Published heads, or with ``padded`` the program's: rounded up to a
    multiple of ``tp_head_pad`` (its default is 16)."""
    if not padded:
        return model["n_heads"]
    pad = max(model.get("tp_head_pad", 16), 1)
    return -(-model["n_heads"] // pad) * pad


def cim_sites(model: dict, padded: bool = False) -> List[Tuple[str, int, int]]:
    """(name, K, N) of each CIM call of one layer."""
    d = model["d_model"]
    if model["family"] == "ssm":
        di = model.get("ssm_expand", 2) * d
        n = model["ssm_state"]
        h = di // model.get("ssm_head_dim", 64)
        return [("in_proj", d, 2 * di + 2 * n + h), ("out_proj", di, d)]
    if model["family"] != "dense" or model["n_kv_heads"] != model["n_heads"]:
        raise ValueError("work counts cover mamba2 and MHA dense models")
    hd = heads(model, padded) * model["d_head"]
    f = model["d_ff"]
    return [("qkv", d, 3 * hd), ("wo", hd, d), ("gate_up", d, 2 * f),
            ("down", f, d)]


def cim_weights(model: dict, padded: bool = False) -> int:
    """CIM weights of the whole model: the bytes one pass reads."""
    return model["n_layers"] * sum(k * n for _, k, n in
                                   cim_sites(model, padded))


def cim_ops(model: dict, rows: int, padded: bool = False) -> int:
    return 2 * rows * cim_weights(model, padded)


def cim_bytes(model: dict, rows: int, passes: int,
              padded: bool = False) -> int:
    act = model["n_layers"] * sum(k + 4 * n for _, k, n in
                                  cim_sites(model, padded))
    return passes * cim_weights(model, padded) + rows * act


def kv_bytes_per_token(model: dict, padded: bool = False) -> int:
    if model["family"] == "ssm":
        return 0
    return model["n_layers"] * heads(model, padded) * model["d_head"] * 4


def attn_ops_rows(model: dict, first: int, last: int,
                  padded: bool = False) -> int:
    """Attention operations of rows at positions first..last (context
    position + 1)."""
    if model["family"] == "ssm" or last < first:
        return 0
    ctx = (last + 1) * (last + 2) // 2 - first * (first + 1) // 2
    return 4 * model["n_layers"] * heads(model, padded) * model["d_head"] * ctx


def ssm_ops(model: dict, rows: int) -> int:
    if model["family"] != "ssm":
        return 0
    d = model["d_model"]
    di = model.get("ssm_expand", 2) * d
    n, p = model["ssm_state"], model.get("ssm_head_dim", 64)
    w = model.get("ssm_conv_width", 4)
    per = 5 * (di // p) * p * n + 2 * w * (di + 2 * n)
    return model["n_layers"] * per * rows


def head_ops(model: dict, served: int) -> int:
    return 2 * model["d_model"] * model["vocab_size"] * served


def request_rows(prompt_len: int, served: int, shared_tokens: int = 0) -> int:
    return prompt_len - shared_tokens + served - 1


def step_work(model: dict, requests) -> Tuple[int, int]:
    """(int8 ops, bf16 ops) the model needs for ``requests``, an iterable
    of (prompt_len, served) pairs: every prompt and served token's row,
    none taken from a cache."""
    i8 = b16 = 0
    for plen, n in requests:
        rows = request_rows(plen, n)
        i8 += cim_ops(model, rows)
        b16 += (attn_ops_rows(model, 0, rows - 1) + ssm_ops(model, rows)
                + head_ops(model, n))
    return i8, b16


def decode_kv_bytes(model: dict, prompt_len: int, served: int) -> int:
    """Cache bytes the decode rows of one request must read: the row
    that yields served token i + 1 sees context prompt_len + i."""
    ctx = sum(prompt_len + i for i in range(1, served))
    return ctx * kv_bytes_per_token(model)


def decode_attn_ops(model: dict, prompt_len: int, served: int) -> int:
    if served < 2:
        return 0
    return attn_ops_rows(model, prompt_len, prompt_len + served - 2)
