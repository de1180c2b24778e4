"""Operations and bytes of the served work, from a configuration's
published widths (its ``model`` section), never from the program.

Counting conventions (the yardstick; later changes to the program do
not move them):

* A CIM projection of an (M, K) activation by a (K, N) weight is
  2*M*K*N operations at the chip's int8 peak.  Projections that share
  an input count as one call of the summed N (QKV; gate and up; the
  four mamba2 input projections), as the program fuses them.  A site
  that only a share of the rows pass through (a routed expert) counts
  that share of them, its ``row_share``.
* Its bytes are 1 per weight per model pass (every decode step and
  every prefill chunk reads all weights once), plus 1 per activation
  element in and 4 per int32 element out.
* Heads are the published ones (36 for minicpm-2b); ``padded=True``
  counts the program's ``tp_head_pad`` heads instead, for comparison.
* Rows the model must compute for a request of prompt length P and n
  served tokens: P - S + n - 1, where S prompt tokens come from shared
  cache blocks (the last served token is never fed back).
* Attention at context c costs 4*H*dh*c operations per layer per row
  (QK and PV) at the bf16 peak; a decode row at context c reads the
  cache's bytes per token c times.
* The LM head is 2*D*V operations per served token at the bf16 peak.
* Recurrent state (the mamba2 recurrence and its convolutions) costs
  what its architecture counts, at the bf16 peak.

Each architecture (``arch/<name>.py``) counts its own layers:
``cim_sites(m, padded)``, every CIM call of one whole model pass as
(name, K, N, row_share); ``kv_bytes_per_token(m, padded)``;
``attn_ops_rows(m, first, last, padded)``, attention operations of rows
at positions first..last (context position + 1); ``state_ops(m, rows)``.
The functions here take that module as ``arch``.
"""
from __future__ import annotations

from typing import Tuple


def heads(model: dict, padded: bool = False) -> int:
    """Published heads, or with ``padded`` the program's: rounded up to a
    multiple of ``tp_head_pad`` (its default is 16)."""
    if not padded:
        return model["n_heads"]
    pad = max(model.get("tp_head_pad", 16), 1)
    return -(-model["n_heads"] // pad) * pad


def cim_weights(arch, model: dict, padded: bool = False) -> int:
    """CIM weights of the whole model: the bytes one pass reads."""
    return sum(k * n for _, k, n, _ in arch.cim_sites(model, padded))


def cim_ops(arch, model: dict, rows: int, padded: bool = False):
    return 2 * rows * sum(k * n * share for _, k, n, share in
                          arch.cim_sites(model, padded))


def cim_bytes(arch, model: dict, rows: int, passes: int,
              padded: bool = False):
    act = sum((k + 4 * n) * share for _, k, n, share in
              arch.cim_sites(model, padded))
    return passes * cim_weights(arch, model, padded) + rows * act


def head_ops(model: dict, served: int) -> int:
    return 2 * model["d_model"] * model["vocab_size"] * served


def request_rows(prompt_len: int, served: int, shared_tokens: int = 0) -> int:
    return prompt_len - shared_tokens + served - 1


def step_work(arch, model: dict, requests) -> Tuple[int, int]:
    """(int8 ops, bf16 ops) the model needs for ``requests``, an iterable
    of (prompt_len, served) pairs: every prompt and served token's row,
    none taken from a cache."""
    i8 = b16 = 0
    for plen, n in requests:
        rows = request_rows(plen, n)
        i8 += cim_ops(arch, model, rows)
        b16 += (arch.attn_ops_rows(model, 0, rows - 1)
                + arch.state_ops(model, rows) + head_ops(model, n))
    return i8, b16


def decode_kv_bytes(arch, model: dict, prompt_len: int, served: int) -> int:
    """Cache bytes the decode rows of one request must read: the row
    that yields served token i + 1 sees context prompt_len + i."""
    ctx = sum(prompt_len + i for i in range(1, served))
    return ctx * arch.kv_bytes_per_token(model)


def decode_attn_ops(arch, model: dict, prompt_len: int, served: int) -> int:
    if served < 2:
        return 0
    return arch.attn_ops_rows(model, prompt_len, prompt_len + served - 2)
