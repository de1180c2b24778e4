"""The comparison that decides ``correct``.

Two numbers, each with its limit:

* ``bad_requests``: requests of the window that came back with another
  count of tokens than they asked for, or with a token outside the
  vocabulary.  Limit 0: the traffic sets no stop token, so every request
  runs to its length.
* ``far_share``: over a sample of the window's requests, drawn from the
  seed and holding the longest one, the share of served tokens whose
  logit lies more than ``far_sigma`` standard deviations (of the
  reference's logits at that position) below the reference's best.  The
  reference (reference.py) runs once over each prompt with its served
  tokens, teacher-forced, after the program's state is freed, with its
  activations in the configuration's ``check.act``.

Why a share of far tokens and not the widest or the mean gap: the macro
rounds every 16-element conversion to a 7-bit code, about 10% of a
projection's output, and any difference in its input redraws those
roundings downstream.  So two implementations that are not bit-identical
part on near ties all the time, and a single token's gap, or the mean
of many, moves little between the program and a lower-precision twin.
How often a served token lies far below the best is what a lower
precision changes most: see PERF.md for the readings.

The control is the reference with its activations in the
configuration's ``check.control``, one precision below ``check.act``:
at each position of the same sequences it reads the gap of the token
it puts first.  It is no part of a benchmark run; ``readings.py`` and
the tests use it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference

# thresholds at which runs and readings log far_share, to set far_sigma by
FAR_SIGMAS = (0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 2.0)


def bad_requests(finished: Sequence[Tuple[int, np.ndarray, int, np.ndarray]],
                 vocab: int) -> int:
    """finished: (rid, prompt, max_new, served tokens) per request."""
    bad = 0
    for _, _, want, toks in finished:
        if len(toks) != want or (len(toks) and
                                 (toks.min() < 0 or toks.max() >= vocab)):
            bad += 1
    return bad


def sample(finished: Sequence, k: int, seed: int) -> List[int]:
    """Indices of k requests: the longest (prompt + served), and k - 1
    more drawn from the seed."""
    if not finished:
        return []
    total = [len(p) + len(t) for _, p, _, t in finished]
    longest = int(np.argmax(total))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[int(i)] for i in pick)


@jax.jit
def _gaps(ref, tokens):
    """Per row: the reference's best logit minus its logit of the token,
    in standard deviations of the reference's logits of that row."""
    ref = ref.astype(jnp.float32)
    got = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return (jnp.max(ref, axis=1) - got) / jnp.std(ref, axis=1)


def logit_gaps(arch, params, model: dict, cases, seq_len: int, n_cap: int,
               act: str, controls: Sequence[str] = ()
               ) -> Dict[str, List[np.ndarray]]:
    """Per case (prompt, served) of a model of architecture ``arch`` (its
    module): the gap of each served token ("served") against the
    reference with activations in ``act``, and for each control, named by
    its activation dtype, the gap of the token it puts first at the same
    position of the same sequence."""
    items = reference.model_items(model)
    out: Dict[str, List[np.ndarray]] = {"served": []}
    for name in controls:
        out[name] = []
    for prompt, served in cases:
        plen, n = len(prompt), len(served)
        seq = np.zeros(seq_len, np.int32)
        seq[:plen] = prompt
        seq[plen:plen + n - 1] = served[:-1]
        pad = np.zeros(n_cap, np.int32)
        pad[:n] = served
        ref = reference.logits(params, jnp.asarray(seq), plen - 1, arch=arch,
                               model_items=items, n_out=n_cap, act=act)
        out["served"].append(np.asarray(_gaps(ref, jnp.asarray(pad)))[:n])
        for name in controls:
            low = reference.logits(params, jnp.asarray(seq), plen - 1,
                                   arch=arch, model_items=items, n_out=n_cap,
                                   act=name)
            top = jnp.argmax(low, axis=1).astype(jnp.int32)
            out[name].append(np.asarray(_gaps(ref, top))[:n])
    return out


def far_share(gaps: Sequence[np.ndarray], far_sigma: float) -> float:
    """Share of all sampled tokens whose gap exceeds ``far_sigma``."""
    n = sum(len(g) for g in gaps)
    return float(sum((g > far_sigma).sum() for g in gaps) / n) if n else 1.0


def mean_gap(gaps: Sequence[np.ndarray]) -> float:
    """Token-weighted mean over the sampled requests."""
    n = sum(len(g) for g in gaps)
    return float(sum(g.sum() for g in gaps) / n) if n else float("inf")


def widest_gap(gaps: Sequence[np.ndarray]) -> float:
    return float(max((g.max() for g in gaps if len(g)), default=float("inf")))


def verdict(bad: int, share: Optional[float],
            limit: Optional[float]) -> dict:
    """The numbers compared, each with its limit, and whether all hold
    (never where the configuration sets no threshold or limit yet)."""
    checks = {"bad_requests": {"value": bad, "limit": 0},
              "far_share": {"value": share, "limit": limit}}
    ok = bad == 0 and None not in (share, limit) and share <= limit
    return {"correct": bool(ok), "checks": checks}
