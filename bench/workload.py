"""The one traffic generator: turns a mix file (``traffic/<name>.json``)
and a seed into batches of requests.

A mix file holds parameters only:

  queue_per_slot  requests per batch, as a multiple of the cell's slots
  prefix          {"tokens": T, "tenants": n, "zipf": s}: every prompt
                  starts with T tokens.  With n > 0 tenants each tenant
                  owns one T-token system prompt, drawn once per run, and
                  a batch's requests are split over the tenants in
                  Zipf(s) proportions; with n = 0 every prefix is distinct.
  prompt, output  {"median", "sigma", "min", "max"}: lognormal lengths of
                  the prompt after the prefix, and of the generated
                  output, clipped to [min, max].

Every batch of one cell holds the same multiset of lengths: the lengths
are the lognormal's quantiles at (i + 1/2)/Q.  The batch index alone
decides which request gets which length and which tenant, and the seed
draws the tokens and the tenants' system prompts.  So every seed asks
for the same work in the same order (the order in which requests meet
in the slots sets a batch's makespan), and a run's rate does not swing
with the draw.
"""
from __future__ import annotations

import json
import statistics
from typing import List, Tuple

import numpy as np


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    for key in ("queue_per_slot", "prefix", "prompt", "output"):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    return mix


def quantile_lengths(spec: dict, n: int, scale: float = 1.0,
                     multiple: int = 1) -> np.ndarray:
    """n lognormal quantiles of ``spec``, clipped and rounded up to a
    multiple of ``multiple``."""
    median = spec["median"] * scale
    lo = max(1, int(round(spec["min"] * scale)))
    hi = max(lo, int(round(spec["max"] * scale)))
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        v = median * np.exp(spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
        v = min(max(int(round(v)), lo), hi)
        out.append(-(-v // multiple) * multiple)
    return np.asarray(out, np.int64)


def zipf_counts(n_items: int, tenants: int, s: float) -> np.ndarray:
    """Split n_items over tenants in proportion to 1/k**s (largest
    remainder, every tenant at least one)."""
    w = 1.0 / np.arange(1, tenants + 1) ** s
    share = w / w.sum() * n_items
    counts = np.maximum(np.floor(share).astype(np.int64), 1)
    while counts.sum() > n_items:
        counts[np.argmax(counts)] -= 1
    order = np.argsort(-(share - np.floor(share)))
    i = 0
    while counts.sum() < n_items:
        counts[order[i % tenants]] += 1
        i += 1
    return counts


class Traffic:
    """Batches of (rid, prompt, max_new_tokens) for one run of one cell."""

    def __init__(self, mix: dict, seed: int, slots: int, vocab: int,
                 prompt_cap: int, max_new_cap: int, multiple: int = 1,
                 scale: float = 1.0):
        self.q = int(mix["queue_per_slot"] * slots)
        self.seed, self.vocab = seed, vocab
        pre = mix["prefix"]
        self.prefix = int(round(pre["tokens"] * scale))
        self.tenants = int(pre.get("tenants", 0))
        suffix = quantile_lengths(mix["prompt"], self.q, scale)
        total = self.prefix + suffix
        total = -(-total // multiple) * multiple
        if total.max() > prompt_cap:
            raise ValueError(f"prompts reach {total.max()} tokens > cap "
                             f"{prompt_cap}")
        self.prompt_lens = total
        self.output_lens = np.minimum(
            quantile_lengths(mix["output"], self.q, scale), max_new_cap)
        rng = np.random.default_rng([seed, 0x5EED])
        self.system = (rng.integers(0, vocab, (self.tenants, self.prefix),
                                    dtype=np.int64)
                       if self.tenants else None)
        self.counts = (zipf_counts(self.q, self.tenants, pre.get("zipf", 1.0))
                       if self.tenants else None)

    def batch(self, index: int) -> List[Tuple[int, np.ndarray, int]]:
        order = np.random.default_rng([index, 0x0DE5])
        plens = order.permutation(self.prompt_lens)
        olens = order.permutation(self.output_lens)
        tenant = (order.permutation(np.repeat(np.arange(self.tenants),
                                              self.counts))
                  if self.tenants else None)
        rng = np.random.default_rng([self.seed, index])
        out = []
        for i in range(self.q):
            toks = rng.integers(0, self.vocab, int(plens[i]), dtype=np.int64)
            if tenant is not None:
                toks[:self.prefix] = self.system[tenant[i]]
            out.append((index * self.q + i, toks.astype(np.int32),
                        int(olens[i])))
        return out
