"""Continuous-batching serving scheduler over a fixed pool of decode slots.

The paper's macro is weight-stationary: weights are written once and
activations stream.  The serving-system analogue is a fixed pool of decode
slots over prepacked weights -- one compiled decode step serves the pool
forever, and the scheduler's only job is keeping the slots full.  The
lock-step loop in launch/serve.py wastes exactly what the macro's
single-conversion trick saves: a finished sequence burns a slot (a
conversion) until the slowest request ends.  Here every step advances only
live slots, and a freed slot is refilled from the request queue through
``lm.prefill_into_slot`` without recompiling anything.

The entire serve loop is DEVICE-RESIDENT.  The request queue (prompts +
per-request budgets/stop tokens) is staged into device buffers up front,
and one AOT-compiled ``lax.while_loop`` runs a ``lax.switch`` until the
queue is drained:

  harvest : some slot finished (EOS or max-new-tokens, tracked by the
            on-device ``live`` mask; finishes are parked in a ``pending``
            mask) -> copy its output row into the per-request result
            buffer and free the slot (paged mode: decrement its blocks'
            refcounts and point its table at the trash block).
  admit   : a slot is free and the queue head is admissible -> reset the
            slot and arm it.  Contiguous mode prefills the whole prompt
            here (``lm.prefill_into_slot``); paged mode only ALLOCATES
            (grab blocks off the device free list, copy the shared-prefix
            chain from the donor's recorded table, place pin refcounts)
            and marks the slot ``filling`` -- the prompt itself streams in
            through the prefill branch.
  prefill : (paged only) advance ONE filling slot by one
            ``prefill_chunk``-token chunk (``lm.prefill_chunk_into_slot``).
            Chunked admission interleaves with decode steps, so a long
            prompt can no longer stall the whole pool for its full
            prefill; the final chunk samples the request's first token.
  step    : one pooled decode step; only live slots advance.

The host syncs with the device exactly ONCE per workload -- there is no
per-token (or even per-request) host round-trip, which is what lets the
scheduler's fewer-wasted-slot-steps advantage survive dispatch latency
even at smoke scale on CPU.  (``run_instrumented`` deliberately trades
that away: it drives the SAME compiled iteration body one switch at a
time to put a host timestamp on every iteration -- TTFT and per-step
latency percentiles for the serve benchmark -- while ``run`` keeps the
pure loop for throughput numbers.)

PAGED mode (``paged=PagedLayout(...)``) replaces the per-slot contiguous
``max_seq`` KV regions with global per-layer block pools and per-slot
block tables (lm.init_paged_cache).  The allocator lives INSIDE the loop:
a ``(n_blocks,)`` refcount vector doubles as the free list (free <=>
ref==0; an argsort puts free blocks first in id order), admission grants
``max_blk`` blocks eagerly (prompt span + decode budget + speculative
headroom -- no mid-flight growth, so admission is the only place that can
run out), and harvest decrements.  Shared prompt prefixes are planned on
the host (paging.plan_prefix_sharing): a sharer copies the donor's
leading table entries instead of recomputing them, donors carry pin
refcounts for every chain that passes through their blocks, and the
refcount algebra returns every block to zero when the queue drains.
Tokens are BIT-IDENTICAL to the contiguous scheduler (and to a solo run):
the attention validity horizon does not care where KV rows physically
live, and the chunked/shared prefill paths recompute exactly the rows
whose values the single-shot path would have produced.

Determinism contract (tested in tests/test_scheduler.py): a request's
tokens depend only on (params, prompt, rid) -- NOT on which slot it ran
in, what shared the pool with it, or when it was admitted.  Sampling keys
are folded per request id (``fold_in(sampling_key(seed), rid)``) and each
slot consumes its own key stream one split per generated token, so even
temperature sampling is bit-identical to a solo run.

Mixed-fidelity deployment plans (repro.plan) serve through this scheduler
UNCHANGED: a plan is static metadata on the ModelConfig, resolved inside
``lm.prefill_into_slot``/``lm.decode_step`` at trace time, so the pool's
AOT-compiled loop already embeds every projection's own macro config --
zero recompiles across decode steps, per-layer D/A splits and all
(tests/test_plan.py).  Caveat: deterministic noise emulation
(cfg.cim_noise_seed) draws per POOL ROW, so noisy tokens depend on slot
assignment -- like silicon, where each slot maps to a physical macro bank
-- and the scheduler's slot-independence contract holds only noise-free.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import lm
from ..models.layers import MOE_TAPS
from ..models.config import ModelConfig
from ..obs import taps
from ..obs import rings as obs_rings
from ..obs.compiles import backend_compiles
from ..obs.rings import ObsConfig, ObsSnapshot
from ..obs.scopes import scoped
from ..obs.trace import get_tracer, span
from ..resilience import faults as rfaults
from .paging import PagedLayout, cdiv, contiguous_kv_bytes, plan_prefix_sharing


def sampling_key(seed: int) -> jax.Array:
    """Sampling PRNG stream, deliberately distinct from the params-init
    stream: serve.py used to feed PRNGKey(seed) to BOTH ``lm.init`` and
    the decode-loop sampler (regression-tested in tests/test_scheduler.py)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0x53414D50)  # "SAMP"


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.  ``rid`` seeds the request's sampling
    stream and must be unique within a run.  ``stop_token < 0`` disables
    EOS detection (the request runs to ``max_new_tokens``)."""
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int
    stop_token: int = -1


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    tokens: np.ndarray            # (n,) generated tokens, stop token incl.
    latency_s: float              # arrival (run start) -> completion
    finish_iter: int              # loop iteration the request finished at
    first_iter: int = 0           # loop iteration its first token appeared
    ttft_s: float = float("nan")  # measured only by run_instrumented
    prompt_len: int = 0           # prompt tokens


@dataclasses.dataclass
class ServeReport:
    finished: List[FinishedRequest]
    wall_s: float
    n_steps: int                  # pooled decode steps (rounds, if spec)
    n_admits: int
    slots: int
    n_drafted: int = 0            # draft tokens proposed (speculative mode)
    n_accepted: int = 0           # draft tokens accepted by verify
    n_pf: int = 0                 # chunked-prefill iterations (paged mode)
    peak_blocks: int = 0          # peak live pool blocks (paged mode)
    obs: Optional[ObsSnapshot] = None   # harvested device rings (obs mode)
    n_iter: int = 0               # loop iterations, harvests included
    n_compiles: int = 0           # XLA backend compiles during run()
    # host seconds of each span closed inside run() (sched.*, launch.*)
    host_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    # paged decode-attention geometry; 0 when unpaged or attention-free
    block_size: int = 0
    n_tbl: int = 0
    # held-expert layers, over every MoE layer of every decode step and
    # prefill chunk: (live row, held expert) pairs the router chose, rows
    # the held experts' CIM calls computed, and held-expert calls taken
    # (of n_experts a layer and pass); 0 without such layers
    moe_routed_rows: int = 0
    moe_expert_rows: int = 0
    moe_expert_calls: int = 0

    def take_moe_counts(self, carry) -> None:
        """Take the held-expert counts (``MOE_TAPS``) from a loop's carry."""
        for k in MOE_TAPS:
            setattr(self, k, int(carry[k]))

    @property
    def total_tokens(self) -> int:
        return sum(len(f.tokens) for f in self.finished)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify pass accepted."""
        return (self.n_accepted / self.n_drafted if self.n_drafted
                else float("nan"))

    @property
    def tokens_per_step(self) -> float:
        """Emitted tokens per pooled step: ~1*occupancy lock-free decode,
        up to (draft_k+1)*slots when every draft block is accepted."""
        return (self.total_tokens / self.n_steps if self.n_steps
                else float("nan"))

    @property
    def tok_s(self) -> float:
        return self.total_tokens / self.wall_s if self.wall_s > 0 else float("nan")

    @property
    def occupancy(self) -> float:
        """Useful-token fraction of the slot-steps spent (admits each
        yield one token; every pooled step spends ``slots`` slot-steps)."""
        slot_steps = self.slots * self.n_steps + self.n_admits + self.n_pf
        return self.total_tokens / slot_steps if slot_steps else float("nan")

    @property
    def attn_live_block_frac(self) -> float:
        """Share of the paged decode-attention grid (slots x table
        entries, every pooled step) that live rows' lengths cover: each
        served token after the first is one decode row at context
        ``prompt_len + i``, which needs ceil((prompt_len + i) /
        block_size) blocks.  0 when unpaged or attention-free."""
        grid = self.n_steps * self.slots * self.n_tbl
        if not grid:
            return 0.0
        bs = self.block_size
        live = sum(-(-(f.prompt_len + i) // bs)
                   for f in self.finished for i in range(1, len(f.tokens)))
        return live / grid

    def latency_percentiles(self) -> Dict[str, float]:
        lats = sorted(f.latency_s for f in self.finished)
        if not lats:
            return {"p50_s": float("nan"), "p95_s": float("nan")}
        pick = lambda q: lats[min(len(lats) - 1, int(q * (len(lats) - 1) + 0.5))]
        return {"p50_s": pick(0.50), "p95_s": pick(0.95)}

    def ttft_percentiles(self) -> Dict[str, float]:
        """Time-to-first-token percentiles; NaN unless the report came
        from ``run_instrumented`` (the pure device loop has no per-event
        clock to read without paying the sync it removes)."""
        ts = sorted(f.ttft_s for f in self.finished
                    if not np.isnan(f.ttft_s))
        if not ts:
            return {"ttft_p50_s": float("nan"), "ttft_p95_s": float("nan")}
        pick = lambda q: ts[min(len(ts) - 1, int(q * (len(ts) - 1) + 0.5))]
        return {"ttft_p50_s": pick(0.50), "ttft_p95_s": pick(0.95)}

    def summary(self) -> Dict:
        out = dict(total_tokens=self.total_tokens,
                   wall_s=round(self.wall_s, 4),
                   tok_s=round(self.tok_s, 2),
                   occupancy=round(self.occupancy, 4),
                   n_steps=self.n_steps, n_admits=self.n_admits,
                   slots=self.slots,
                   **{k: round(v, 4) for k, v in
                      self.latency_percentiles().items()})
        if self.n_drafted:
            out.update(n_drafted=self.n_drafted,
                       n_accepted=self.n_accepted,
                       acceptance_rate=round(self.acceptance_rate, 4),
                       tokens_per_step=round(self.tokens_per_step, 4))
        if self.n_pf or self.peak_blocks:
            out.update(n_pf=self.n_pf, peak_blocks=self.peak_blocks)
        return out

    def tokens_by_rid(self) -> Dict[int, np.ndarray]:
        return {f.rid: f.tokens for f in self.finished}


def _i32(v) -> jax.Array:
    return jnp.asarray(v, jnp.int32)


# q_meta column layout (one row per staged request):
#   0 rid  1 max_new  2 stop  3 prompt_len  4 share_src  5 n_shared_blocks
#   6 arrival_iter  7 max_blk
_QM_COLS = 8


class ContinuousBatchingScheduler:
    """Fixed-slot continuous batching, fully device-resident.

    ``params`` may hold prepacked CIM weights (lm.pack_cim_params) -- the
    scheduler never touches weights, so pack-once/serve-many carries
    straight through.  ``max_new_cap`` bounds every request's
    max_new_tokens and sizes the on-device output buffers; ``prompt_len``
    is the static MAXIMUM prompt length (contiguous mode: also the exact
    length -- shorter prompts must be padded by the caller; paged mode:
    shorter prompts are fine, the scheduler pads the staging buffer and
    tracks true lengths per request).

    Request latencies are exact at the workload level (one wall clock
    around the device loop) and attributed per request by its finish
    iteration: latency_i = wall * finish_iter_i / total_iters.  This is an
    estimate -- admit iterations cost more than step iterations -- but the
    loop never leaves the device, so there is no per-event host timestamp
    to read without paying the sync the design removes.  Use
    ``run_instrumented`` when you need real TTFT / per-iteration numbers.

    ``paged=PagedLayout(...)`` switches the KV cache to the global block
    pool + per-slot table layout with on-device alloc/free, host-planned
    shared-prefix reuse (``prefix_sharing``, attention families only --
    SSM/conv recurrent state is not positional and cannot be shared) and
    chunked prefill (``prefill_chunk`` tokens per scheduler iteration,
    default: whole prompt in one chunk).  Paged tokens are bit-identical
    to contiguous-mode tokens.

    ``draft_k > 0`` turns on plan-cascade speculative decoding: each step
    branch becomes one atomic draft-K/verify/accept ROUND (see
    ``spec_step``), drafting under ``draft_plan`` (an all-analog shadow of
    the serving plan -- ``plan.derive_draft_plan`` -- served from the SAME
    packed weights) and verifying under the deployed config.  Rounds are
    atomic per loop iteration, so harvest/admit still interleave between
    rounds and the determinism contract is unchanged: a request's tokens
    depend only on (params, prompt, rid); greedy output is bit-identical
    to the non-speculative scheduler, temperature sampling is
    distribution-identical (rejection sampling) and stays pool-vs-solo
    bit-identical at EQUAL draft_k.  Restricted to positional-KV families
    (attention); SSM/conv recurrences cannot roll back a rejected block.

    ``adaptive_draft_k=True`` feeds the measured acceptance rate (an EMA
    over spec rounds) back into the next round's draft depth over the
    rung ladder {K, K/2, K/4}: high acceptance keeps deep drafts, low
    acceptance stops paying for blocks the verifier rejects.  Greedy
    tokens are invariant to the rung (accept-longest-prefix + correction
    reproduces the argmax chain at any K); temperature sampling stays
    distribution-correct but the pool-vs-solo bit-equality holds only at
    FIXED draft_k (the rung schedule depends on poolmates' acceptance).

    ``obs=ObsConfig(...)`` threads fixed-size telemetry rings through
    the loop carry (obs/rings.py): per-request admit/first-token/finish
    iteration stamps, per-iteration occupancy/token samples, and scalar
    counters (ADC clips via obs/taps.py, prefix hits, free-list
    low-water mark), all written with saturating masked scatters so the
    loop still syncs the host exactly once.  Telemetry is a STATIC flag
    compiling a SEPARATE executable: with ``obs=None`` the lowered
    serve loop is byte-identical to the pre-telemetry program
    (``loop_hlo_text`` exposes the text; serve_bench gates its sha256),
    and with obs on the emitted tokens are bit-identical -- the rings
    only read values the loop already computes (tests/test_obs.py).
    """

    def __init__(self, params, cfg: ModelConfig, slots: int, prompt_len: int,
                 max_new_cap: int, temperature: float = 0.0, seed: int = 0,
                 pad_token: int = 0, draft_k: int = 0, draft_plan=None,
                 paged: Optional[PagedLayout] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_sharing: bool = True,
                 adaptive_draft_k: bool = False,
                 obs: Optional[ObsConfig] = None):
        if cfg.family == "vlm":
            raise NotImplementedError(
                "scheduler is text-only for now (no per-request frontends)")
        if draft_k and cfg.family in ("ssm", "hybrid"):
            raise NotImplementedError(
                "speculative decoding needs positional KV rollback; the "
                f"{cfg.family!r} family carries recurrent SSM/conv state "
                "that a rejected draft block cannot roll back")
        if draft_k < 0 or draft_k > 31:
            raise ValueError(f"draft_k {draft_k} outside [0, 31] (k+1 must "
                             "stay on the skinny-M verify path)")
        if adaptive_draft_k and not draft_k:
            raise ValueError("adaptive_draft_k needs draft_k > 0")
        self.cfg, self.slots = cfg, slots
        self.obs = obs
        self.prompt_len, self.cap = prompt_len, max_new_cap
        self.temperature, self.pad_token = temperature, pad_token
        self._base_key = sampling_key(seed)
        # speculative rounds write draft/verify KV rows up to pos + draft_k
        # before rollback, so the cache keeps that much extra headroom
        self.max_seq = prompt_len + max_new_cap + draft_k
        self._params = params
        self.draft_k = draft_k
        self.adaptive_draft_k = adaptive_draft_k
        rungs: List[int] = []
        for k in (draft_k, draft_k // 2, draft_k // 4):
            k = max(1, k)
            if k not in rungs:
                rungs.append(k)
        self._rungs = rungs if adaptive_draft_k else [draft_k]
        self.draft_cfg = (dataclasses.replace(cfg, cim_plan=draft_plan)
                          if draft_plan is not None else cfg)

        self.paged = paged
        # held-expert layers count their routed and computed rows through
        # taps (models/layers.py); the loop sums them in its carry
        self._moe = bool(cfg.layer_count("E"))
        self.prefix_sharing = prefix_sharing
        if paged is not None:
            C = prefill_chunk if prefill_chunk is not None else prompt_len
            if not (1 <= C <= prompt_len):
                raise ValueError(f"prefill_chunk {C} outside [1, {prompt_len}]")
            if paged.n_tbl >= paged.n_blocks:
                raise ValueError(
                    f"table width {paged.n_tbl} >= pool size {paged.n_blocks}"
                    " (one slot could hold more blocks than exist)")
            self.prefill_chunk = C
            self._p_pad = cdiv(prompt_len, C) * C
            need = max(self._p_pad, prompt_len + max_new_cap - 1 + draft_k)
            if paged.tokens_per_slot < need:
                raise ValueError(
                    f"paged layout addresses {paged.tokens_per_slot} tokens "
                    f"per slot < worst-case need {need} (prompt span + "
                    "decode budget + draft headroom)")
        else:
            self.prefill_chunk = prompt_len
            self._p_pad = prompt_len
        self._loops: Dict[int, object] = {}    # queue length -> executable
        self._n_runs = 0                       # run() calls: batch serial
        self._iters: Dict[int, object] = {}    # queue length -> one-iter exe

        @scoped("sched.sample")
        def sample(logits, keys):
            """logits (R, V) f32, keys (R, 2) -> (R,) int32 tokens."""
            if temperature <= 0:
                return jnp.argmax(logits, -1).astype(jnp.int32)
            return jax.vmap(lambda l, k: jax.random.categorical(
                k, l / temperature))(logits, keys).astype(jnp.int32)

        def arm_slot(params, st, slot, prompt, rid, max_new, stop):
            """Reset + prefill ``slot`` with one request and sample its
            first token.  A request can finish ON that token; the event is
            parked in the pending mask like any step finish."""
            logits, cache = lm.prefill_into_slot(params, cfg, prompt,
                                                 st["cache"], slot)
            k_next, k_use = jax.random.split(
                jax.random.fold_in(self._base_key, rid))
            tok = sample(logits[:, -1], k_use[None])[0]
            fin0 = (tok == stop) | (max_new <= 1)
            st = dict(st, cache=cache)
            st["last_tok"] = st["last_tok"].at[slot, 0].set(tok)
            st["out"] = (st["out"].at[slot].set(self.pad_token)
                         .at[slot, 0].set(tok))
            st["n_gen"] = st["n_gen"].at[slot].set(1)
            st["max_new"] = st["max_new"].at[slot].set(max_new)
            st["stop"] = st["stop"].at[slot].set(stop)
            st["keys"] = st["keys"].at[slot].set(k_next)
            st["live"] = st["live"].at[slot].set(~fin0)
            st["pending"] = st["pending"].at[slot].set(fin0)
            return st

        def step(params, st):
            """One pooled decode step; finishes land in pending."""
            live = st["live"]
            logits, cache = lm.decode_step(params, cfg, st["last_tok"],
                                           st["cache"], live=live)
            splits = jax.vmap(jax.random.split)(st["keys"])      # (B,2,2)
            tok = sample(logits[:, -1], splits[:, 1])
            tok = jnp.where(live, tok, jnp.int32(self.pad_token))
            keys = jnp.where(live[:, None], splits[:, 0], st["keys"])
            ar = jnp.arange(self.slots)
            idx = jnp.minimum(st["n_gen"], self.cap - 1)
            row = st["out"][ar, idx]
            out = st["out"].at[ar, idx].set(jnp.where(live, tok, row))
            n_gen = st["n_gen"] + live.astype(jnp.int32)
            finished = live & ((tok == st["stop"]) | (n_gen >= st["max_new"]))
            return dict(st, cache=cache, last_tok=tok[:, None], out=out,
                        n_gen=n_gen, keys=keys, live=live & ~finished,
                        pending=st["pending"] | finished)

        def spec_step(params, st, K: int):
            """One speculative ROUND as a single pooled step: draft K
            tokens under the draft-plan config (same packed weights), roll
            the per-slot positions back, verify all K+1 positions in ONE
            wide forward (M = slots*(K+1) stays on the skinny-M prepacked
            kernels), then accept the longest agreeing prefix plus a
            correction/bonus token.  Emits a VARIABLE 1..K+1 tokens per
            slot; the whole round compiles into one loop iteration, so
            per-step dispatch overhead is amortized over every accepted
            token.  Returns (state, n_drafted, n_accepted).

            Rollback is positional: draft and verify writes land at rows
            >= the committed ``cache["pos"]``, which the attention
            validity horizon masks until pos is advanced past them -- so
            "rolling back" a rejected suffix is just not advancing pos
            over it, and the next round's writes overwrite those rows.
            Paged caches change NOTHING here: the table is untouched
            mid-round (admission pre-allocated ``draft_k`` rows of
            headroom), so rollback never frees or re-allocates a block,
            and non-live slots' draft/verify writes are redirected to the
            trash block (they may alias shared or mid-prefill blocks).
            """
            live = st["live"]
            pos0 = st["cache"]["pos"]
            cache, keys, last = st["cache"], st["keys"], st["last_tok"]
            d_toks, d_logits = [], []
            for _ in range(K):
                logits, cache = lm.decode_step(params, self.draft_cfg, last,
                                               cache, live=live)
                splits = jax.vmap(jax.random.split)(keys)
                dtok = sample(logits[:, -1], splits[:, 1])
                dtok = jnp.where(live, dtok, jnp.int32(self.pad_token))
                keys = jnp.where(live[:, None], splits[:, 0], keys)
                d_toks.append(dtok)
                if temperature > 0:
                    d_logits.append(logits[:, -1])
                last = dtok[:, None]
            drafts = jnp.stack(d_toks, axis=1)                  # (B, K)
            vtoks = jnp.concatenate([st["last_tok"], drafts], axis=1)
            cache = dict(cache, pos=pos0)   # rollback before verify
            vlogits, cache = lm.verify_step(params, cfg, vtoks, cache,
                                            live=live)

            # verify position i gives the distribution of the token AFTER
            # prefix [last, d_1..d_i]; cand pads drafts to K+1 columns so
            # the correction token can be placed at column n_acc
            cand = jnp.concatenate([drafts, drafts[:, -1:]], axis=1)
            if temperature <= 0:
                v_arg = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
                match = (v_arg[:, :K] == drafts).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                corr = v_arg              # correction at ANY column is its argmax
            else:
                # standard rejection sampling: accept d_i with probability
                # min(1, p_verify(d_i)/p_draft(d_i)); on first rejection,
                # resample from the normalized residual max(p_v - p_d, 0).
                # When all K drafts are accepted the padded zero row makes
                # the residual collapse to p_v[:, K] -- the bonus draw.
                dlg = jnp.stack(d_logits, axis=1)               # (B, K, V)
                p_d = jax.nn.softmax(dlg / temperature, axis=-1)
                p_v = jax.nn.softmax(vlogits / temperature, axis=-1)
                pd_tok = jnp.take_along_axis(
                    p_d, drafts[..., None], -1)[..., 0]
                pv_tok = jnp.take_along_axis(
                    p_v[:, :K], drafts[..., None], -1)[..., 0]
                splits = jax.vmap(jax.random.split)(keys)
                u = jax.vmap(lambda k: jax.random.uniform(k, (K,)))(
                    splits[:, 1])
                keys = jnp.where(live[:, None], splits[:, 0], keys)
                acc = (u * pd_tok < pv_tok).astype(jnp.int32)
                n_acc = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
                pv_n = jnp.take_along_axis(
                    p_v, n_acc[:, None, None], axis=1)[:, 0]
                pd_ext = jnp.concatenate(
                    [p_d, jnp.zeros_like(p_d[:, :1])], axis=1)
                pd_n = jnp.take_along_axis(
                    pd_ext, n_acc[:, None, None], axis=1)[:, 0]
                res = jnp.maximum(pv_n - pd_n, 0.0)
                tot = jnp.sum(res, axis=-1, keepdims=True)
                res = jnp.where(tot > 0, res / jnp.maximum(tot, 1e-38),
                                pv_n)
                splits = jax.vmap(jax.random.split)(keys)
                corr = jax.vmap(lambda r, k: jax.random.categorical(
                    k, jnp.log(jnp.maximum(r, 1e-38))))(
                    res, splits[:, 1]).astype(jnp.int32)[:, None]
                keys = jnp.where(live[:, None], splits[:, 0], keys)

            cols = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
            emitted = jnp.where(cols == n_acc[:, None], corr, cand)
            # clamp by the per-request budget, then truncate at the first
            # stop token INSIDE the emitted block (stop included)
            allowed = jnp.maximum(st["max_new"] - st["n_gen"], 0)
            n_emit = jnp.minimum(n_acc + 1, allowed)
            is_stop = (emitted == st["stop"][:, None]) & (cols < n_emit[:, None])
            has_stop = jnp.any(is_stop, axis=1)
            n_emit = jnp.where(has_stop, jnp.argmax(is_stop, axis=1) + 1,
                               n_emit)
            n_emit = jnp.where(live, n_emit, 0)

            ar = jnp.arange(self.slots)
            out = st["out"]
            for j in range(K + 1):
                idx = jnp.minimum(st["n_gen"] + j, self.cap - 1)
                cur = out[ar, idx]
                out = out.at[ar, idx].set(
                    jnp.where(j < n_emit, emitted[:, j], cur))
            n_gen = st["n_gen"] + n_emit
            finished = live & (has_stop | (n_gen >= st["max_new"]))
            new_last = jnp.take_along_axis(
                emitted, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
            new_last = jnp.where(n_emit > 0, new_last, st["last_tok"][:, 0])
            # committed rows [0, pos0 + n_emit): the old frontier token
            # plus every emitted token except the new frontier
            cache = dict(cache, pos=pos0 + n_emit)
            st = dict(st, cache=cache, last_tok=new_last[:, None], out=out,
                      n_gen=n_gen, keys=keys, live=live & ~finished,
                      pending=st["pending"] | finished)
            return (st, jnp.sum(jnp.where(live, K, 0)).astype(jnp.int32),
                    jnp.sum(jnp.where(live, n_acc, 0)).astype(jnp.int32))

        self._sample = sample
        self._arm_slot, self._step_fn = arm_slot, step
        self._spec_step = spec_step
        self._lockstep_exes = None

    # -- KV footprint accounting ---------------------------------------

    def kv_bytes_contiguous(self, dtype_bytes: int = 2) -> int:
        """KV bytes the contiguous layout would hold resident for this
        pool (slots * max_seq regions) -- the baseline the paged pool's
        peak-block footprint is compared against."""
        return contiguous_kv_bytes(self.cfg, self.slots, self.max_seq,
                                   dtype_bytes=dtype_bytes)

    def kv_bytes_paged(self, n_blocks: Optional[int] = None,
                       dtype_bytes: int = 2) -> int:
        """KV bytes of ``n_blocks`` pool blocks (default: the whole
        pool).  Pass a report's ``peak_blocks`` for the peak-resident
        number the serve benchmark gates on."""
        if self.paged is None:
            raise ValueError("kv_bytes_paged on a contiguous scheduler")
        return self.paged.kv_bytes(self.cfg, n_blocks=n_blocks,
                                   dtype_bytes=dtype_bytes)

    def _lockstep_executables(self):
        """Lock-step baseline executables: batch-1 admit + drain-N-steps
        (run_lockstep), compiled lazily against the same pool state."""
        if self._lockstep_exes is None:
            state = self._init_state()
            p0 = _i32(np.zeros((1, self.prompt_len)))
            z = _i32(0)
            admit = (jax.jit(self._arm_slot, donate_argnums=(1,))
                     .lower(self._params, state, z, p0, z, z, z).compile())

            def drain(params, st, n):
                return jax.lax.fori_loop(
                    0, n, lambda _, s: self._step_fn(params, s), st)

            drain = (jax.jit(drain, donate_argnums=(1,))
                     .lower(self._params, state, z).compile())
            self._lockstep_exes = (admit, drain)
        return self._lockstep_exes

    # -- device-resident serve loop ------------------------------------

    def _occupied(self, st):
        occ = st["live"] | st["pending"]
        if self.paged is not None:
            occ = occ | st["filling"]
        return occ

    def _step_once(self, params, c, q_toks, q_meta, q_pins, n_queue: int):
        """ONE scheduler iteration: pick a branch, run it, bump n_iter.
        The while_loop body (``_build_loop``) and the host-stepped
        instrumented runner (``run_instrumented``) share this function,
        so instrumenting never measures a different program.  Returns
        (carry, branch, continue?).

        Each branch runs under its named scope (``sched.harvest``,
        ``sched.admit``, ``sched.prefill``, ``sched.step``), which the
        compiled loop's HLO metadata carries down to every op
        (``compiled_loop_text``); scopes add no op."""
        cfg, paged = self.cfg, self.paged

        @scoped("sched.harvest")
        def harvest(c):
            st = c["st"]
            slot = jnp.argmax(st["pending"])
            qidx = st["occupant"][slot]
            c = dict(c)
            c["res_out"] = c["res_out"].at[qidx].set(st["out"][slot])
            c["res_n"] = c["res_n"].at[qidx].set(st["n_gen"][slot])
            c["res_iter"] = c["res_iter"].at[qidx].set(c["n_iter"])
            if self.obs is not None:
                o = obs_rings.ring_push(c["obs"], obs_rings.EV_FINISH,
                                        q_meta[qidx, 0], c["n_iter"])
                c["obs"] = dict(o, tick_tok=jnp.zeros((), jnp.int32))
            st = dict(st, pending=st["pending"].at[slot].set(False))
            if paged is not None:
                # free the slot's grant: one ref off each of its first
                # n_alloc table entries (shared entries included -- the
                # donor pinned one ref per chain through them), then park
                # the table on the trash block
                tbl_row = st["cache"]["table"][slot]
                j = jnp.arange(paged.n_tbl, dtype=jnp.int32)
                tgt = jnp.where(j < st["n_alloc"][slot], tbl_row,
                                paged.n_blocks)
                st["ref"] = st["ref"].at[tgt].add(-1, mode="drop")
                st["n_alloc"] = st["n_alloc"].at[slot].set(0)
                st["cache"] = dict(st["cache"],
                                   table=st["cache"]["table"].at[slot].set(0))
            c["st"] = st
            return c

        @scoped("sched.admit")
        def admit_contiguous(c):
            st, qidx = c["st"], c["q_head"]
            slot = jnp.argmin(self._occupied(st))
            prompt = jax.lax.dynamic_slice(q_toks, (qidx, 0),
                                           (1, self.prompt_len))
            rid, max_new, stop = (q_meta[qidx, 0], q_meta[qidx, 1],
                                  q_meta[qidx, 2])
            upd = {}
            if self.obs is not None:
                with taps.collect() as fr:
                    st = self._arm_slot(params, st, slot, prompt, rid,
                                        max_new, stop)
                # the whole-prompt prefill samples the first token here:
                # admit and first-token land on the same iteration stamp
                o = obs_rings.ring_push(c["obs"], obs_rings.EV_ADMIT,
                                        rid, c["n_iter"])
                o = obs_rings.ring_push(o, obs_rings.EV_FIRST, rid,
                                        c["n_iter"])
                o = obs_rings.ctr_add(o, obs_rings.CTR_ADC_CLIP,
                                      taps.drain_sum(fr, "adc_clip"))
                upd["obs"] = dict(o, tick_tok=jnp.ones((), jnp.int32))
            else:
                st = self._arm_slot(params, st, slot, prompt, rid, max_new,
                                    stop)
            st = dict(st, occupant=st["occupant"].at[slot].set(qidx))
            return dict(c, st=st, q_head=qidx + 1,
                        n_admits=c["n_admits"] + 1,
                        res_first=c["res_first"].at[qidx].set(c["n_iter"]),
                        **upd)

        @scoped("sched.admit")
        def admit_paged(c):
            """Grant blocks + arm the slot; the prompt streams in through
            the prefill branch.  The free list is the refcount vector
            itself: argsort(free-first, by id) makes the grant
            deterministic, and the admission gate already guaranteed
            enough zeros exist."""
            st, qidx = c["st"], c["q_head"]
            bs, n_tbl, NB = paged.block_size, paged.n_tbl, paged.n_blocks
            slot = jnp.argmin(self._occupied(st))
            rid, max_new, stop = (q_meta[qidx, 0], q_meta[qidx, 1],
                                  q_meta[qidx, 2])
            src = jnp.clip(q_meta[qidx, 4], 0, n_queue - 1)
            n_sh, max_blk = q_meta[qidx, 5], q_meta[qidx, 7]
            pins = q_pins[qidx]                              # (n_tbl,)
            ar_nb = jnp.arange(NB, dtype=jnp.int32)
            order = jnp.argsort(
                jnp.where(st["ref"] == 0, ar_nb, NB + ar_nb)).astype(jnp.int32)
            j = jnp.arange(n_tbl, dtype=jnp.int32)
            fresh = order[jnp.clip(j - n_sh, 0, NB - 1)]
            shared = c["req_tables"][src]
            tbl_row = jnp.where(
                j < n_sh, shared,
                jnp.where(j < max_blk, fresh, 0)).astype(jnp.int32)
            # fresh blocks come up at ref 1 (+ pins for later chains that
            # pass through them); shared blocks were pre-pinned by their
            # materializer, so the sharer adds nothing here
            is_fresh = (j >= n_sh) & (j < max_blk)
            tgt = jnp.where(is_fresh, tbl_row, NB)
            ref = st["ref"].at[tgt].add(
                jnp.where(is_fresh, 1 + pins, 0), mode="drop")
            used = jnp.sum((ref > 0).astype(jnp.int32)) - 1  # - trash pin
            cache = lm.reset_slot(st["cache"], slot)
            # a sharer starts its chunk walk at the last chunk boundary
            # inside the shared region: the few recomputed rows write
            # values bit-identical to what the donor already materialized
            s0 = (n_sh * bs) // self.prefill_chunk * self.prefill_chunk
            cache = dict(cache,
                         table=cache["table"].at[slot].set(tbl_row),
                         pos=cache["pos"].at[slot].set(s0))
            k0 = jax.random.fold_in(self._base_key, rid)
            st = dict(st, cache=cache, ref=ref,
                      filling=st["filling"].at[slot].set(True),
                      live=st["live"].at[slot].set(False),
                      pending=st["pending"].at[slot].set(False),
                      n_gen=st["n_gen"].at[slot].set(0),
                      max_new=st["max_new"].at[slot].set(max_new),
                      stop=st["stop"].at[slot].set(stop),
                      out=st["out"].at[slot].set(self.pad_token),
                      keys=st["keys"].at[slot].set(k0),
                      occupant=st["occupant"].at[slot].set(qidx),
                      n_alloc=st["n_alloc"].at[slot].set(max_blk))
            upd = {}
            if self.obs is not None:
                o = obs_rings.ring_push(c["obs"], obs_rings.EV_ADMIT,
                                        rid, c["n_iter"])
                o = obs_rings.ctr_add(o, obs_rings.CTR_PREFIX_BLOCKS, n_sh)
                o = obs_rings.ctr_add(o, obs_rings.CTR_SHARED_ADMITS,
                                      (n_sh > 0).astype(jnp.int32))
                upd["obs"] = dict(o, tick_tok=jnp.zeros((), jnp.int32))
            return dict(c, st=st, q_head=qidx + 1,
                        n_admits=c["n_admits"] + 1,
                        req_tables=c["req_tables"].at[qidx].set(tbl_row),
                        peak_blocks=jnp.maximum(c["peak_blocks"], used),
                        **upd)

        @scoped("sched.prefill")
        def prefill_chunk(c):
            """Advance the first filling slot by one chunk; the final
            chunk samples the first token exactly as arm_slot would
            (same key split, same logits row) and flips the slot live."""
            st = c["st"]
            C = self.prefill_chunk
            slot = jnp.argmax(st["filling"])
            qidx = st["occupant"][slot]
            plen = q_meta[qidx, 3]
            start = st["cache"]["pos"][slot]
            chunk = jax.lax.dynamic_slice(q_toks, (qidx, start), (1, C))
            with self._taps() as fr:
                logits, cache = lm.prefill_chunk_into_slot(
                    params, cfg, chunk, st["cache"], slot)
            if self.obs is not None:
                clip = taps.drain_sum(fr, "adc_clip")
            done = (start + C) >= plen
            row = jnp.clip(plen - 1 - start, 0, C - 1)
            lg = jax.lax.dynamic_slice(
                logits, (0, row, 0), (1, 1, logits.shape[-1]))[:, 0]
            k_next, k_use = jax.random.split(st["keys"][slot])
            tok = self._sample(lg, k_use[None])[0]
            fin0 = (tok == st["stop"][slot]) | (st["max_new"][slot] <= 1)
            # the final chunk ran to the padded span; commit pos = plen so
            # decode writes land right after the true prompt (the span's
            # padding rows sit beyond the validity horizon until decode
            # overwrites them)
            pos_new = jnp.where(done, plen, start + C)
            cache = dict(cache, pos=cache["pos"].at[slot].set(pos_new))
            st = dict(
                st, cache=cache,
                last_tok=st["last_tok"].at[slot, 0].set(
                    jnp.where(done, tok, st["last_tok"][slot, 0])),
                out=st["out"].at[slot, 0].set(
                    jnp.where(done, tok, st["out"][slot, 0])),
                n_gen=st["n_gen"].at[slot].set(
                    jnp.where(done, 1, 0)),
                keys=st["keys"].at[slot].set(
                    jnp.where(done, k_next, st["keys"][slot])),
                live=st["live"].at[slot].set(done & ~fin0),
                pending=st["pending"].at[slot].set(done & fin0),
                filling=st["filling"].at[slot].set(~done))
            upd = {}
            if self.obs is not None:
                # first-token stamp at EXACTLY the site that sets
                # res_first: the final chunk samples the first token
                o = obs_rings.ring_push(c["obs"], obs_rings.EV_FIRST,
                                        q_meta[qidx, 0], c["n_iter"],
                                        do=done)
                o = obs_rings.ctr_add(o, obs_rings.CTR_ADC_CLIP, clip)
                upd["obs"] = dict(o, tick_tok=done.astype(jnp.int32))
            upd.update(self._moe_counts(c, fr))
            return dict(c, st=st, last_pf=jnp.bool_(True),
                        n_pf=c["n_pf"] + 1,
                        pf_done=c["pf_done"].at[qidx].set(
                            c["pf_done"][qidx] | done),
                        res_first=c["res_first"].at[qidx].set(
                            jnp.where(done, c["n_iter"],
                                      c["res_first"][qidx])),
                        **upd)

        def step_core(c):
            upd = (dict(last_pf=jnp.bool_(False)) if paged is not None
                   else {})
            if self.draft_k:
                if len(self._rungs) > 1:
                    ema = c["acc_ema"]
                    R = len(self._rungs)
                    idx = jnp.where(ema > 0.8, 0,
                                    jnp.where(ema > 0.4, min(1, R - 1),
                                              R - 1))
                    st, drafted, accepted = taps.switch(
                        idx,
                        [lambda s, k=k: self._spec_step(params, s, k)
                         for k in self._rungs],
                        c["st"])
                else:
                    st, drafted, accepted = self._spec_step(
                        params, c["st"], self.draft_k)
                rate = (accepted.astype(jnp.float32)
                        / jnp.maximum(drafted, 1).astype(jnp.float32))
                ema = jnp.where(drafted > 0,
                                0.8 * c["acc_ema"] + 0.2 * rate,
                                c["acc_ema"])
                return dict(c, st=st, n_steps=c["n_steps"] + 1,
                            n_drafted=c["n_drafted"] + drafted,
                            n_accepted=c["n_accepted"] + accepted,
                            acc_ema=ema, **upd)
            return dict(c, st=self._step_fn(params, c["st"]),
                        n_steps=c["n_steps"] + 1, **upd)

        @scoped("sched.step")
        def step(c):
            if self.obs is None:
                with self._taps() as fr:
                    c2 = step_core(c)
                return dict(c2, **self._moe_counts(c, fr))
            n_gen0 = jnp.sum(c["st"]["n_gen"])
            with taps.collect() as fr:
                c2 = step_core(c)
            c2 = dict(c2, **self._moe_counts(c, fr))
            # n_gen is monotone across a decode step / spec round, so
            # the delta is exactly the tokens this iteration emitted
            # (variable 1..K+1 per live slot in spec mode)
            tok = jnp.sum(c2["st"]["n_gen"]) - n_gen0
            o = obs_rings.ctr_add(c2["obs"], obs_rings.CTR_ADC_CLIP,
                                  taps.drain_sum(fr, "adc_clip"))
            return dict(c2, obs=dict(o, tick_tok=tok))

        st = c["st"]
        if self.obs is not None:
            # pre-branch occupancy: the decoders that waited (or ran)
            # through this iteration, for the stall/occupancy samples
            live0 = jnp.sum(st["live"].astype(jnp.int32))
            drafted0, accepted0 = c["n_drafted"], c["n_accepted"]
        qh = jnp.minimum(c["q_head"], n_queue - 1)
        arrived = q_meta[qh, 6] <= c["n_iter"]
        can_admit = ((c["q_head"] < n_queue)
                     & ~jnp.all(self._occupied(st)) & arrived)
        if paged is not None:
            n_sh = q_meta[qh, 5]
            src = jnp.clip(q_meta[qh, 4], 0, n_queue - 1)
            free_cnt = jnp.sum((st["ref"] == 0).astype(jnp.int32))
            can_admit &= (n_sh == 0) | c["pf_done"][src]
            can_admit &= free_cnt >= (q_meta[qh, 7] - n_sh)
            # prefill/step alternation: a filling slot always progresses,
            # but never starves live decoders for more than one iteration
            want_pf = (jnp.any(st["filling"])
                       & (~jnp.any(st["live"]) | ~c["last_pf"]))
            branch = jnp.where(
                jnp.any(st["pending"]), 0,
                jnp.where(can_admit, 1, jnp.where(want_pf, 2, 3)))
            c = jax.lax.switch(branch,
                               [harvest, admit_paged, prefill_chunk, step], c)
        else:
            branch = jnp.where(jnp.any(st["pending"]), 0,
                               jnp.where(can_admit, 1, 2))
            c = jax.lax.switch(branch, [harvest, admit_contiguous, step], c)
        if self.obs is not None:
            free = (jnp.sum((c["st"]["ref"] == 0).astype(jnp.int32))
                    if paged is not None else jnp.zeros((), jnp.int32))
            c = dict(c, obs=obs_rings.iter_tick(
                c["obs"], c["n_iter"], branch, live0,
                c["n_drafted"] - drafted0, c["n_accepted"] - accepted0,
                free))
        c = dict(c, n_iter=c["n_iter"] + 1)
        cont = jnp.any(self._occupied(c["st"])) | (c["q_head"] < n_queue)
        return c, branch, cont

    def _lower_loop(self, n_queue: int):
        """Lower (don't compile) the whole-workload loop for a queue of
        n_queue requests.

        Metrics OFF: this function is required to produce StableHLO text
        byte-identical to the pre-telemetry scheduler -- the sha256 of
        ``loop_hlo_text`` is the zero-overhead-when-off gate in
        benchmarks/serve_bench.py, so every telemetry hook below is a
        Python-level conditional, never a traced-then-unused value.

        Metrics ON: the telemetry rings enter as their own donated
        argument (the only carry members that appear unchanged in shape
        among the outputs, so donation actually aliases -- the
        OBS-RING-DONATION lint checks this) and leave as ``out["obs"]``
        for ``harvest_obs``.
        """
        def serve_body(params, carry, q_toks, q_meta, q_pins):
            def body(c):
                return self._step_once(params, c, q_toks, q_meta, q_pins,
                                       n_queue)[0]

            def cond(c):
                return (jnp.any(self._occupied(c["st"]))
                        | (c["q_head"] < n_queue))

            c = jax.lax.while_loop(cond, body, carry)
            out = dict(res_out=c["res_out"], res_n=c["res_n"],
                       res_iter=c["res_iter"], res_first=c["res_first"],
                       n_iter=c["n_iter"], n_steps=c["n_steps"],
                       n_admits=c["n_admits"], n_drafted=c["n_drafted"],
                       n_accepted=c["n_accepted"])
            if self.paged is not None:
                out.update(n_pf=c["n_pf"], peak_blocks=c["peak_blocks"])
            if self._moe:
                out.update({k: c[k] for k in MOE_TAPS})
            if self.obs is not None:
                out["obs"] = c["obs"]
            return out

        carry = self._init_carry(n_queue, with_obs=False)
        qt = _i32(np.zeros((n_queue, self._p_pad)))
        qm = _i32(np.zeros((n_queue, _QM_COLS)))
        qp = _i32(np.zeros((n_queue, self._n_pin_cols())))
        if self.obs is not None:
            def serve_loop(params, carry, obs, q_toks, q_meta, q_pins):
                return serve_body(params, dict(carry, obs=obs), q_toks,
                                  q_meta, q_pins)
            return jax.jit(serve_loop, donate_argnums=(2,)).lower(
                self._params, carry, obs_rings.init_obs_state(self.obs),
                qt, qm, qp)

        def serve_loop(params, carry, q_toks, q_meta, q_pins):
            return serve_body(params, carry, q_toks, q_meta, q_pins)

        # no donation: the loop's outputs are only the result buffers, so
        # the input state can't alias anything (XLA would warn and ignore)
        return jax.jit(serve_loop).lower(self._params, carry, qt, qm, qp)

    def loop_hlo_text(self, n_queue: int) -> str:
        """Pre-optimization StableHLO of the serve loop (fingerprint
        input for the zero-overhead-when-off gate, obs/fingerprint.py).
        It carries no named scopes: those live in debug locations."""
        return self._lower_loop(n_queue).as_text()

    def compiled_loop_text(self, n_queue: int) -> str:
        """Optimised HLO text of the compiled serve loop for a queue of
        ``n_queue`` (compiled first if need be).  Every instruction's
        ``metadata={op_name="..."}`` holds its JAX name stack, the
        ``sched.*`` branch and ``lm.*`` layer scopes included, so a
        profiler trace's device ops can be put down to them by
        instruction name."""
        return self.compile_for(n_queue).as_text()

    # -- segmented (guarded) serve loop --------------------------------

    def _lower_segment(self, n_queue: int):
        """Lower the BUDGET-BOUNDED serve loop the resilience driver runs
        (resilience/failover.GuardedServer).

        Identical to ``_lower_loop``'s body except for two things:

        * the while condition also requires ``n_iter < budget``, and the
          executable returns the FULL carry -- so the host can run the
          workload as a sequence of device-resident segments, reading the
          health counters (and possibly switching to a pack-compatible
          sibling scheduler's executable) at each boundary.  Within a
          segment the one-host-sync contract holds exactly as in ``run``;
          the budget is the watchdog's sampling cadence.
        * the body is traced under ``resilience.faults.clock(n_iter)``:
          with a fault model armed at lower time, the injected drift's
          severity schedule follows the DEVICE iteration counter, so one
          executable covers the whole mid-stream drift scenario -- zero
          retraces, zero recompiles as severity evolves.  With no model
          armed the clock is a Python-level no-op and the segment body
          lowers the exact ops of the plain loop (RES-OFF-PATH gates
          this by fingerprint).

        The telemetry rings stay INLINE in the carry (unlike
        ``_lower_loop``'s separately-donated obs argument): the carry
        round-trips through this executable every segment, so donation
        of the whole carry aliases the rings anyway.
        """
        def seg_loop(params, carry, budget, q_toks, q_meta, q_pins):
            def body(c):
                with rfaults.clock(c["n_iter"]):
                    return self._step_once(params, c, q_toks, q_meta,
                                           q_pins, n_queue)[0]

            def cond(c):
                work = (jnp.any(self._occupied(c["st"]))
                        | (c["q_head"] < n_queue))
                return work & (c["n_iter"] < budget)

            return jax.lax.while_loop(cond, body, carry)

        carry = self._init_carry(n_queue, with_obs=True)
        qt = _i32(np.zeros((n_queue, self._p_pad)))
        qm = _i32(np.zeros((n_queue, _QM_COLS)))
        qp = _i32(np.zeros((n_queue, self._n_pin_cols())))
        return jax.jit(seg_loop, donate_argnums=(1,)).lower(
            self._params, carry, _i32(0), qt, qm, qp)

    def segment_hlo_text(self, n_queue: int) -> str:
        """Pre-optimization StableHLO of the segmented loop (fingerprint
        input for the fault-off-path gate in resilience tests/lint)."""
        return self._lower_segment(n_queue).as_text()

    def compile_segment(self, n_queue: int):
        """Compile (and cache) the segmented loop for a queue length."""
        key = ("seg", n_queue)
        if key not in self._loops:
            self._loops[key] = self._lower_segment(n_queue).compile()
        return self._loops[key]

    def _build_loop(self, n_queue: int):
        """Compile the whole-workload loop for a queue of n_queue requests."""
        return self._lower_loop(n_queue).compile()

    def _build_iter(self, n_queue: int):
        """Compile ONE scheduler iteration (the switch) for the
        instrumented runner.  The carry is donated: the host steps the
        loop, so the pool state round-trips through this executable every
        iteration."""
        def one(params, carry, q_toks, q_meta, q_pins):
            c, branch, cont = self._step_once(params, carry, q_toks,
                                              q_meta, q_pins, n_queue)
            return c, branch, cont

        carry = self._init_carry(n_queue)
        qt = _i32(np.zeros((n_queue, self._p_pad)))
        qm = _i32(np.zeros((n_queue, _QM_COLS)))
        qp = _i32(np.zeros((n_queue, self._n_pin_cols())))
        return (jax.jit(one, donate_argnums=(1,))
                .lower(self._params, carry, qt, qm, qp).compile())

    def _n_pin_cols(self) -> int:
        return self.paged.n_tbl if self.paged is not None else 1

    def _init_state(self) -> Dict:
        B, cap = self.slots, self.cap
        st = dict(
            last_tok=jnp.full((B, 1), self.pad_token, jnp.int32),
            live=jnp.zeros((B,), jnp.bool_),
            n_gen=jnp.zeros((B,), jnp.int32),
            max_new=jnp.zeros((B,), jnp.int32),
            stop=jnp.full((B,), -1, jnp.int32),
            out=jnp.full((B, cap), self.pad_token, jnp.int32),
            keys=jnp.zeros((B, 2), jnp.uint32),
            pending=jnp.zeros((B,), jnp.bool_),
            occupant=jnp.zeros((B,), jnp.int32),
        )
        if self.paged is not None:
            lay = self.paged
            st["cache"] = lm.init_paged_cache(
                self.cfg, B, lay.n_blocks, lay.block_size, lay.n_tbl)
            st["ref"] = jnp.zeros((lay.n_blocks,), jnp.int32).at[0].set(1)
            st["filling"] = jnp.zeros((B,), jnp.bool_)
            st["n_alloc"] = jnp.zeros((B,), jnp.int32)
        else:
            st["cache"] = lm.init_cache(self.cfg, B, self.max_seq)
        return st

    def _init_carry(self, n_queue: int, with_obs: bool = True) -> Dict:
        """``with_obs=False`` builds the obs-less carry the whole-loop
        executable takes (its telemetry rings enter as a separately
        donated argument, see ``_lower_loop``); the single-iteration
        executable keeps them in the carry it round-trips."""
        carry = dict(
            st=self._init_state(), q_head=_i32(0), n_iter=_i32(0),
            n_steps=_i32(0), n_admits=_i32(0), n_drafted=_i32(0),
            n_accepted=_i32(0), acc_ema=jnp.float32(1.0),
            res_out=jnp.full((n_queue, self.cap), self.pad_token, jnp.int32),
            res_n=jnp.zeros((n_queue,), jnp.int32),
            res_iter=jnp.zeros((n_queue,), jnp.int32),
            res_first=jnp.zeros((n_queue,), jnp.int32),
        )
        if self.paged is not None:
            carry.update(
                last_pf=jnp.bool_(False), n_pf=_i32(0),
                peak_blocks=_i32(0),
                pf_done=jnp.zeros((n_queue,), jnp.bool_),
                req_tables=jnp.zeros((n_queue, self.paged.n_tbl),
                                     jnp.int32))
        if self._moe:
            carry.update({k: _i32(0) for k in MOE_TAPS})
        if self.obs is not None and with_obs:
            carry["obs"] = obs_rings.init_obs_state(self.obs)
        return carry

    def _taps(self):
        """The tap collector a model pass of the loop runs under: every
        name with telemetry on, the held-expert row counts where the
        model has held-expert layers, none otherwise (a frame that is
        never opened adds no operation)."""
        if self.obs is not None:
            return taps.collect()
        if self._moe:
            return taps.collect(MOE_TAPS)
        return contextlib.nullcontext({})

    def _moe_counts(self, c, frame) -> Dict[str, jax.Array]:
        """The carry's held-expert counts plus what a pass emitted."""
        if not self._moe:
            return {}
        return {k: c[k] + taps.drain_sum(frame, k) for k in MOE_TAPS}

    # -- host-side staging ---------------------------------------------

    def _check(self, requests: Sequence[Request]):
        for r in requests:
            if r.max_new_tokens > self.cap:
                raise ValueError(
                    f"request {r.rid}: max_new_tokens {r.max_new_tokens} "
                    f"> cap {self.cap}")
            if self.paged is not None:
                plen = len(r.prompt)
                if not (1 <= plen <= self.prompt_len):
                    raise ValueError(
                        f"request {r.rid}: prompt len {plen} outside "
                        f"[1, {self.prompt_len}]")
                if (self.cfg.family in ("ssm", "hybrid")
                        and plen % self.prefill_chunk):
                    raise ValueError(
                        f"request {r.rid}: prompt len {plen} must be a "
                        f"multiple of prefill_chunk {self.prefill_chunk} "
                        f"for the {self.cfg.family!r} family (a garbage "
                        "chunk tail would corrupt the recurrent state)")
                need = max(cdiv(plen, self.prefill_chunk)
                           * self.prefill_chunk,
                           plen + r.max_new_tokens - 1 + self.draft_k)
                if need > self.paged.tokens_per_slot:
                    raise ValueError(
                        f"request {r.rid}: needs {need} addressable tokens"
                        f" > table capacity {self.paged.tokens_per_slot}")
            elif len(r.prompt) != self.prompt_len:
                raise ValueError(
                    f"request {r.rid}: prompt len {len(r.prompt)} != "
                    f"scheduler prompt_len {self.prompt_len}")
        if len({r.rid for r in requests}) != len(requests):
            raise ValueError("request rids must be unique within a run")

    def _stage(self, requests: Sequence[Request],
               arrival_iters: Optional[Sequence[int]] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Stage the workload into the (q_toks, q_meta, q_pins) device
        buffers, resolving prefix sharing and per-request block grants."""
        n = len(requests)
        toks = np.full((n, self._p_pad), self.pad_token, np.int32)
        for i, r in enumerate(requests):
            toks[i, :len(r.prompt)] = np.asarray(r.prompt, np.int32)
        arr = (np.zeros(n, np.int64) if arrival_iters is None
               else np.asarray(arrival_iters, np.int64))
        if arrival_iters is not None and len(arr) != n:
            raise ValueError("arrival_iters length != len(requests)")
        meta = np.zeros((n, _QM_COLS), np.int64)
        if self.paged is not None:
            lay, C = self.paged, self.prefill_chunk
            enable = (self.prefix_sharing
                      and self.cfg.family not in ("ssm", "hybrid"))
            with span("sched.plan_prefix"):
                plan = plan_prefix_sharing(
                    [np.asarray(r.prompt) for r in requests],
                    lay.block_size, lay.n_tbl, enable=enable)
            self.last_prefix_plan = plan
            pins = plan.pin_counts.astype(np.int64)
            max_blks = np.zeros(n, np.int64)
            for i, r in enumerate(requests):
                plen = len(r.prompt)
                need = max(cdiv(plen, C) * C,
                           plen + r.max_new_tokens - 1 + self.draft_k)
                max_blks[i] = lay.blocks_for(need)
            # static no-deadlock guarantee: even with every pinned shared
            # block held live by a not-yet-admitted sharer, the pool can
            # satisfy the largest single fresh grant
            n_pinned = int(np.sum(plan.pin_counts > 0))
            worst = int(np.max(max_blks - plan.n_shared_blocks, initial=0))
            if worst + n_pinned > lay.n_blocks - 1:
                raise ValueError(
                    f"paged pool too small: worst-case fresh grant {worst}"
                    f" + {n_pinned} pinned shared blocks > "
                    f"{lay.n_blocks - 1} allocatable blocks")
            for i, r in enumerate(requests):
                meta[i] = [r.rid, r.max_new_tokens, r.stop_token,
                           len(r.prompt), plan.share_src[i],
                           plan.n_shared_blocks[i], arr[i], max_blks[i]]
        else:
            pins = np.zeros((n, 1), np.int64)
            for i, r in enumerate(requests):
                meta[i] = [r.rid, r.max_new_tokens, r.stop_token,
                           len(r.prompt), -1, 0, arr[i], 0]
        return _i32(toks), _i32(meta), _i32(pins)

    def compile_for(self, n_requests: int, lockstep: bool = False,
                    instrumented: bool = False):
        """Pre-compile the serve loop for a queue length (off the clock);
        ``lockstep=True`` also pre-compiles the baseline executables so a
        timed run_lockstep never pays compile, ``instrumented=True`` the
        single-iteration executable run_instrumented steps.  Each build
        is a ``launch.compile`` span (a load, with a warm persistent
        compile cache)."""
        if n_requests not in self._loops:
            with span("launch.compile", what="loop", q=n_requests):
                self._loops[n_requests] = self._build_loop(n_requests)
        if lockstep and self._lockstep_exes is None:
            with span("launch.compile", what="lockstep"):
                self._lockstep_executables()
        if instrumented and n_requests not in self._iters:
            with span("launch.compile", what="iter", q=n_requests):
                self._iters[n_requests] = self._build_iter(n_requests)
        return self._loops[n_requests]

    def run(self, requests: Sequence[Request],
            arrival_iters: Optional[Sequence[int]] = None) -> ServeReport:
        """Serve ``requests`` to completion.  ``arrival_iters`` holds an
        open-loop arrival schedule in LOOP-ITERATION units (the device
        clock): request i is not admitted before iteration
        arrival_iters[i].  Default: everything arrives at t=0.

        The call is host span ``sched.run`` (attrs: the scheduler's batch
        serial number and Q) over ``sched.check``, ``sched.stage``,
        ``sched.carry``, ``sched.loop`` (dispatch to block_until_ready)
        and ``sched.fetch`` (results to host, report built); their
        seconds land in ``ServeReport.host_s`` and the compiles the call
        made in ``n_compiles``."""
        self._n_runs += 1
        compiles0 = backend_compiles()
        with get_tracer().collect() as spans:
            with span("sched.run", batch=self._n_runs, q=len(requests)):
                report = self._run(requests, arrival_iters)
        for s in spans:
            report.host_s[s["name"]] = (report.host_s.get(s["name"], 0.0)
                                        + s["dur_s"])
        report.n_compiles = backend_compiles() - compiles0
        return report

    def _run(self, requests, arrival_iters) -> ServeReport:
        with span("sched.check"):
            self._check(requests)
        loop = self.compile_for(len(requests))
        with span("sched.stage"):
            q_toks, q_meta, q_pins = self._stage(requests, arrival_iters)
        with span("sched.carry"):
            carry = jax.block_until_ready(
                self._init_carry(len(requests), with_obs=False))
            args = (q_toks, q_meta, q_pins)
            if self.obs is not None:
                args = (jax.block_until_ready(
                    obs_rings.init_obs_state(self.obs)),) + args
        with span("sched.loop"):
            t0 = time.time()                # compile + staging off the clock
            res = jax.block_until_ready(loop(self._params, carry, *args))
            wall = time.time() - t0
        with span("sched.fetch"):
            return self._report(requests, res, wall)

    def _attn_geometry(self) -> Dict[str, int]:
        """The report's paged decode-attention geometry, if any."""
        if self.paged is None or self.cfg.family == "ssm":
            return {}
        return dict(block_size=self.paged.block_size, n_tbl=self.paged.n_tbl)

    def _report(self, requests, res, wall: float) -> ServeReport:
        res_out, res_n = np.asarray(res["res_out"]), np.asarray(res["res_n"])
        res_iter, n_iter = np.asarray(res["res_iter"]), int(res["n_iter"])
        res_first = np.asarray(res["res_first"])
        done = [FinishedRequest(
            rid=r.rid, tokens=res_out[i, :res_n[i]].copy(),
            latency_s=wall * int(res_iter[i]) / max(n_iter, 1),
            finish_iter=int(res_iter[i]), first_iter=int(res_first[i]),
            prompt_len=len(r.prompt))
            for i, r in enumerate(requests)]
        report = ServeReport(finished=done, wall_s=wall,
                             n_steps=int(res["n_steps"]),
                             n_admits=int(res["n_admits"]), slots=self.slots,
                             n_drafted=int(res["n_drafted"]),
                             n_accepted=int(res["n_accepted"]),
                             n_pf=int(res.get("n_pf", 0)),
                             peak_blocks=int(res.get("peak_blocks", 0)),
                             n_iter=n_iter, **self._attn_geometry())
        if self._moe:
            report.take_moe_counts(res)
        if self.obs is not None:
            report.obs = obs_rings.harvest_obs(
                self.obs, jax.device_get(res["obs"]), n_iter=n_iter,
                wall_s=wall, slots=self.slots,
                n_steps=report.n_steps, n_drafted=report.n_drafted,
                n_accepted=report.n_accepted,
                paged=self.paged is not None)
        return report

    def run_instrumented(self, requests: Sequence[Request],
                         arrival_iters: Optional[Sequence[int]] = None
                         ) -> Tuple[ServeReport, Dict[str, np.ndarray]]:
        """Serve with a host timestamp on EVERY loop iteration: the same
        compiled iteration body the while_loop runs, stepped from the
        host.  Wall time is inflated by one device->host sync per
        iteration, so use ``run`` for throughput and this for latency
        structure: real TTFT per request and the per-iteration duration
        series (whose step-branch percentiles are the serve benchmark's
        decode-stall gate).  Returns (report, timeline) where timeline
        has ``branch`` (the switch index per iteration) and ``iter_s``."""
        self._check(requests)
        n = len(requests)
        self.compile_for(n, instrumented=True)
        it = self._iters[n]
        q_toks, q_meta, q_pins = self._stage(requests, arrival_iters)
        c = jax.block_until_ready(self._init_carry(n))
        branches: List[int] = []
        iter_s: List[float] = []
        t0 = time.time()
        t_prev = t0
        while True:
            c, br, cont = it(self._params, c, q_toks, q_meta, q_pins)
            br, cont = int(br), bool(cont)          # per-iteration sync
            t_now = time.time()
            iter_s.append(t_now - t_prev)
            t_prev = t_now
            branches.append(br)
            if not cont:
                break
        wall = time.time() - t0
        res_out = np.asarray(c["res_out"])
        res_n = np.asarray(c["res_n"])
        res_iter = np.asarray(c["res_iter"])
        res_first = np.asarray(c["res_first"])
        cum = np.cumsum(iter_s)
        at = lambda k: float(cum[min(int(k), len(cum) - 1)])
        done = [FinishedRequest(
            rid=r.rid, tokens=res_out[i, :res_n[i]].copy(),
            latency_s=at(res_iter[i]), finish_iter=int(res_iter[i]),
            first_iter=int(res_first[i]), ttft_s=at(res_first[i]),
            prompt_len=len(r.prompt))
            for i, r in enumerate(requests)]
        report = ServeReport(
            finished=done, wall_s=wall, n_steps=int(c["n_steps"]),
            n_admits=int(c["n_admits"]), slots=self.slots,
            n_drafted=int(c["n_drafted"]), n_accepted=int(c["n_accepted"]),
            n_pf=int(c.get("n_pf", 0)),
            peak_blocks=int(c.get("peak_blocks", 0)), n_iter=len(branches),
            **self._attn_geometry())
        if self._moe:
            report.take_moe_counts(c)
        if self.obs is not None:
            # the instrumented runner's carry keeps the rings inline
            # (the host round-trips it), so harvest reads them directly
            report.obs = obs_rings.harvest_obs(
                self.obs, jax.device_get(c["obs"]),
                n_iter=len(branches), wall_s=wall, slots=self.slots,
                n_steps=report.n_steps, n_drafted=report.n_drafted,
                n_accepted=report.n_accepted,
                paged=self.paged is not None)
        timeline = dict(branch=np.asarray(branches, np.int32),
                        iter_s=np.asarray(iter_s))
        return report, timeline

    def run_lockstep(self, requests: Sequence[Request]) -> ServeReport:
        """Lock-step baseline through the SAME per-slot machinery: waves
        of ``slots`` requests all decode to the wave's longest budget, and
        per-request stop handling is applied post-hoc by truncation -- the
        pre-scheduler serve.py discipline, isolated so the benchmark delta
        is pure scheduling (identical kernels, admit path and step math)."""
        if self.paged is not None:
            raise ValueError("run_lockstep is the contiguous baseline; "
                             "build the scheduler without paged=")
        self._check(requests)
        admit, drain = self._lockstep_executables()
        state = self._init_state()
        done: List[FinishedRequest] = []
        n_steps = n_admits = 0
        t0 = time.time()
        for w0 in range(0, len(requests), self.slots):
            wave = list(requests[w0:w0 + self.slots])
            wave_max = max(r.max_new_tokens for r in wave)
            for slot, r in enumerate(wave):
                # stop=-1, budget=wave_max: every slot decodes the full wave
                state = admit(
                    self._params, state, _i32(slot),
                    _i32(np.asarray(r.prompt)[None, :]), _i32(r.rid),
                    _i32(wave_max), _i32(-1))
                n_admits += 1
            state = drain(self._params, state, _i32(wave_max - 1))
            n_steps += wave_max - 1
            out_h = np.asarray(state["out"])
            t_wave = time.time() - t0
            for slot, r in enumerate(wave):
                toks = out_h[slot, :wave_max]
                n = r.max_new_tokens
                if r.stop_token >= 0:
                    hits = np.nonzero(toks == r.stop_token)[0]
                    if hits.size:
                        n = min(n, int(hits[0]) + 1)
                done.append(FinishedRequest(rid=r.rid, tokens=toks[:n].copy(),
                                            latency_s=t_wave,
                                            finish_iter=n_steps + n_admits))
        return ServeReport(finished=done, wall_s=time.time() - t0,
                           n_steps=n_steps, n_admits=n_admits,
                           slots=self.slots)


def mixed_length_requests(n: int, prompt_len: int, vocab_size: int,
                          stop_lengths: Sequence[int] = (4, 16, 8, 12),
                          seed: int = 0) -> List[Request]:
    """Synthetic mixed-length workload: request i stops after
    ``stop_lengths[i % len]`` tokens.  The interleaving is deliberately
    adversarial for lock-step waves (short and long requests share one)."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab_size, prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=int(stop_lengths[i % len(stop_lengths)]))
            for i in range(n)]
