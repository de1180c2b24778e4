"""Paged KV cache: block-allocator properties, paged-vs-contiguous token
parity through the continuous-batching scheduler, shared-prefix reuse and
chunked prefill (launch/paging.py, launch/scheduler.py paged mode,
models/lm.py paged cache plumbing, kernels/paged_attn).

The load-bearing contract everywhere: paging changes WHERE KV rows live,
never a single token.  Every scheduler test compares the paged pool
(single-shot, chunked, prefix-shared, alternate geometry) against the
contiguous scheduler or a paged reference run and asserts BIT-identical
tokens, greedy and sampled.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    from _prop_fallback import given, settings, st

from repro.configs import get_config
from repro.launch.paging import (BlockAllocator, PagedLayout,
                                 contiguous_kv_bytes, plan_prefix_sharing)
from repro.launch.scheduler import (ContinuousBatchingScheduler,
                                    FinishedRequest, Request, ServeReport)
from repro.models import lm


def _params(arch, seed=0):
    cfg = get_config(arch, smoke=True)
    params, _ = lm.init(jax.random.PRNGKey(seed), cfg)
    return params, cfg


def _mixed_requests(cfg, n, plens, caps, seed, stop=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        plens[i % len(plens)],
                                        dtype=np.int32),
                    max_new_tokens=caps[i % len(caps)], stop_token=stop)
            for i in range(n)]


# ---------------------------------------------------------------------------
# block allocator properties (host reference model)
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_allocator_random_ops_never_leak_or_alias(seed):
    """Random alloc/share/write(CoW)/free sequences keep every invariant
    the on-device allocator relies on: refcounts non-negative, the free
    list holds exactly the ref==0 blocks with no duplicates, the trash
    block stays pinned, and no two live chains alias a block they both
    think they own exclusively (CoW splits before a shared write)."""
    rng = np.random.default_rng(seed)
    alloc = BlockAllocator(n_blocks=24)
    chains = []                                       # list of block lists
    for _ in range(120):
        op = rng.integers(0, 4)
        if op == 0 and alloc.n_free >= 1:             # alloc a new chain
            n = int(rng.integers(1, min(4, alloc.n_free) + 1))
            chains.append(alloc.alloc(n))
        elif op == 1 and chains:                      # share a prefix
            src = chains[int(rng.integers(0, len(chains)))]
            if src:
                k = int(rng.integers(1, len(src) + 1))
                alloc.share(src[:k])
                chains.append(list(src[:k]))
        elif op == 2 and chains:                      # CoW write
            ci = int(rng.integers(0, len(chains)))
            if chains[ci]:
                bi = int(rng.integers(0, len(chains[ci])))
                try:
                    alloc.write(chains[ci], bi)
                except MemoryError:
                    pass                              # pool full: no split
        elif op == 3 and chains:                      # free a whole chain
            alloc.free(chains.pop(int(rng.integers(0, len(chains)))))
        alloc.check()
        # exclusivity: a ref==1 block appears in exactly one chain
        flat = [b for c in chains for b in c]
        for b in set(flat):
            if alloc.ref[b] == 1:
                assert flat.count(b) == 1, f"ref-1 block {b} aliased"
    for c in chains:
        alloc.free(c)
    alloc.check()
    assert alloc.n_free == 23                         # all but trash block


def test_allocator_rejects_double_free_and_bad_share():
    alloc = BlockAllocator(n_blocks=8)
    (b,) = alloc.alloc(1)
    alloc.free([b])
    with pytest.raises(ValueError):
        alloc.free([b])
    with pytest.raises(ValueError):
        alloc.share([b])                              # free block
    with pytest.raises(ValueError):
        alloc.share([0])                              # trash block
    with pytest.raises(MemoryError):
        alloc.alloc(8)                                # > pool - trash


def test_cow_write_splits_shared_block_only():
    alloc = BlockAllocator(n_blocks=8)
    donor = alloc.alloc(3)
    sharer = list(donor)
    alloc.share(sharer)
    nb = alloc.write(sharer, 1)
    assert sharer == [donor[0], nb, donor[2]]
    assert nb != donor[1]                             # split happened
    assert alloc.ref[donor[1]] == 1 and alloc.ref[nb] == 1
    assert alloc.write(sharer, 1) == nb               # exclusive: in place
    alloc.check()


# ---------------------------------------------------------------------------
# prefix-sharing planner
# ---------------------------------------------------------------------------


def test_prefix_plan_shares_full_blocks_only():
    rng = np.random.default_rng(0)
    sys_p = rng.integers(0, 100, 10, dtype=np.int32)
    a = np.concatenate([sys_p, [1, 2, 3]])
    b = np.concatenate([sys_p, [4, 5, 6]])
    c = rng.integers(0, 100, 13, dtype=np.int32)
    plan = plan_prefix_sharing([a, b, c], block_size=4, n_tbl=8)
    # 10 shared tokens = 2 full blocks (the half-filled third block is
    # recomputed, never shared); c shares nothing
    assert plan.share_src[0] == -1 and plan.n_shared_blocks[0] == 0
    assert plan.share_src[1] == 0 and plan.n_shared_blocks[1] == 2
    assert plan.share_src[2] == -1
    # the donor carries one pin per shared block for the one sharer
    assert plan.pin_counts[0, :2].tolist() == [1, 1]
    assert plan.pin_counts[0, 2:].sum() == 0
    # identical prompts share at most (plen-1)//bs blocks: the sharer
    # still recomputes the row its first sampled token conditions on
    plan2 = plan_prefix_sharing([a, a.copy()], block_size=4, n_tbl=8)
    assert plan2.n_shared_blocks[1] == (len(a) - 1) // 4
    off = plan_prefix_sharing([a, b], block_size=4, n_tbl=8, enable=False)
    assert (off.share_src == -1).all()


def test_paged_layout_bytes_accounting():
    cfg = get_config("qwen3-14b", smoke=True)
    lay = PagedLayout(block_size=4, n_tbl=8, n_blocks=32)
    assert lay.tokens_per_slot == 32
    assert lay.blocks_for(9) == 3
    # contiguous(slots*max_seq rows) == paged pool holding the same rows
    assert (contiguous_kv_bytes(cfg, slots=2, max_seq=64)
            == lay.kv_bytes(cfg, n_blocks=2 * lay.blocks_for(64)))
    assert lay.kv_bytes(cfg, n_blocks=4) < lay.kv_bytes(cfg)


# ---------------------------------------------------------------------------
# scheduler parity: paged == contiguous, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_paged_matches_contiguous_tokens(temperature):
    """Same exact-length workload through the contiguous pool and the
    paged pool (single-shot prefill): bitwise-identical tokens, greedy
    and sampled."""
    params, cfg = _params("qwen3-14b")
    P, CAP = 16, 10
    reqs = _mixed_requests(cfg, 5, [P], [6, 10, 4], seed=1)
    kw = dict(slots=2, prompt_len=P, max_new_cap=CAP,
              temperature=temperature, seed=7)
    want = ContinuousBatchingScheduler(params, cfg, **kw).run(
        reqs).tokens_by_rid()
    lay = PagedLayout(block_size=4, n_tbl=10, n_blocks=40)
    got = ContinuousBatchingScheduler(
        params, cfg, paged=lay, **kw).run(reqs).tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_chunked_shared_prefill_is_bit_exact(temperature):
    """Chunked prefill + shared-prefix reuse vs single-shot unshared
    paged prefill on a mixed-length multi-tenant workload: identical
    tokens, and the prefix plan actually shares blocks (the test would
    pass vacuously otherwise)."""
    params, cfg = _params("qwen3-14b")
    P, CAP = 16, 10
    rng = np.random.default_rng(2)
    sys_p = rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
    reqs = []
    for i in range(6):
        if i % 2 == 0:
            tail = rng.integers(0, cfg.vocab_size, [4, 2, 4][i // 2],
                                dtype=np.int32)
            prompt = np.concatenate([sys_p, tail])
        else:
            prompt = rng.integers(0, cfg.vocab_size, [9, 16, 13][i // 2],
                                  dtype=np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=[6, 10, 4][i % 3],
                            stop_token=3))
    plan = plan_prefix_sharing([r.prompt for r in reqs], 4, 10)
    assert plan.n_shared_blocks.max() == 3            # 12-token prefix
    lay = PagedLayout(block_size=4, n_tbl=10, n_blocks=40)
    kw = dict(slots=2, prompt_len=P, max_new_cap=CAP,
              temperature=temperature, seed=7, paged=lay)
    want = ContinuousBatchingScheduler(
        params, cfg, prefix_sharing=False, **kw).run(reqs).tokens_by_rid()
    got = ContinuousBatchingScheduler(
        params, cfg, prefill_chunk=8, prefix_sharing=True,
        **kw).run(reqs).tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])


def test_paged_pool_equals_solo_and_block_geometry_invariance():
    """A request's sampled tokens do not depend on pool companions,
    admission order, or the block geometry carrying its KV rows."""
    params, cfg = _params("qwen3-14b")
    P, CAP = 16, 8
    reqs = _mixed_requests(cfg, 4, [16, 9, 13], [6, 8], seed=3)
    kw = dict(slots=2, prompt_len=P, max_new_cap=CAP, temperature=0.7,
              seed=5, prefill_chunk=8)
    pool = ContinuousBatchingScheduler(
        params, cfg, paged=PagedLayout(4, 10, 40), **kw)
    tokens = pool.run(reqs).tokens_by_rid()
    for r in reqs[:2]:
        solo = pool.run([r]).tokens_by_rid()[r.rid]
        np.testing.assert_array_equal(tokens[r.rid], solo)
    alt = ContinuousBatchingScheduler(
        params, cfg, paged=PagedLayout(8, 5, 24), **kw)
    alt_tokens = alt.run(reqs).tokens_by_rid()
    for rid in tokens:
        np.testing.assert_array_equal(alt_tokens[rid], tokens[rid])


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "mamba2-130m"])
def test_paged_recurrent_families_bit_exact(arch):
    """Hybrid and pure-SSM families through the paged pool with chunked
    prefill: the recurrent state rides per-slot dense buffers (only
    attention KV is paged), decode steps must not corrupt a
    mid-prefill slot's recurrence, and tokens stay bit-identical to the
    contiguous scheduler."""
    params, cfg = _params(arch)
    reqs = _mixed_requests(cfg, 4, [16], [6, 9], seed=4)
    kw = dict(slots=2, prompt_len=16, max_new_cap=10, temperature=0.7,
              seed=5)
    want = ContinuousBatchingScheduler(params, cfg, **kw).run(
        reqs).tokens_by_rid()
    rep = ContinuousBatchingScheduler(
        params, cfg, paged=PagedLayout(4, 10, 40), prefill_chunk=8,
        **kw).run(reqs)
    got = rep.tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    # only zamba2's shared attention block reads the paged pool
    assert (rep.attn_live_block_frac > 0) == (arch == "zamba2-1.2b")


def test_paged_decode_kernel_tokens_match_fallback(monkeypatch):
    """The Pallas decode read (interpret mode here), which skips the
    blocks past each row's length and every block of a row the step does
    not decode, serves the same greedy tokens as the XLA gather fallback
    (its f32 PV differs from the fallback's bf16 PV in the last bits, so
    sampled tokens may part)."""
    params, cfg = _params("qwen3-14b")
    reqs = _mixed_requests(cfg, 4, [16, 9, 13], [6, 8], seed=3)
    kw = dict(slots=2, prompt_len=16, max_new_cap=8, seed=5,
              prefill_chunk=8, paged=PagedLayout(4, 10, 40))
    got = {}
    for env in ("0", "1"):
        monkeypatch.setenv("REPRO_PAGED_ATTN", env)
        got[env] = ContinuousBatchingScheduler(
            params, cfg, **kw).run(reqs).tokens_by_rid()
    for rid in got["0"]:
        np.testing.assert_array_equal(got["1"][rid], got["0"][rid])


def test_block_recycling_and_refcount_drain():
    """A workload needing ~1.5x the pool in block-grants only fits if
    harvest returns every finished request's blocks to the free list
    (refcount algebra closes); peak occupancy stays within the pool."""
    params, cfg = _params("qwen3-14b")
    lay = PagedLayout(block_size=4, n_tbl=12, n_blocks=48)
    sched = ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=16, max_new_cap=10, seed=0,
        paged=lay, prefill_chunk=8)
    reqs = _mixed_requests(cfg, 12, [16], [8], seed=5, stop=-1)
    rep = sched.run(reqs)
    assert rep.total_tokens == 12 * 8
    # 12 requests x 6 blocks each = 72 grants > 47 allocatable blocks
    assert 0 < rep.peak_blocks <= lay.n_blocks - 1
    assert rep.n_admits == 12
    # each request decodes at contexts 17..23: 5+5+5+5+6+6+6 = 38 blocks
    assert (rep.block_size, rep.n_tbl) == (4, 12)
    assert rep.attn_live_block_frac == 12 * 38 / (rep.n_steps * 2 * 12)


def test_attn_live_block_frac_hand_counted():
    """16-token blocks, 8-entry tables, 2 slots, 5 pooled steps.  A
    (prompt 15, 4 tokens) decodes at contexts 16, 17, 18: 1 + 2 + 2
    blocks; B (prompt 32, 3 tokens) at 33, 34: 3 + 3.  11 of 80."""
    fin = [FinishedRequest(rid=0, tokens=np.zeros(4, np.int32),
                           latency_s=0.0, finish_iter=0, prompt_len=15),
           FinishedRequest(rid=1, tokens=np.zeros(3, np.int32),
                           latency_s=0.0, finish_iter=0, prompt_len=32)]
    rep = ServeReport(finished=fin, wall_s=1.0, n_steps=5, n_admits=2,
                      slots=2, block_size=16, n_tbl=8)
    assert rep.attn_live_block_frac == 11 / 80
    unpaged = ServeReport(finished=fin, wall_s=1.0, n_steps=5, n_admits=2,
                          slots=2)
    assert unpaged.attn_live_block_frac == 0.0


def test_arrival_schedule_and_instrumented_runner_token_invariance():
    """Poisson-style arrival gating and the host-stepped instrumented
    runner both execute the identical compiled iteration: tokens match
    the pure device loop bit for bit, and TTFT percentiles come back
    finite."""
    params, cfg = _params("qwen3-14b")
    reqs = _mixed_requests(cfg, 5, [16, 9], [6, 8], seed=6)
    sched = ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=16, max_new_cap=10,
        temperature=0.7, seed=5, paged=PagedLayout(4, 10, 40),
        prefill_chunk=8)
    want = sched.run(reqs).tokens_by_rid()
    rep, timeline = sched.run_instrumented(reqs,
                                           arrival_iters=[0, 1, 3, 6, 9])
    got = rep.tokens_by_rid()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])
    ttft = rep.ttft_percentiles()
    assert np.isfinite(ttft["ttft_p50_s"]) and ttft["ttft_p95_s"] > 0
    assert (timeline["branch"] == 2).sum() > 0        # prefill iterations
    assert timeline["iter_s"].shape == timeline["branch"].shape


def test_paged_admission_rejections():
    params, cfg = _params("qwen3-14b")
    lay = PagedLayout(block_size=4, n_tbl=6, n_blocks=24)
    sched = ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=16, max_new_cap=8, paged=lay)
    long_req = Request(rid=0, prompt=np.zeros(17, np.int32),
                       max_new_tokens=4)
    with pytest.raises(ValueError, match="outside"):
        sched.run([long_req])
    over = Request(rid=0, prompt=np.zeros(16, np.int32), max_new_tokens=12)
    with pytest.raises(ValueError, match="> cap"):
        sched.run([over])
    # without pinned shared blocks the guard cannot fire (the layout
    # capacity check already forces n_blocks-1 >= n_tbl >= worst grant),
    # so the too-small case needs a shared prefix: the donor's pinned
    # blocks plus the worst-case fresh grant exceed the allocatable pool
    with pytest.raises(ValueError, match="pool too small"):
        tiny = ContinuousBatchingScheduler(
            params, cfg, slots=1, prompt_len=16, max_new_cap=8,
            paged=PagedLayout(block_size=4, n_tbl=6, n_blocks=7))
        same = np.arange(16, dtype=np.int32)
        tiny.run([Request(rid=0, prompt=same, max_new_tokens=8),
                  Request(rid=1, prompt=same, max_new_tokens=8)])
    with pytest.raises(ValueError, match="run_lockstep"):
        sched.run_lockstep([Request(rid=0, prompt=np.zeros(16, np.int32),
                                    max_new_tokens=4)])
