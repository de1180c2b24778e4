"""The nemotron_h layer_types stack against the benchmark's plain
reference (``bench/arch/nemotron_h.py``, loaded by path), at a tiny size
on the CPU with seeded weights from the benchmark's own ``weights.py``.

* Served logits: the scheduler's chunked prefill and paged decode, and
  the same passes driven directly (whose tokens the scheduler's must
  equal), against the reference's full forward over the whole sequence:
  with float projections, and on the macro with bfloat16 weights packed
  as the cell serves them.
* The expert share: the held-expert layer's outputs for all 16 shares
  of 8 held experts, the shared expert counted once, add up to the
  uncut reference layer's output over all 128.
* Held-expert dispatch: an expert no live row chose computes nothing,
  one with few routed rows computes them alone, one with many computes
  every row; the output equals every expert over every row, gated, and
  the ``MOE_TAPS`` counts are the branches taken.
* ``ssm_n_groups`` = 1 leaves the mamba2 mixer bit-identical to the
  code before groups existed.
"""
import hashlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import PackedCimWeights
from repro.launch.paging import PagedLayout
from repro.launch.scheduler import ContinuousBatchingScheduler, Request
from repro.models import layers as L
from repro.models import lm
from repro.models.config import ModelConfig
from repro.obs import taps

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
sys.path.insert(0, BENCH)
import reference  # noqa: E402
import weights  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "nemotron_h_reference", os.path.join(BENCH, "arch", "nemotron_h.py"))
ARCH = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ARCH)

# the period MEMEM*E at a tiny width: 2 SSM groups, GQA 4/2 heads, 8 of
# 16 experts held, float32 throughout, so the program and the reference
# differ by float32 rounding alone
MODEL = dict(
    name="nemotron-tiny", family="hybrid", layer_types="MEMEM*E", n_layers=7,
    d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=0, vocab_size=256,
    use_rope=False, n_experts=8, moe_router_experts=16, moe_first_expert=0,
    top_k=3, moe_d_ff=32, n_shared_experts=1, shared_expert_d_ff=48,
    moe_routed_scaling=2.5, ssm_state=16, ssm_n_heads=8,
    ssm_head_dim=8, ssm_n_groups=2, ssm_conv_width=4, ssm_chunk=16,
    norm_eps=1e-5, tie_embeddings=False, dtype="float32", tp_head_pad=1,
    flash_block=64)
SEED = 2 ** 33 + 16
CHUNK, PROMPT, NEW = 16, 48, 8


def _float_dot(a, w):
    return jnp.dot(a, w.astype(jnp.float32), precision=reference.HI)


def _reference_logits(params, model, seq, lin):
    """The reference's full forward, float32 activations, projections
    through ``lin``."""
    m = dict(reference.model_items(model))
    x = params["embed"][seq].astype(jnp.float32)
    x = ARCH.forward(x, params["layers"], m, lambda t: t, lin)
    h = reference.rms_norm(x, params["ln_f"], m["norm_eps"])
    return _float_dot(h, params["lm_head"])


def _direct(params, cfg, prompt, n_new, layout):
    """Chunked prefill into slot 0 of a two-slot paged cache, then greedy
    paged decode with slot 1 frozen: the logits of every served token."""
    cache = lm.init_paged_cache(cfg, 2, layout.n_blocks, layout.block_size,
                                layout.n_tbl)
    cache["table"] = cache["table"].at[0].set(
        jnp.arange(1, layout.n_tbl + 1, dtype=jnp.int32))
    chunk = jax.jit(lambda p, t, c: lm.prefill_chunk_into_slot(p, cfg, t, c, 0))
    step = jax.jit(lambda p, t, c: lm.decode_step(
        p, cfg, t, c, live=jnp.array([True, False])))
    for s in range(0, len(prompt), CHUNK):
        logits, cache = chunk(params, jnp.asarray(prompt[None, s:s + CHUNK]),
                              cache)
    out = [logits[0, -1]]
    toks = [int(jnp.argmax(out[-1]))]
    for _ in range(n_new - 1):
        tok = jnp.array([[toks[-1]], [0]], jnp.int32)
        logits, cache = step(params, tok, cache)
        out.append(logits[0, 0])
        toks.append(int(jnp.argmax(out[-1])))
    return np.stack(out), np.asarray(toks)


def _served(raw, params, model, cfg, lin, rng):
    """Served logits of one request (driven directly, its tokens equal to
    the scheduler's, which serves it beside a second request), the
    reference's logits at the same positions, the scheduler's report,
    and a call that serves the same requests through its per-iteration
    runner."""
    prompt = rng.integers(0, model["vocab_size"], PROMPT).astype(np.int32)
    layout = PagedLayout(block_size=8, n_tbl=8, n_blocks=20)
    got, toks = _direct(params, cfg, prompt, NEW, layout)
    sched = ContinuousBatchingScheduler(
        params, cfg, slots=2, prompt_len=PROMPT, max_new_cap=NEW,
        paged=layout, prefill_chunk=CHUNK)
    other = rng.integers(0, model["vocab_size"], CHUNK).astype(np.int32)
    reqs = [Request(0, prompt, NEW), Request(1, other, 3)]
    rep = sched.run(reqs)
    np.testing.assert_array_equal(rep.tokens_by_rid()[0], toks)
    seq = jnp.asarray(np.concatenate([prompt, toks[:-1]]))
    want = np.asarray(_reference_logits(raw, model, seq, lin))[PROMPT - 1:]
    return got, want, rep, lambda: sched.run_instrumented(reqs)[0]


def test_served_logits_match_reference():
    cfg = ModelConfig(**MODEL)
    params = weights.make_params(ARCH, MODEL, SEED)
    got, want, rep, stepped = _served(params, params, MODEL, cfg,
                                      _float_dot, np.random.default_rng(0))
    # float32 on both sides; the program's chunked SSD scan, flash
    # attention and fused projections reorder float32 sums, which moves a
    # logit by 3e-6 of the logits' spread here; the reference with
    # bfloat16 activations moves it by 0.27
    scale = want.std(axis=-1, keepdims=True)
    assert np.max(np.abs(got - want) / scale) < 1e-4
    # a held expert that some live row chose computes every row of its
    # pass (16 chunk rows, or the two slots of a decode step: both fit
    # the skinny route's buffer); one that none chose computes nothing
    n_moe = MODEL["layer_types"].count("E")
    calls = (rep.n_pf + rep.n_steps) * n_moe * MODEL["n_experts"]
    assert 0 < rep.moe_expert_calls < calls
    assert (2 * rep.moe_expert_calls <= rep.moe_expert_rows
            <= CHUNK * rep.moe_expert_calls)
    assert 0 < rep.moe_routed_rows < rep.moe_expert_rows
    # the per-iteration runner steps the same body: the same counts
    inst = stepped()
    counts = lambda r: [getattr(r, k) for k in L.MOE_TAPS]
    assert counts(inst) == counts(rep)


def test_served_logits_on_the_macro_match_reference():
    """The path the cell times: bfloat16 weights packed for the macro
    (every held expert's too), against the reference's integer macro."""
    model = dict(MODEL, dtype="bfloat16")
    cfg = ModelConfig(**model, cim_mode=True)
    raw = weights.make_params(ARCH, model, SEED)
    params = lm.pack_cim_params(raw, cfg)
    moe = params["layers"]["E"]["moe"]
    for leaf in (*moe["experts"].values(), *moe["shared"].values()):
        assert isinstance(leaf, PackedCimWeights)
    got, want, _, _ = _served(raw, params, model, cfg, reference.cim_linear,
                              np.random.default_rng(1))
    # the stream, KV cache and conv state are float32 (cfg.act_dtype) as
    # in the reference, and the macro's integer codes agree, so only
    # float32 sums in another order part them: 1.0e-6 to 1.7e-6 of the
    # logits' spread over four seeds; the reference with bfloat16
    # activations is 2.0 to 4.0 away, and bfloat16 caches alone 1.0
    scale = want.std(axis=-1, keepdims=True)
    assert np.max(np.abs(got - want) / scale) < 1e-4


@pytest.mark.parametrize("seed", [1, 2])
def test_expert_shares_add_up_to_the_uncut_layer(seed):
    """16 chips of 8 experts each: each share's routed part, plus the
    shared expert once, is the whole layer over 128 experts."""
    uncut = dict(MODEL, dtype="float32", n_experts=128, moe_router_experts=128,
                 top_k=6, d_model=32, moe_d_ff=8, shared_expert_d_ff=16)
    m = dict(reference.model_items(uncut))
    layers = ARCH.make_layers(jax.random.PRNGKey(seed), m, jnp.float32)
    mo = jax.tree.map(lambda v: v[0], layers["E"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(seed + 100), (24, 32), jnp.float32)
    want = ARCH.moe_mixer(h, mo, m, lambda t: t, _float_dot)

    shared = None
    total = jnp.zeros_like(want)
    for s in range(16):
        cfg = ModelConfig(**dict(uncut, n_experts=8, moe_first_expert=8 * s))
        p = dict(mo, experts=jax.tree.map(lambda v: v[8 * s:8 * s + 8],
                                          mo["experts"]))
        y = L.held_moe_apply(p, h[None], cfg)
        shared = L.relu2_mlp_apply(mo["shared"], h, cfg, "moe/shared")
        total = total + (y[0] - shared)
    total = total + shared
    # float32 sums in another order: 16 partial sums against one
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def _held_layer(cfg, bias):
    """Layer 0's held-expert layer of the tiny model, packed for the
    macro, with ``bias`` added to the held experts' choice bias."""
    model = dict(MODEL, dtype="bfloat16")
    params = lm.pack_cim_params(weights.make_params(ARCH, model, SEED), cfg)
    p = jax.tree.map(lambda v: v[0], params["layers"]["E"]["moe"])
    held = slice(MODEL["moe_first_expert"],
                 MODEL["moe_first_expert"] + MODEL["n_experts"])
    return dict(p, e_bias=p["e_bias"].at[held].add(jnp.asarray(bias)))


def _dense_over_held(p, x, cfg):
    """Every held expert over every row, gated, summed in expert order,
    plus the shared expert."""
    xf = x.reshape(-1, x.shape[-1])
    gate, _ = L.held_moe_route(p, xf, cfg)
    acc = jnp.zeros(xf.shape, jnp.float32)
    for e in range(cfg.n_experts):
        pe = jax.tree.map(lambda v: v[e], p["experts"])
        acc = acc + L.relu2_mlp_apply(pe, xf, cfg, "moe/experts") * gate[:, e:e + 1]
    y = acc.astype(x.dtype) + L.relu2_mlp_apply(p["shared"], xf, cfg,
                                                "moe/shared")
    return y.reshape(x.shape)


def _tapped_moe(p, x, cfg, live):
    with taps.collect(L.MOE_TAPS) as frame:
        y = L.held_moe_apply(p, x, cfg, live=live)
    return y, [taps.drain_sum(frame, k) for k in L.MOE_TAPS]


# expert 1 never chosen, expert 5 chosen by every row; the rest by a few
BIAS = np.array([0.0, -1e9, 0.0, 0.0, 0.0, 1e9, 0.0, 0.0], np.float32)
DISPATCH = {
    # 48 decode slots, 12 of them dead: T 48 > the 32-row buffer
    "decode": ((48, 1), np.arange(48) % 4 != 3),
    # a prefill chunk of 64 rows, every row live
    "prefill": ((1, 64), None),
}


@pytest.mark.parametrize("case", sorted(DISPATCH))
def test_held_expert_dispatch_is_bit_identical(case):
    """Each held expert takes the branch its live routed rows call for,
    and every live row's output equals (==) the dense-over-held sum."""
    cfg = ModelConfig(**dict(MODEL, dtype="bfloat16"), cim_mode=True)
    p = _held_layer(cfg, BIAS)
    (B, S), live = DISPATCH[case]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, S, MODEL["d_model"]),
                          jnp.float32)
    live = None if live is None else jnp.asarray(live)
    got, _ = jax.jit(lambda p, x, l: _tapped_moe(p, x, cfg, l))(p, x, live)
    want = jax.jit(lambda p, x: _dense_over_held(p, x, cfg))(p, x)
    keep = np.ones(B * S, bool) if live is None else np.repeat(live, S)
    got, want = (np.asarray(a).reshape(B * S, -1)[keep] for a in (got, want))
    assert np.array_equal(got, want)

    # all three branches taken: no routed row, 1..32 of them, past 32
    _, chose = L.held_moe_route(p, x.reshape(B * S, -1), cfg)
    n = np.sum(np.asarray(chose) & keep[:, None], axis=0)
    assert n[1] == 0 and n[5] == keep.sum() > 32
    assert any(0 < v <= 32 for v in n)


@pytest.mark.parametrize("noise", [None, 5])
@pytest.mark.parametrize("case", sorted(DISPATCH))
def test_held_expert_counts_match_the_branches(case, noise):
    """``MOE_TAPS`` from the router's choices: routed pairs over live
    rows; rows computed 0, 32 or T by branch; calls of experts any live
    row chose.  With analog noise on every expert computes every row."""
    cfg = ModelConfig(**dict(MODEL, dtype="bfloat16"), cim_mode=True,
                      cim_noise_seed=noise)
    p = _held_layer(cfg, BIAS)
    (B, S), live = DISPATCH[case]
    T, E = B * S, MODEL["n_experts"]
    x = jax.random.normal(jax.random.PRNGKey(8), (B, S, MODEL["d_model"]),
                          jnp.float32)
    live = None if live is None else jnp.asarray(live)
    _, got = jax.jit(lambda p, x, l: _tapped_moe(p, x, cfg, l))(p, x, live)

    _, chose = L.held_moe_route(p, x.reshape(T, -1), cfg)
    keep = np.ones(T, bool) if live is None else np.asarray(live)
    n = np.sum(np.asarray(chose) & keep[:, None], axis=0)
    if noise is None:
        rows = sum(0 if v == 0 else 32 if v <= 32 else T for v in n)
        calls = int(np.count_nonzero(n))
    else:
        rows, calls = T * E, E
    assert [int(v) for v in got] == [int(n.sum()), rows, calls]
    assert 0 < calls < E or noise is not None


# SHA-256 of the mamba2 smoke mixer's outputs (fresh prefill; chunked with
# SSM and conv state; one decode step), recorded with the code before
# ssm_n_groups existed
G1_DIGESTS = (
    "3d2e21ee742cff45bd8be69d7b6f94933d7355d8eb0ade7992aca09045683bc2",
    "ecb23a58c0503aed8631b83785f59751dc26e66601677169e38e770b6ae2156b",
    "6d9d2850ca3c933981a3ab9467ddf4cde9dab5e17e6a85992ddb3e0c822ca72b",
)


def _digest(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_one_group_mixer_is_bit_identical():
    cfg = get_config("mamba2-130m", smoke=True)
    assert cfg.ssm_n_groups == 1
    p, _ = L.mamba2_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    B, S = 2, 64
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    W, DI = cfg.ssm_conv_width, cfg.d_inner
    x = jax.random.normal(ks[0], (B, S, cfg.d_model), jnp.float32)
    st = 0.1 * jax.random.normal(ks[1], (B, H, P, N), jnp.float32)
    conv = (0.1 * jax.random.normal(ks[2], (B, W - 1, DI), jnp.float32),
            0.1 * jax.random.normal(ks[3], (B, W - 1, 2 * N), jnp.float32))
    fresh = jax.jit(lambda p, x: L.mamba2_apply(p, x, cfg))(p, x)
    chunk = jax.jit(lambda p, x, s, c: L.mamba2_apply(
        p, x, cfg, ssm_state=s, conv_state=c))(p, x, st, conv)
    dec = jax.jit(lambda p, x, s, c: L.mamba2_apply(
        p, x, cfg, ssm_state=s, conv_state=c, decode=True))(
            p, x[:, :1], st, conv)
    assert (_digest(fresh), _digest(chunk), _digest(dec)) == G1_DIGESTS
