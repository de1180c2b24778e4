"""Prepacked-weight CIM execution engine: pack-once/serve-many must be a
pure caching transform -- bit-identical to per-call weight conditioning
for every fidelity, pytree-transparent (jit / vmap / scan / checkpoint),
and wired through the model zoo's serving stack."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core import (
    CimEngine, DEFAULT_CONFIG, PackedCimWeights,
    cim_linear, cim_linear_packed, cim_matmul, cim_matmul_int,
    complex_cim_matmul, fabricate, pack_cim_weights,
    pack_complex_cim_weights,
)

CFG = DEFAULT_CONFIG


def _xw(seed=0, m=8, k=100, n=8):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.normal(k1, (m, k)), jax.random.normal(k2, (k, n))


# ---------------------------------------------------------------------------
# pytree mechanics
# ---------------------------------------------------------------------------


def test_packed_pytree_roundtrip():
    _, w = _xw()
    p = pack_cim_weights(w, CFG)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    r = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(r, PackedCimWeights)
    assert (r.k_dim, r.n_dim) == (p.k_dim, p.n_dim)  # static meta survives
    for a, b in zip(leaves, jax.tree_util.tree_leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pack_eager_equals_jit_bit_identical():
    """PR-3 caveat, closed: the packing pipeline is jit-compiled
    internally, so eager and outer-jit packing produce BIT-IDENTICAL
    leaves even at model scale (stacked bf16 projections under vmap --
    eager packing used to differ by one ulp in the per-channel scale,
    flipping occasional quantized magnitudes)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import lm
    cfg = dataclasses.replace(get_config("minicpm-2b", smoke=True),
                              cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    pe = lm.pack_cim_params(params, cfg)                      # "eager" call
    pj = jax.jit(lambda p: lm.pack_cim_params(p, cfg))(params)  # serve-style
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(pe),
                               jax.tree_util.tree_leaves_with_path(pj)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"eager != jit pack at {jax.tree_util.keystr(pa)}")
    # and the op-level pack: eager call == explicit outer jit
    _, w = _xw(seed=12)
    qe = pack_cim_weights(w, CFG)
    qj = jax.jit(lambda v: pack_cim_weights(v, CFG))(w)
    for a, b in zip(jax.tree_util.tree_leaves(qe),
                    jax.tree_util.tree_leaves(qj)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_through_jit_and_vmap():
    _, w = _xw()
    p_eager = pack_cim_weights(w, CFG)
    p_jit = jax.jit(lambda v: pack_cim_weights(v, CFG))(w)
    for a, b in zip(jax.tree_util.tree_leaves(p_eager),
                    jax.tree_util.tree_leaves(p_jit)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # identity through jit preserves structure + metadata
    r = jax.jit(lambda t: t)(p_jit)
    assert (r.k_dim, r.n_dim) == (p_jit.k_dim, p_jit.n_dim)
    # stacked packing (the scanned-layer-stack shape)
    ws = jnp.stack([w, 2 * w, -w])
    ps = jax.vmap(lambda v: pack_cim_weights(v, CFG))(ws)
    assert ps.pallas_w.shape[0] == 3
    one = jax.tree.map(lambda v: v[1], ps)
    ref = pack_cim_weights(2 * w, CFG)
    np.testing.assert_array_equal(np.asarray(one.wq()), np.asarray(ref.wq()))
    np.testing.assert_array_equal(np.asarray(one.pallas_w),
                                  np.asarray(ref.pallas_w))


@pytest.mark.parametrize("knobs", [{}, dict(n_dcim_products=0),
                                   dict(acc_len=32)],
                         ids=["default", "all_analog", "acc_len32"])
def test_pack_stores_only_the_int8_tiles(knobs):
    """A pack stores one layout, the kernels' block-padded int8 weight tile
    and its folded planes: at most (1 + n_j) bytes a padded weight plus
    the scale.  Every other route derives its operands from them."""
    from repro.core.ccim import _dcim_by_j
    from repro.kernels.ccim_matmul.ops import pick_weight_blocks
    cfg = dataclasses.replace(CFG, **knobs)
    _, w = _xw(seed=13, k=100, n=24)
    p = pack_cim_weights(w, cfg)
    leaves = jax.tree_util.tree_leaves_with_path(p)
    assert [jax.tree_util.keystr(k) for k, _ in leaves] == [
        ".scale", ".pallas_w", ".pallas_planes"]
    assert p.pallas_w.dtype == p.pallas_planes.dtype == jnp.int8
    n_j = len(_dcim_by_j(cfg))
    _, _, Np, Kp = pick_weight_blocks(100, 24, cfg.acc_len)
    assert p.pallas_planes.shape == (n_j, Kp, Np)
    assert (sum(a.nbytes for _, a in leaves)
            <= (1 + n_j) * Kp * Np + p.scale.nbytes)
    assert p.wq().shape == (100, 24)


# ---------------------------------------------------------------------------
# packed-vs-unpacked parity (the acceptance bar: bit-identical everywhere)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fidelity", ["fast", "fast_broadcast", "bit_true",
                                      "exact"])
def test_packed_parity_all_fidelities(fidelity):
    x, w = _xw(seed=1)
    p = pack_cim_weights(w, CFG)
    macro = fabricate(jax.random.PRNGKey(7), CFG)
    nk = jax.random.PRNGKey(9)
    u = cim_matmul(x, w, CFG, noise_key=nk, macro=macro, fidelity=fidelity,
                   use_pallas=False)
    q = cim_matmul(x, p, CFG, noise_key=nk, macro=macro, fidelity=fidelity,
                   use_pallas=False)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


def test_packed_parity_noise_free_and_int():
    x, w = _xw(seed=2)
    p = pack_cim_weights(w, CFG)
    u = cim_matmul(x, w, CFG, use_pallas=False)
    q = cim_matmul(x, p, CFG, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))
    xq = jax.random.randint(jax.random.PRNGKey(3), (8, 100), -127, 128)
    wq = p.wq()
    ui = cim_matmul_int(xq, wq, None, CFG, None, "fast", use_pallas=False)
    qi = cim_matmul_int(xq, p, None, CFG, None, "fast", use_pallas=False)
    np.testing.assert_array_equal(np.asarray(ui), np.asarray(qi))


def test_packed_parity_pallas_interpret():
    """Prepacked-plane kernel path == in-kernel decomposition path."""
    x, w = _xw(seed=4, m=8, k=96, n=8)
    p = pack_cim_weights(w, CFG)
    u = cim_matmul(x, w, CFG, use_pallas=True)
    q = cim_matmul(x, p, CFG, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


def test_packed_parity_complex():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = (jax.random.normal(k1, (8, 64))
         + 1j * jax.random.normal(k2, (8, 64))).astype(jnp.complex64)
    w = (jax.random.normal(k2, (64, 8))
         + 1j * jax.random.normal(k3, (64, 8))).astype(jnp.complex64)
    p = pack_complex_cim_weights(jnp.real(w), jnp.imag(w), CFG)
    for use_pallas in (False, True):   # 4-pass GEMM and fused kernel paths
        u = complex_cim_matmul(x, w, CFG, use_pallas=use_pallas)
        q = complex_cim_matmul(x, p, CFG, use_pallas=use_pallas)
        np.testing.assert_array_equal(np.asarray(u), np.asarray(q))
    u = complex_cim_matmul(x, w, CFG, noise_key=k3, use_pallas=False)
    q = complex_cim_matmul(x, p, CFG, noise_key=k3, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


def test_packed_parity_nondefault_config():
    """A non-prototype macro config packs/serves correctly too (no Pallas
    routing: the kernels hardcode the prototype's numerics)."""
    cfg = dataclasses.replace(CFG, acc_len=8)
    x, w = _xw(seed=6, k=40)
    p = pack_cim_weights(w, cfg)
    u = cim_matmul(x, w, cfg, use_pallas=False)
    q = cim_matmul(x, p, cfg, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


def test_packed_config_mismatch_rejected():
    """Serving a pack under a different macro config must error, not
    silently misread the folded planes (the pack IS cfg-specific)."""
    cfg = dataclasses.replace(CFG, n_dcim_products=1)
    x, w = _xw(seed=7, k=48)
    p = pack_cim_weights(w, cfg)
    with pytest.raises(ValueError, match="different CCIMConfig"):
        cim_matmul(x, p, CFG, use_pallas=False)


# ---------------------------------------------------------------------------
# STE / engine handle
# ---------------------------------------------------------------------------


def test_cim_linear_packed_forward_and_ste_backward():
    x, w = _xw(seed=8)
    p = pack_cim_weights(w, CFG)
    y_u = cim_linear(x, w, None, CFG, "fast", False)
    y_p = cim_linear_packed(x, p, None, CFG, "fast", False)
    np.testing.assert_array_equal(np.asarray(y_u), np.asarray(y_p))
    # backward: gradients flow to activations through the DEQUANTIZED
    # array contents (frozen weights get no cotangent)
    g = jax.grad(lambda v: jnp.sum(cim_linear_packed(v, p, None, CFG,
                                                     "fast", False)))(x)
    ref = jnp.ones((x.shape[0], p.n_dim)) @ p.dequantized().T
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref), rtol=1e-5)


def test_engine_handle_dispatch():
    x, w = _xw(seed=10)
    eng = CimEngine(cfg=CFG, fidelity="fast", use_pallas=False)
    p = eng.pack(w)
    np.testing.assert_array_equal(np.asarray(eng.matmul(x, w)),
                                  np.asarray(eng.matmul(x, p)))


# ---------------------------------------------------------------------------
# checkpoint round-trip (pay the PTQ cost once per deployment)
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_packed(tmp_path):
    _, w = _xw(seed=11)
    tree = {"proj": pack_cim_weights(w, CFG), "other": jnp.ones((3,))}
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, tree)
    target = jax.tree.map(jnp.zeros_like, tree)
    r = ckpt.restore(d, target)
    assert isinstance(r["proj"], PackedCimWeights)
    assert (r["proj"].k_dim, r["proj"].n_dim) == (100, 8)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored pack serves identically
    x, _ = _xw(seed=11)
    np.testing.assert_array_equal(
        np.asarray(cim_matmul(x, tree["proj"], CFG, use_pallas=False)),
        np.asarray(cim_matmul(x, r["proj"], CFG, use_pallas=False)))


# ---------------------------------------------------------------------------
# end-to-end: packed serving == unpacked serving, token for token
# ---------------------------------------------------------------------------


def test_serve_packed_matches_unpacked():
    from repro.launch.serve import serve
    u = serve("minicpm-2b", smoke=True, batch=2, prompt_len=8, gen=3,
              cim=True, pack=False)
    p = serve("minicpm-2b", smoke=True, batch=2, prompt_len=8, gen=3,
              cim=True, pack=True)
    np.testing.assert_array_equal(u, p)


def test_pack_cim_params_structure():
    from repro.configs import get_config
    from repro.core import FusedPackedCimWeights
    from repro.models import lm
    cfg = dataclasses.replace(get_config("minicpm-2b", smoke=True),
                              cim_mode=True)
    params, _ = lm.init(jax.random.PRNGKey(0), cfg, pack_cim=True)
    blk = params["layers"]
    # plan-compatible input-sharing groups fuse into ONE wide pack each
    qkv = blk["attn"]["wq+wk+wv"]
    assert isinstance(qkv, FusedPackedCimWeights)
    assert qkv.seg_names == ("wq", "wk", "wv")
    assert sum(qkv.seg_dims) == qkv.packed.n_dim
    assert isinstance(blk["mlp"]["w1+w3"], FusedPackedCimWeights)
    # wo/w2 consume different activations -> stay individually packed
    assert isinstance(blk["attn"]["wo"], PackedCimWeights)
    assert isinstance(blk["mlp"]["w2"], PackedCimWeights)
    # stacked leading layer axis survives packing (scan-sliceable)
    assert qkv.packed.pallas_w.shape[0] == cfg.n_layers
    # non-projection leaves stay float
    assert not isinstance(params["embed"], PackedCimWeights)
    assert not isinstance(blk["ln1"], PackedCimWeights)
    # fusion off -> the PR-2 per-projection structure
    cfg0 = dataclasses.replace(cfg, cim_fuse=False)
    p0, _ = lm.init(jax.random.PRNGKey(0), cfg0, pack_cim=True)
    assert isinstance(p0["layers"]["attn"]["wq"], PackedCimWeights)
