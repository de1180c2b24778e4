"""Model-zoo building blocks: norms, GQA attention (RoPE / qk-norm /
softcap / sliding-window / prefix-LM), SwiGLU MLP, MoE (shared + routed
top-k, capacity-based dispatch), Mamba2 / SSD.

Functional style: ``*_init(key, cfg) -> (params, axes)`` where ``axes`` is a
same-structure tree of logical-dimension-name tuples consumed by
distributed/sharding.py, and ``*_apply(params, x, ...)`` is pure.

Every matmul routes through ``_dense`` -> ``core.engine.CimEngine`` so any
architecture can run its projections on the emulated C-CIM macro
(cfg.cim_mode) -- the paper's technique as a first-class execution mode.
Projection weights may be prepacked ``PackedCimWeights`` (see
``lm.pack_cim_params``): quantize/decompose once, serve many.
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ccim import DEFAULT_CONFIG
from ..core.engine import (CimEngine, FusedPackedCimWeights,
                           PackedCimWeights)
from ..kernels.ccim_matmul.kernel import SKINNY_SUBLANE
from ..kernels.paged_attn import ops as paged_attn_ops
from ..obs import taps
from ..obs.scopes import scoped
from .config import ModelConfig

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def cim_engine(cfg: ModelConfig, path: Optional[str] = None) -> CimEngine:
    """The execution engine a model config resolves to: macro config,
    fidelity and Pallas routing all come from the config (no module
    globals), so two models in one process can run different macros.

    With a deployment plan (cfg.cim_plan, see repro.plan) the projection
    ``path`` (e.g. "attn/wq") resolves to ITS OWN entry -- per-projection
    macro config and fidelity -- at trace time; plans are static metadata,
    so mixed-fidelity models still compile to one executable per step.
    """
    if cfg.cim_plan is not None and path is not None:
        e = cfg.cim_plan.resolve(path)
        return CimEngine(cfg=e.cfg, fidelity=e.fidelity,
                         use_pallas=cfg.cim_use_pallas)
    return CimEngine(cfg=cfg.cim_cfg or DEFAULT_CONFIG,
                     fidelity=cfg.cim_fidelity,
                     use_pallas=cfg.cim_use_pallas)


def _dense_noise_key(cfg: ModelConfig, path: Optional[str]) -> Optional[Array]:
    """Per-projection deterministic analog-noise stream (cfg.cim_noise_seed).

    The seed is folded with a hash of the projection path, so every
    projection draws independent mismatch/comparator noise while staying
    reproducible.  Scanned layer stacks share one path -- and therefore
    one draw pattern across depth -- mirroring how the weight-stationary
    macro reuses the same physical banks for every layer of a stack.
    """
    if cfg.cim_noise_seed is None:
        return None
    tag = zlib.crc32((path or "").encode("utf-8"))
    return jax.random.fold_in(jax.random.PRNGKey(cfg.cim_noise_seed), tag)


@scoped("lm.cim")
def _dense(x: Array, w, cfg: ModelConfig, path: Optional[str] = None) -> Array:
    """x (..., K) @ w (K, N) -- through the macro when cim_mode is on.

    ``w`` may be a ``PackedCimWeights`` (prepacked array contents from
    ``lm.pack_cim_params``): then the macro runs unconditionally with
    activation-only quantization on the hot path.  ``path`` identifies the
    projection for the deployment plan (per-projection config/fidelity)
    and the deterministic noise stream; plan fidelity "float" bypasses the
    macro entirely.
    """
    if isinstance(w, PackedCimWeights):
        if not cfg.cim_mode:
            raise ValueError(
                "packed CIM weights require cim_mode=True (packed params "
                "are macro array contents, not float matrices)")
        eng = cim_engine(cfg, path)
        if eng.fidelity == "float":
            raise ValueError(
                f"plan assigns fidelity 'float' to {path!r} but its weights "
                "are packed macro array contents; re-pack under the plan "
                "(lm.pack_cim_params leaves float-fidelity sites unpacked)")
        return eng.matmul(x, w, _dense_noise_key(cfg, path))
    if cfg.cim_mode:
        eng = cim_engine(cfg, path)
        if eng.fidelity == "float":
            return x @ w
        return eng.matmul(x, w, _dense_noise_key(cfg, path))
    return x @ w


FUSED_SEP = "+"   # fused param-leaf key: member names joined, e.g. "wq+wk+wv"


def fusion_partitions(cfg: ModelConfig, prefix: str, names) -> list:
    """Partition fusion-candidate projections (which share one input
    activation) by resolved plan entry: [(entry_cfg, fidelity, members)]
    for every partition of two or more fusable members.

    The ONE definition of group compatibility -- pack time
    (lm._pack_tree) and trace time (_dense_group) must agree, or fused
    packs would go unconsumed.  Only 'fast'/'exact' fuse: 'float'
    bypasses the macro, and the broadcast/bit_true fidelities draw noise
    with non-column-local shapes.
    """
    part: Dict[Tuple, list] = {}
    for n in names:
        eng = cim_engine(cfg, prefix + n)
        if eng.fidelity in ("fast", "exact"):
            part.setdefault((eng.cfg, eng.fidelity), []).append(n)
    return [(c, f, g) for (c, f), g in part.items() if len(g) >= 2]


def _split_segments(y: Array, names, dims) -> Dict[str, Array]:
    """Split a fused (..., sum(dims)) output back into per-projection
    results at the static per-segment N-offsets."""
    offs = np.cumsum((0,) + tuple(dims))
    return {n: jax.lax.slice_in_dim(y, int(offs[i]), int(offs[i + 1]),
                                    axis=-1)
            for i, n in enumerate(names)}


def _seg_noise(cfg: ModelConfig, prefix: str, names) -> Optional[Tuple]:
    """Per-segment noise keys for a fused group -- each segment draws the
    SAME stream its unfused projection would (path-folded seed), which is
    what keeps fusion bit-identical under analog-noise emulation."""
    if cfg.cim_noise_seed is None:
        return None
    return tuple(_dense_noise_key(cfg, prefix + n) for n in names)


def _dense_fused(x: Array, leaf: FusedPackedCimWeights, cfg: ModelConfig,
                 prefix: str, names) -> Dict[str, Array]:
    """Serve one pack-time-fused projection group (lm.pack_cim_params):
    one activation quantization + one wide macro GEMM, split per segment."""
    if not cfg.cim_mode:
        raise ValueError(
            "fused packed CIM weights require cim_mode=True (packed params "
            "are macro array contents, not float matrices)")
    eng = cim_engine(cfg, prefix + names[0])
    if eng.fidelity == "float":
        raise ValueError(
            f"plan assigns fidelity 'float' to {prefix}{names[0]!r} but the "
            "group was packed as macro array contents; re-pack under the "
            "serving plan (pack_cim_params fuses by the plan's entries)")
    for s in names[1:]:
        e2 = cim_engine(cfg, prefix + s)
        if (e2.cfg, e2.fidelity) != (eng.cfg, eng.fidelity):
            raise ValueError(
                f"fused group {prefix}{'+'.join(names)} was packed under one "
                f"plan entry, but the serving plan resolves {prefix}{s!r} "
                f"differently ({e2.fidelity} vs {eng.fidelity}); re-pack "
                "under the serving plan (the unfused path would refuse the "
                "same mismatch)")
    y = eng.matmul(x, leaf, _seg_noise(cfg, prefix, names))
    return _split_segments(y, names, leaf.seg_dims)


@scoped("lm.cim")
def _dense_group(x: Array, p: Params, names, cfg: ModelConfig,
                 prefix: str) -> Dict[str, Array]:
    """Run a block's projections that all consume ``x``, fusing plan-
    compatible sites into one wide macro GEMM (DESIGN.md section 9).

    Three routes, every one bit-identical per projection to ``_dense``:
      * pack-time fused leaves (``FusedPackedCimWeights``, key
        "wq+wk+wv") -- the packed serving hot path.  These are ALWAYS
        served fused: the leaf structure is the execution plan, and
        ``cfg.cim_fuse`` governs grouping at pack/trace time, not how an
        already-fused pack executes (re-pack with cim_fuse=False for a
        per-projection pack);
      * trace-time fusion of raw float weights under cim_mode and
        cfg.cim_fuse: members resolving to the same plan entry
        concatenate along N for the call (unpacked serving / QAT get the
        same 7 -> ~3 GEMM collapse);
      * everything else (float fidelity, heterogeneous entries, cim off,
        cfg.cim_fuse=False) falls through to per-projection ``_dense``.
    """
    remaining = list(names)
    out: Dict[str, Array] = {}
    for key, leaf in p.items():
        if isinstance(leaf, FusedPackedCimWeights):
            segs = key.split(FUSED_SEP)
            if all(s in remaining for s in segs):
                out.update(_dense_fused(x, leaf, cfg, prefix, segs))
                remaining = [n for n in remaining if n not in segs]
    if cfg.cim_mode and cfg.cim_fuse and len(remaining) > 1:
        fusable = [n for n in remaining
                   if p.get(n) is not None
                   and not isinstance(p[n], PackedCimWeights)]
        for ecfg, fid, g in fusion_partitions(cfg, prefix, fusable):
            eng = CimEngine(cfg=ecfg, fidelity=fid,
                            use_pallas=cfg.cim_use_pallas)
            wcat = jnp.concatenate([p[n] for n in g], axis=-1)
            dims = tuple(int(p[n].shape[-1]) for n in g)
            nkeys = _seg_noise(cfg, prefix, g)
            y = eng.matmul(x, wcat, nkeys,
                           noise_segments=dims if nkeys else None)
            out.update(_split_segments(y, g, dims))
            remaining = [n for n in remaining if n not in g]
    for n in remaining:
        out[n] = _dense(x, p[n], cfg, prefix + n)
    return out


def _init_dense(key, d_in, d_out, axes, scale=None, dtype=jnp.bfloat16):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale
    return w.astype(dtype), axes


def rms_norm(x: Array, w: Array, eps: float) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x (B, S, H, D), positions (B, S) -> rotated x.

    Rotation of each (even, odd) pair by angle pos/theta^(2i/D): this IS a
    complex multiply x * e^{i phi} -- the workload class the paper's complex
    MAC targets (see DESIGN.md §4).
    """
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) -- flash-style scan + plain + decode paths
# ---------------------------------------------------------------------------


def _head_mask(cfg: ModelConfig) -> Optional[Array]:
    """(padded_heads,) 1/0 mask; None when no padding is in effect."""
    if cfg.padded_heads == cfg.n_heads:
        return None
    return (jnp.arange(cfg.padded_heads) < cfg.n_heads).astype(jnp.float32)


def attention_init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    """Projections sized with TP-padded head counts; pad head slots are
    zero-initialised and masked after attention, so they stay exactly zero
    through training (zero grads) -- the math never sees them."""
    dh, d = cfg.head_dim, cfg.d_model
    hq, hkv = cfg.padded_heads, cfg.padded_kv_heads
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = _init_dense(ks[0], d, hq * dh, ("embed", "heads"), dtype=dtype)
    p["wk"], a["wk"] = _init_dense(ks[1], d, hkv * dh, ("embed", "kv_heads"), dtype=dtype)
    p["wv"], a["wv"] = _init_dense(ks[2], d, hkv * dh, ("embed", "kv_heads"), dtype=dtype)
    p["wo"], a["wo"] = _init_dense(ks[3], hq * dh, d, ("heads", "embed"), dtype=dtype)
    mask = _head_mask(cfg)
    if mask is not None:
        mq = jnp.repeat(mask, dh)[None, :].astype(dtype)
        p["wq"] = p["wq"] * mq
        p["wo"] = p["wo"] * mq.T
        if hkv == hq:  # MHA: kv heads padded alongside q heads
            p["wk"] = p["wk"] * mq
            p["wv"] = p["wv"] * mq
    if cfg.qk_norm:
        p["q_norm"], a["q_norm"] = jnp.zeros((dh,), dtype), ("head_dim",)
        p["k_norm"], a["k_norm"] = jnp.zeros((dh,), dtype), ("head_dim",)
    return p, a


def _ambient_mesh():
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _shard_batch_dim(x, expert_dim: Optional[int] = None):
    """Pin dim 0 of ``x`` to the data-parallel axes (dispatch buffers:
    GSPMD otherwise merges per-shard scatters with a full-size all-reduce
    -- measured 43 GB/layer on qwen2-moe).  When ``expert_dim`` is given
    and divisible by the model axis, it is sharded too (EP layout for the
    expert GEMMs -- arctic's 128 experts)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in ("pod", "data") if a in sizes)
    if not dp or x.shape[0] % math.prod(sizes[a] for a in dp) != 0:
        return x
    if (expert_dim is not None and "model" in sizes
            and x.shape[expert_dim] % sizes["model"] == 0):
        # EP-divisible experts (arctic): GSPMD's own (B/data, E/model)
        # placement beats any pin we tried -- forcing either E-replicated
        # (303 s) or E-sharded-with-ZeRO-weights (444 s) regressed vs 61 s
        # unpinned (EXPERIMENTS.md iteration 13). Leave it alone.
        return x
    spec = jax.sharding.PartitionSpec(dp, *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, spec)


def _head_constraints(q, k, v):
    """Pin attention shardings: q sharded on (padded) heads over 'model',
    k/v REPLICATED over 'model'.

    Without this, GSPMD reshards the (B,S,Hkv*dh) kv projection by
    splitting head_dim, which turns every flash QK/AV dot into a partial
    sum: measured 429 GB/step/device of score all-reduces on qwen3-14b.
    Replicating kv costs one (B,S,Hkv,dh) all-gather per layer instead
    (~80x less traffic at GQA ratios)."""
    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return q, k, v
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    con = jax.lax.with_sharding_constraint
    q = con(q, jax.sharding.PartitionSpec(U, None, "model", None))
    k = con(k, jax.sharding.PartitionSpec(U, None, None, None))
    v = con(v, jax.sharding.PartitionSpec(U, None, None, None))
    return q, k, v


def _qkv(p, x, cfg: ModelConfig, positions, path="attn"):
    B, S, _ = x.shape
    dh = cfg.head_dim
    hq, hkv = cfg.padded_heads, cfg.padded_kv_heads
    qkv = _dense_group(x, p, ("wq", "wk", "wv"), cfg, f"{path}/")
    q = qkv["wq"].reshape(B, S, hq, dh)
    k = qkv["wk"].reshape(B, S, hkv, dh)
    v = qkv["wv"].reshape(B, S, hkv, dh)
    q, k, v = _head_constraints(q, k, v)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask(q_pos, k_pos, is_local, window, n_prefix):
    """(..., Sq, Sk) boolean mask. is_local may be a traced scalar."""
    causal = q_pos[:, :, None] >= k_pos[:, None, :]
    if n_prefix:
        causal = causal | (k_pos[:, None, :] < n_prefix)
    if window is not None:
        local = causal & (q_pos[:, :, None] - k_pos[:, None, :] < window)
        causal = jnp.where(is_local, local, causal)
    return causal


def _flash_blocks(k, v, k_pos, blk):
    B, Sk, Hkv, Dh = k.shape
    n_blk = (Sk + blk - 1) // blk
    pad = n_blk * blk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=10 ** 9)
    kb = k.reshape(B, n_blk, blk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blk, blk, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(B, n_blk, blk).transpose(1, 0, 2)
    return kb, vb, pb, pad


def _flash_fwd(cfg, n_prefix, q, k, v, q_pos, k_pos, is_local):
    """Forward scan over KV blocks; returns (out, m, l) softmax stats."""
    B, Sq, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    blk = min(cfg.flash_block, k.shape[1])
    kb, vb, pb, _ = _flash_blocks(k, v, k_pos, blk)
    qg = q.reshape(B, Sq, Hkv, G, Dh) * (Dh ** -0.5)

    def step(carry, blk_in):
        m, l, acc = carry
        kc, vc, pc = blk_in
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,
                       preferred_element_type=jnp.float32)
        s = softcap(s, cfg.attn_softcap)
        msk = _mask(q_pos, pc, is_local, cfg.sliding_window, n_prefix)
        s = jnp.where(msk[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,Hkv,G,Sq,Dh) f32
    return out, m, l


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def flash_attention(cfg: ModelConfig, n_prefix, q, k, v, q_pos, k_pos,
                    is_local) -> Array:
    """FlashAttention with a block-recomputing backward (O(S) memory in
    fwd AND bwd -- plain scan AD would stack the full attention matrix:
    measured 384 GiB/device on arctic train_4k before this custom VJP)."""
    B, Sq, Hq, Dh = q.shape
    out, _, _ = _flash_fwd(cfg, n_prefix, q, k, v, q_pos, k_pos, is_local)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dh).astype(q.dtype)


def _flash_vjp_fwd(cfg, n_prefix, q, k, v, q_pos, k_pos, is_local):
    B, Sq, Hq, Dh = q.shape
    out, m, l = _flash_fwd(cfg, n_prefix, q, k, v, q_pos, k_pos, is_local)
    y = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dh).astype(q.dtype)
    return y, (q, k, v, q_pos, k_pos, is_local, out, m, l)


def _flash_vjp_bwd(cfg, n_prefix, res, dy):
    q, k, v, q_pos, k_pos, is_local, out, m, l = res
    B, Sq, Hq, Dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    blk = min(cfg.flash_block, Sk)
    kb, vb, pb, pad = _flash_blocks(k, v, k_pos, blk)
    scale = Dh ** -0.5
    qg = q.reshape(B, Sq, Hkv, G, Dh).astype(jnp.float32) * scale
    dyg = (dy.reshape(B, Sq, Hkv, G, Dh)
           .transpose(0, 2, 3, 1, 4).astype(jnp.float32))  # (B,Hkv,G,Sq,Dh)
    l_safe = jnp.maximum(l, 1e-30)
    # D_i = sum_d dy_i * out_i  (out already normalised)
    Drow = jnp.sum(dyg * out, axis=-1)                      # (B,Hkv,G,Sq)

    def step(dq, blk_in):
        kc, vc, pc = blk_in
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,
                       preferred_element_type=jnp.float32)
        z = softcap(s, cfg.attn_softcap)
        msk = _mask(q_pos, pc, is_local, cfg.sliding_window, n_prefix)
        z = jnp.where(msk[:, None, None, :, :], z, -1e30)
        p = jnp.exp(z - m[..., None]) / l_safe[..., None]   # (B,Hkv,G,Sq,blk)
        dv = jnp.einsum("bhgqk,bhgqd->bkhd", p, dyg)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", dyg, vc.astype(jnp.float32))
        dz = p * (dp - Drow[..., None])
        if cfg.attn_softcap is not None:
            # mask BEFORE the tanh'-factor: masked z = -1e30 would give
            # 0 * inf = NaN otherwise
            factor = 1.0 - (z / cfg.attn_softcap) ** 2
            factor = jnp.where(msk[:, None, None, :, :], factor, 0.0)
            dz = dz * factor
        dq_new = dq + jnp.einsum("bhgqk,bkhd->bqhgd", dz, kc.astype(jnp.float32))
        dk = jnp.einsum("bhgqk,bqhgd->bkhd", dz, qg)
        return dq_new, (dk, dv)

    dq0 = jnp.zeros((B, Sq, Hkv, G, Dh), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, (kb, vb, pb))
    dq = (dq * scale).reshape(B, Sq, Hq, Dh).astype(q.dtype)
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(B, -1, Hkv, Dh)
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(B, -1, Hkv, Dh)
    if pad:
        dk, dv = dk[:, :Sk], dv[:, :Sk]
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None, None)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def plain_attention(q, k, v, q_pos, k_pos, cfg: ModelConfig, is_local,
                    n_prefix=0) -> Array:
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, Dh) * (Dh ** -0.5)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = softcap(s, cfg.attn_softcap)
    msk = _mask(q_pos, k_pos, is_local, cfg.sliding_window, n_prefix)
    s = jnp.where(msk[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, Hq, Dh)


def attention_apply(
    p: Params,
    x: Array,                       # (B, S, D)
    cfg: ModelConfig,
    positions: Array,               # (B, S)
    is_local,                       # scalar bool (traced ok)
    kv_cache: Optional[Tuple[Array, Array]] = None,  # (B,Smax,Hkv,Dh) x2
    cache_pos: Optional[Array] = None,               # (B,): per-slot write idx
    n_prefix: int = 0,
    return_kv: bool = False,
    path: str = "attn",
    block_table: Optional[Array] = None,             # (B, n_tbl) int32 paged
    write_mask: Optional[Array] = None,              # (B,) bool: rows that write
):
    """Returns (out (B,S,D), new_kv or None).

    ``cache_pos`` is a per-slot ``(B,)`` vector: each batch row writes its
    S new KV entries at its own position (continuous batching -- slots sit
    at different depths), and each row's validity horizon is its own
    ``cache_pos + S``.  ``path`` is the deployment-plan projection prefix
    (the zamba2 shared block passes "shared/attn").

    With ``block_table`` the cache is PAGED: ``kv_cache`` holds global
    ``(n_blocks, block_size, Hkv, Dh)`` pools shared by every row, and
    row b's logical position p lives at pool[table[b, p//bs], p%bs].
    Writes become a flat-index scatter through the table, reads gather
    the table back into a dense per-row view and run the SAME masked
    attention as the contiguous path (bit-identical tokens -- the
    validity horizon does not care where rows physically live), except
    S==1 decode reads, which route to the fused gather+attention kernel
    in kernels/paged_attn when that backend path is enabled.  Rows whose
    table entries are 0 hit the reserved trash block: harvested slots
    park there so their frozen-position writes cannot corrupt blocks
    that were recycled to live slots.

    ``write_mask`` (paged only) redirects masked-OUT rows' KV writes to
    the trash block.  The contiguous cache never needs it (a dead slot's
    frozen-position writes stay inside its own region), but paged pools
    are SHARED: a pooled decode/verify step would otherwise scribble a
    non-live slot's garbage row into a block another request is still
    reading (mid-chunked-prefill slots sit inside refcounted shared
    blocks).  Live rows are untouched, so masking is bit-invisible.
    """
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions, path)
    out, new_kv = _attention_core(q, k, v, cfg, positions, is_local,
                                  kv_cache, cache_pos, n_prefix, return_kv,
                                  block_table, write_mask)
    return _attn_out(p, out, cfg, B, S, path), new_kv


@scoped("lm.attn")
def _attention_core(q, k, v, cfg: ModelConfig, positions, is_local,
                    kv_cache, cache_pos, n_prefix, return_kv, block_table,
                    write_mask):
    """``attention_apply`` between its projections: the cache write
    (contiguous rows or paged scatter), the read, and the attention
    itself.  Returns (out (B,S,H,Dh), new_kv or None)."""
    B, S = q.shape[:2]
    new_kv = None
    if kv_cache is not None and block_table is not None:
        ck, cv = kv_cache
        nb, bs, hkv, dh = ck.shape
        n_tbl = block_table.shape[1]
        pos_w = cache_pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        blk = jnp.take_along_axis(block_table,
                                  jnp.minimum(pos_w // bs, n_tbl - 1), axis=1)
        if write_mask is not None:
            blk = jnp.where(write_mask[:, None], blk, 0)  # -> trash block
        flat = (blk * bs + pos_w % bs).reshape(-1)
        ckf = ck.reshape(nb * bs, hkv, dh).at[flat].set(
            k.astype(ck.dtype).reshape(B * S, hkv, dh))
        cvf = cv.reshape(nb * bs, hkv, dh).at[flat].set(
            v.astype(cv.dtype).reshape(B * S, hkv, dh))
        new_kv = (ckf.reshape(ck.shape), cvf.reshape(cv.shape))
        if S == 1 and n_prefix == 0 and paged_attn_ops.kernel_enabled():
            # length 0: a row this step does not decode walks no blocks
            lens = cache_pos + 1
            if write_mask is not None:
                lens = jnp.where(write_mask, lens, 0)
            out = paged_attn_ops.paged_attention_decode(
                q[:, 0], new_kv[0], new_kv[1], block_table, lens,
                is_local, softcap=cfg.attn_softcap,
                window=cfg.sliding_window)[:, None]
            return out, new_kv
        L = n_tbl * bs
        idx = (block_table[:, :, None] * bs
               + jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(B, L)
        k_full = jnp.take(ckf, idx, axis=0)
        v_full = jnp.take(cvf, idx, axis=0)
        k_pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        valid = k_pos < (cache_pos[:, None] + S)
        k_pos = jnp.where(valid, k_pos, 10 ** 9)
    elif kv_cache is not None:
        ck, cv = kv_cache

        def row_write(c, u, s):
            return jax.lax.dynamic_update_slice(c, u, (s, 0, 0))

        ck = jax.vmap(row_write)(ck, k.astype(ck.dtype), cache_pos)
        cv = jax.vmap(row_write)(cv, v.astype(cv.dtype), cache_pos)
        new_kv = (ck, cv)
        k_pos = jnp.broadcast_to(jnp.arange(ck.shape[1])[None, :], (B, ck.shape[1]))
        valid = k_pos < (cache_pos[:, None] + S)
        k_pos = jnp.where(valid, k_pos, 10 ** 9)  # mask out unwritten slots
        k_full, v_full = ck, cv
    else:
        k_pos = positions
        k_full, v_full = k, v
        if return_kv:
            new_kv = (k, v)

    if cfg.attn_impl == "flash" and S > 1:
        out = flash_attention(cfg, n_prefix, q, k_full, v_full, positions,
                              k_pos, is_local)
    else:
        out = plain_attention(q, k_full, v_full, positions, k_pos, cfg,
                              is_local, n_prefix)
    return out, new_kv


def _attn_out(p: Params, out: Array, cfg: ModelConfig, B: int, S: int,
              path: str) -> Array:
    """Shared attention epilogue: TP-pad head masking + wo projection."""
    mask = _head_mask(cfg)
    if mask is not None:
        # zero the TP-pad heads: keeps wo/wq pad slots at exactly zero
        # through training (their grads vanish here)
        out = out * mask[None, None, :, None].astype(out.dtype)
    return _dense(out.reshape(B, S, -1), p["wo"], cfg, f"{path}/wo")


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None, dtype=jnp.bfloat16):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p, a = {}, {}
    p["w1"], a["w1"] = _init_dense(ks[0], cfg.d_model, d_ff, ("embed", "ff"), dtype=dtype)
    p["w3"], a["w3"] = _init_dense(ks[1], cfg.d_model, d_ff, ("embed", "ff"), dtype=dtype)
    p["w2"], a["w2"] = _init_dense(ks[2], d_ff, cfg.d_model, ("ff", "embed"), dtype=dtype)
    return p, a


def mlp_apply(p: Params, x: Array, cfg: ModelConfig, path: str = "mlp") -> Array:
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    gu = _dense_group(x, p, ("w1", "w3"), cfg, f"{path}/")
    h = act(gu["w1"]) * gu["w3"]
    return _dense(h, p["w2"], cfg, f"{path}/w2")


# ---------------------------------------------------------------------------
# MoE: shared experts + routed top-k with capacity (scatter dispatch)
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(D)
    p, a = {}, {}
    p["router"], a["router"] = _init_dense(ks[0], D, E, ("embed", "experts_r"),
                                           dtype=jnp.float32)
    p["w1"] = (jax.random.normal(ks[1], (E, D, F), jnp.float32) * s).astype(dtype)
    p["w3"] = (jax.random.normal(ks[2], (E, D, F), jnp.float32) * s).astype(dtype)
    p["w2"] = (jax.random.normal(ks[3], (E, F, D), jnp.float32) / math.sqrt(F)).astype(dtype)
    a["w1"] = ("experts", "embed", "moe_ff")
    a["w3"] = ("experts", "embed", "moe_ff")
    a["w2"] = ("experts", "moe_ff", "embed")
    if cfg.shared_expert_d_ff:
        p["shared"], a["shared"] = mlp_init(ks[4], cfg, cfg.shared_expert_d_ff, dtype)
    return p, a


def _moe_ffn(p: Params, buf: Array, cfg: ModelConfig) -> Array:
    """Per-expert SwiGLU over a dispatched buffer (..., E, C, D)."""
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(jnp.einsum("...ecd,edf->...ecf", buf, p["w1"])) * jnp.einsum(
        "...ecd,edf->...ecf", buf, p["w3"])
    return jnp.einsum("...ecf,efd->...ecd", h, p["w2"])


def _moe_small(p, xf, eidx, gate_vals, cfg):
    """Exact (no-drop) path for small token counts (decode)."""
    T, D = xf.shape
    E, K = cfg.n_experts, cfg.top_k
    C = T * K
    ef = eidx.reshape(T * K)
    one_hot = jax.nn.one_hot(ef, E, dtype=jnp.int32)
    pos = jnp.cumsum(one_hot, axis=0) - one_hot
    myp = jnp.take_along_axis(pos, ef[:, None], axis=1)[:, 0]
    xk = jnp.repeat(xf, K, axis=0)
    buf = jnp.zeros((E, C, D), xf.dtype).at[ef, myp].add(xk)
    out_buf = _moe_ffn(p, buf, cfg)
    yk = out_buf[ef, myp] * gate_vals.reshape(T * K, 1).astype(xf.dtype)
    return jnp.sum(yk.reshape(T, K, D), axis=1)


def _moe_grouped(p, x, eidx, gate_vals, cfg):
    """GShard-style group-local dispatch (training/prefill scale).

    Each batch row is a dispatch group: expert positions are computed by a
    SORT within the group (counts + exclusive-cumsum over E), so every
    intermediate is O(S*K) per group -- no (T*K, E) cumsum, and the
    dispatch scatter is group-local, which GSPMD keeps on the data shard
    (measured: 191 GB/dev temp + 3.9 TB/dev collectives with a global
    scatter vs ~tens of GB after this rewrite).  Capacity is per group:
    C_g = ceil(S*K/E * capacity_factor); overflow tokens drop (standard).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    SK = S * K
    C = int(math.ceil(SK / E * cfg.capacity_factor))
    ef = eidx.reshape(B, SK)                                   # (B, SK)
    order = jnp.argsort(ef, axis=1, stable=True)               # (B, SK)
    e_sorted = jnp.take_along_axis(ef, order, axis=1)
    counts = jnp.sum(jax.nn.one_hot(ef, E, dtype=jnp.int32), axis=1)  # (B,E)
    starts = jnp.cumsum(counts, axis=1) - counts               # exclusive
    pos_sorted = (jnp.arange(SK)[None, :]
                  - jnp.take_along_axis(starts, e_sorted, axis=1))
    keep = (pos_sorted < C).astype(x.dtype)                    # (B, SK)
    pos_c = jnp.minimum(pos_sorted, C - 1)

    tok_sorted = order // K                                    # source token
    x_sorted = jnp.take_along_axis(
        x, tok_sorted[..., None], axis=1)                      # (B, SK, D)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None], (B, SK))
    buf = _shard_batch_dim(jnp.zeros((B, E, C, D), x.dtype), expert_dim=1)
    buf = buf.at[bidx, e_sorted, pos_c].add(x_sorted * keep[..., None])
    buf = _shard_batch_dim(buf, expert_dim=1)

    out_buf = _moe_ffn(p, buf, cfg)                            # (B, E, C, D)

    y_sorted = out_buf[bidx, e_sorted, pos_c] * keep[..., None]
    y_sorted = _shard_batch_dim(y_sorted)
    inv = jnp.argsort(order, axis=1)                           # unsort
    yk = jnp.take_along_axis(y_sorted, inv[..., None], axis=1)  # (B, SK, D)
    yk = yk * gate_vals.reshape(B, SK, 1).astype(x.dtype)
    return jnp.sum(yk.reshape(B, S, K, D), axis=2).reshape(B * S, D)


def moe_apply(p: Params, x: Array, cfg: ModelConfig,
              path: str = "moe") -> Tuple[Array, Array]:
    """Returns (y, aux_loss). Experts shard over 'model' (EP); dispatch is
    group-local so only the expert GEMM's buffers cross shards.  ``path``
    prefixes the shared expert's deployment-plan projection paths."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    xf = x.reshape(T, D)
    logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eidx = jax.lax.top_k(probs, K)                # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    if T * K <= 4096:
        y = _moe_small(p, xf, eidx, gate_vals, cfg)
    else:
        y = _moe_grouped(p, x, eidx.reshape(B, S, K),
                         gate_vals.reshape(B, S, K), cfg)

    # load-balance auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_loss

    if cfg.shared_expert_d_ff:
        y = y + mlp_apply(p["shared"], x, cfg,
                          path=f"{path}/shared").reshape(T, D)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# MoE with held experts (expert parallelism), every expert on the macro
# ---------------------------------------------------------------------------


def relu2_mlp_init(key, cfg: ModelConfig, d_ff: int, dtype=jnp.bfloat16):
    """Non-gated MLP, down(relu(up(x))^2) (nemotron_h's experts)."""
    ks = jax.random.split(key, 2)
    p, a = {}, {}
    p["w_up"], a["w_up"] = _init_dense(ks[0], cfg.d_model, d_ff,
                                       ("embed", "moe_ff"), dtype=dtype)
    p["w_down"], a["w_down"] = _init_dense(ks[1], d_ff, cfg.d_model,
                                           ("moe_ff", "embed"), dtype=dtype)
    return p, a


def relu2_mlp_apply(p: Params, x: Array, cfg: ModelConfig, path: str) -> Array:
    h = _dense(x, p["w_up"], cfg, f"{path}/w_up")
    return _dense(jnp.square(jax.nn.relu(h)), p["w_down"], cfg,
                  f"{path}/w_down")


# tap names (obs/taps.py) of the held-expert layers' counts: the (live
# row, held expert) pairs the router chose, the rows the held experts'
# CIM calls computed, and the held-expert calls taken
MOE_TAPS = ("moe_routed_rows", "moe_expert_rows", "moe_expert_calls")


def held_moe_init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    """Router over all ``cfg.router_experts`` experts; the ``n_experts``
    held here stacked on a leading axis under "experts"; the shared
    expert under "shared".  Experts are relu^2 MLPs (nemotron_h's)."""
    ks = jax.random.split(key, 3)
    p, a = {}, {}
    p["router"], a["router"] = _init_dense(
        ks[0], cfg.d_model, cfg.router_experts, ("embed", "experts_r"),
        dtype=jnp.float32)
    p["e_bias"] = jnp.zeros((cfg.router_experts,), jnp.float32)
    a["e_bias"] = ("experts_r",)
    keys = jax.random.split(ks[1], cfg.n_experts)
    p["experts"] = jax.vmap(
        lambda k: relu2_mlp_init(k, cfg, cfg.moe_d_ff, dtype)[0])(keys)
    a["experts"] = {k: ("experts",) + v for k, v in
                    relu2_mlp_init(ks[1], cfg, cfg.moe_d_ff, dtype)[1].items()}
    if cfg.shared_expert_d_ff:
        p["shared"], a["shared"] = relu2_mlp_init(
            ks[2], cfg, cfg.shared_expert_d_ff, dtype)
    return p, a


def held_moe_route(p: Params, xf: Array, cfg: ModelConfig
                   ) -> Tuple[Array, Array]:
    """Rows xf (T, D) -> each row's gate on each held expert, 0 where it
    did not choose it, and whether it chose it: (T, n_experts) each.

    The router is float32 at full precision, as the published code
    computes it: sigmoid scores, the top ``cfg.top_k`` of scores plus the
    choice bias ``e_bias``, gates taken from the plain scores, normalised
    to sum 1 and scaled by ``cfg.moe_routed_scaling``."""
    logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["e_bias"], cfg.top_k)          # (T,K)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg.moe_routed_scaling
    held = cfg.moe_first_expert + jnp.arange(cfg.n_experts)
    hit = idx[:, :, None] == held                                     # (T,K,E)
    gate = jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)
    return gate, jnp.any(hit, axis=1)


@scoped("lm.moe")
def _held_experts(p: Params, xf: Array, cfg: ModelConfig, path: str,
                  live: Optional[Array]) -> Array:
    """The held experts' part of the routed sum for rows xf (T, D),
    routed by ``held_moe_route``.  ``live`` ((T,) bool) marks the rows
    whose choices count.

    Each held expert computes by n, the live rows that chose it: nothing
    where n is 0; those rows gathered into a buffer of min(T,
    ``SKINNY_SUBLANE``) rows, the skinny kernel's route, where they fit;
    else every row, weighted by its gate (0 where the row did not choose
    the expert).  Rows quantize apart, so a routed row's output is the
    same in any buffer, and no row is dropped.  With analog noise on
    (``cfg.cim_noise_seed``), whose draws depend on the row count, every
    expert computes every row.  Under a tap collector that takes them it
    emits the ``MOE_TAPS`` counts."""
    T = xf.shape[0]
    gate, chose = held_moe_route(p, xf, cfg)
    if live is not None:
        chose = chose & live[:, None]
    n = jnp.sum(chose, axis=0, dtype=jnp.int32)                       # (E,)

    cap = min(T, SKINNY_SUBLANE)
    experts = p["experts"]

    def weights(e):
        return jax.tree.map(
            lambda v: jax.lax.dynamic_index_in_dim(v, e, keepdims=False),
            experts)

    def skip(acc, e, g, sel):
        return acc

    def every_row(acc, e, g, sel):
        y = relu2_mlp_apply(weights(e), xf, cfg, f"{path}/experts")
        return acc + y * g[:, None]

    def routed(acc, e, g, sel):
        rows = jnp.nonzero(sel, size=cap, fill_value=T)[0]
        xg = jnp.take(xf, rows, axis=0, mode="fill", fill_value=0)
        y = relu2_mlp_apply(weights(e), xg, cfg, f"{path}/experts")
        # the routed rows' outputs in place, 0 elsewhere: every_row's sum
        y = jnp.zeros((T,) + y.shape[1:], y.dtype).at[rows].set(y,
                                                                mode="drop")
        return acc + y * g[:, None]

    # a buffer of T rows is a pass over every row
    branches, rows_of = (((skip, every_row), (0, T)) if cap == T else
                         ((skip, routed, every_row), (0, cap, T)))
    if cfg.cim_noise_seed is not None:
        kind = jnp.full(n.shape, len(branches) - 1)
    else:
        kind = jnp.where(n == 0, 0,
                         jnp.where(n <= cap, 1, len(branches) - 1))
    if taps.active(MOE_TAPS[0]):
        taps.emit(MOE_TAPS[0], jnp.sum(n))
        taps.emit(MOE_TAPS[1], jnp.sum(jnp.asarray(rows_of, jnp.int32)[kind]))
        taps.emit(MOE_TAPS[2], jnp.sum(kind > 0, dtype=jnp.int32))

    def expert(acc, inp):
        return taps.switch(inp[1], branches, acc, inp[0], *inp[2:]), None

    y, _ = taps.scan(expert, jnp.zeros(xf.shape, jnp.float32),
                     (jnp.arange(cfg.n_experts), kind, gate.T, chose.T))
    return y


def held_moe_apply(p: Params, x: Array, cfg: ModelConfig, path: str = "moe",
                   live: Optional[Array] = None) -> Array:
    """x (B, S, D) -> the held experts' part of the routed sum, summed
    in float32, plus the shared expert (B, S, D) in x's dtype.
    ``live`` ((B,) bool, one row a sequence: decode) marks the rows
    whose choices count: a held expert no live row chose computes
    nothing."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    y = _held_experts(p, xf, cfg, path, live).astype(x.dtype)
    if "shared" in p:
        with jax.named_scope("lm.moe_shared"):
            y = y + relu2_mlp_apply(p["shared"], xf, cfg, f"{path}/shared")
    return y.reshape(B, S, D)


# ---------------------------------------------------------------------------
# Mamba2 (SSD) -- chunked parallel scan for train/prefill, step for decode
# ---------------------------------------------------------------------------


def mamba2_init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    D, DI, H = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    W, BC = cfg.ssm_conv_width, cfg.ssm_bc_dim
    ks = jax.random.split(key, 8)
    p, a = {}, {}
    # component projections (not one fused in_proj): each output dim is
    # TP-divisible (d_inner, 2*state), where the fused 2*DI+2*N+H is not --
    # this is what lets the SSM stack shard over "model" at all
    p["w_z"], a["w_z"] = _init_dense(ks[0], D, DI, ("embed", "ssm_inner"),
                                     dtype=dtype)
    p["w_x"], a["w_x"] = _init_dense(ks[4], D, DI, ("embed", "ssm_inner"),
                                     dtype=dtype)
    p["w_bc"], a["w_bc"] = _init_dense(ks[5], D, BC, ("embed", "state"),
                                       dtype=dtype)
    p["w_dt"], a["w_dt"] = _init_dense(ks[6], D, H, ("embed", "ssm_heads"),
                                       dtype=dtype)
    # separate depthwise convs per stream (x, B, C): no concat/split on a
    # sharded channel dim -> no resharding collective-permutes in the scan
    p["conv_x"] = (jax.random.normal(ks[1], (W, DI), jnp.float32) / W).astype(dtype)
    a["conv_x"] = ("conv", "ssm_inner")
    p["conv_b"] = (jax.random.normal(ks[7], (W, BC), jnp.float32) / W).astype(dtype)
    a["conv_b"] = ("conv", "state")
    p["conv_bias_x"] = jnp.zeros((DI,), dtype)
    a["conv_bias_x"] = ("ssm_inner",)
    p["conv_bias_b"] = jnp.zeros((BC,), dtype)
    a["conv_bias_b"] = ("state",)
    p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32))
    a["A_log"] = ("ssm_heads",)
    p["D_skip"] = jnp.ones((H,), jnp.float32)
    a["D_skip"] = ("ssm_heads",)
    p["dt_bias"] = jnp.log(jnp.expm1(
        jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32,
                                   math.log(1e-3), math.log(1e-1)))))
    a["dt_bias"] = ("ssm_heads",)
    p["gate_norm"] = jnp.zeros((DI,), dtype)
    a["gate_norm"] = ("ssm_inner",)
    p["out_proj"], a["out_proj"] = _init_dense(ks[3], DI, D,
                                               ("ssm_inner", "embed"), dtype=dtype)
    return p, a


def _segsum(a):
    """(..., Q) log-decays -> (..., Q, Q) lower-tri cumulative sums."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum over (j, i]
    mask = jnp.tril(jnp.ones((Q, Q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def _causal_conv(u, w, b, state=None):
    """Depthwise causal conv. u (B,S,C), w (W,C). state (B,W-1,C) for decode."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((u.shape[0], W - 1, u.shape[2]), u.dtype)
        u_p = jnp.concatenate([pad, u], axis=1)
    else:
        u_p = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    out = sum(u_p[:, i : i + u.shape[1], :] * w[i] for i in range(W)) + b
    new_state = u_p[:, -(W - 1):, :] if W > 1 else None
    return jax.nn.silu(out), new_state


def mamba2_apply(p: Params, x: Array, cfg: ModelConfig,
                 ssm_state=None, conv_state=None, decode: bool = False):
    """x (B,S,D). Returns (y, (new_ssm_state, new_conv_state))."""
    proj = _dense_group(x, p, ("w_z", "w_x", "w_bc", "w_dt"), cfg, "mamba/")
    y, new_state, new_conv = _mamba2_mixer(p, proj, x, cfg, ssm_state,
                                           conv_state, decode)
    out = _dense(y, p["out_proj"], cfg, "mamba/out_proj")
    if out.shape[1] != x.shape[1]:
        out = out[:, :x.shape[1]]
    return out, (new_state, new_conv)


@scoped("lm.ssm")
def _mamba2_mixer(p: Params, proj, x: Array, cfg: ModelConfig, ssm_state,
                  conv_state, decode: bool):
    """mamba2 between its projections: the causal convs, the chunked SSD
    scan (or the single-step recurrence), the state update and the gate.
    Returns (y (B, S padded to a chunk multiple, DI), new_ssm_state,
    new_conv_state).

    With ``cfg.ssm_n_groups`` G > 1 the B and C streams hold G groups of
    ``ssm_state`` channels, group g serving heads [g*H/G, (g+1)*H/G), and
    the gated norm normalises each group's DI/G channels apart (mamba_ssm's
    ``RMSNormGated`` with ``group_size = d_inner / n_groups``)."""
    B, S, D = x.shape
    DI, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    G = cfg.ssm_n_groups
    z, xc, BCc, dt_raw = (proj[n] for n in ("w_z", "w_x", "w_bc", "w_dt"))
    cs_x = cs_bc = None
    if conv_state is not None:
        cs_x, cs_bc = conv_state
    xc, new_cx = _causal_conv(xc, p["conv_x"], p["conv_bias_x"], cs_x)
    BCc, new_cbc = _causal_conv(BCc, p["conv_b"], p["conv_bias_b"], cs_bc)
    new_conv = (new_cx, new_cbc)
    Bc, Cc = jnp.split(BCc, [G * N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])                                          # (H,)

    # pad S to a chunk multiple; padded steps get dt=0 => identity decay,
    # zero state update, so the recurrence is unaffected (mamba2_apply
    # slices the padding off after the output projection)
    if not decode:
        Q0 = min(cfg.ssm_chunk, S)
        pad = (Q0 - S % Q0) % Q0
        if pad:
            z = jnp.pad(z, ((0, 0), (0, pad), (0, 0)))
            xc = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
            Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
            Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            S = S + pad

    xh = xc.reshape(B, S, H, P).astype(jnp.float32)
    Bh = Bc.astype(jnp.float32)                                       # (B,S,GN)
    Ch = Cc.astype(jnp.float32)
    ssd = partial(_ssd, decode=decode, chunk=cfg.ssm_chunk)
    if G == 1:
        y, new_state = ssd(xh, Bh, Ch, dt, A, ssm_state, p["D_skip"])
    else:
        # each group is a G=1 SSD over its own heads: vmap over groups
        Hg = H // G
        if ssm_state is None:
            ssm_state = jnp.zeros((B, H, P, N), jnp.float32)
        y, new_state = jax.vmap(ssd, in_axes=(2, 2, 2, 2, 0, 1, 0),
                                out_axes=(2, 1))(
            xh.reshape(B, S, G, Hg, P), Bh.reshape(B, S, G, N),
            Ch.reshape(B, S, G, N), dt.reshape(B, S, G, Hg),
            A.reshape(G, Hg), ssm_state.reshape(B, G, Hg, P, N),
            p["D_skip"].reshape(G, Hg))
        y = y.reshape(B, S, DI)
        new_state = new_state.reshape(B, H, P, N)

    y = y.astype(x.dtype) * jax.nn.silu(z)
    if G == 1:
        y = rms_norm(y, p["gate_norm"], cfg.norm_eps)
    else:
        y = rms_norm(y.reshape(B, S, G, DI // G),
                     p["gate_norm"].reshape(G, DI // G),
                     cfg.norm_eps).reshape(B, S, DI)
    return y, new_state, new_conv


def _ssd(xh, Bh, Ch, dt, A, ssm_state, D_skip, decode: bool, chunk: int):
    """The selective-state recurrence of heads that share one B and C:
    xh (B,S,H,P), Bh/Ch (B,S,N), dt (B,S,H), A/D_skip (H,), ssm_state
    (B,H,P,N) or None (zeros).  Returns (y (B,S,H*P), new_state)."""
    B, S, H, P = xh.shape
    N = Bh.shape[-1]
    if decode:
        # single-step recurrence: state (B,H,P,N)
        a = jnp.exp(dt[:, 0] * A[None, :])                            # (B,H)
        dBx = jnp.einsum("bh,bn,bhp->bhpn", dt[:, 0], Bh[:, 0], xh[:, 0])
        new_state = ssm_state * a[..., None, None] + dBx
        y = jnp.einsum("bn,bhpn->bhp", Ch[:, 0], new_state)
        y = y + D_skip[None, :, None] * xh[:, 0]
        return y.reshape(B, 1, H * P), new_state
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    xb = xh.reshape(B, nc, Q, H, P)
    Bb = Bh.reshape(B, nc, Q, N)
    Cb = Ch.reshape(B, nc, Q, N)
    dtb = dt.reshape(B, nc, Q, H)
    a = dtb * A  # (B,nc,Q,H) log-decay
    a_t = a.transpose(0, 1, 3, 2)                                     # (B,nc,H,Q)
    Lmat = jnp.exp(_segsum(a_t))                                      # (B,nc,H,Q,Q)
    # intra-chunk (diagonal) term
    scores = jnp.einsum("bcqn,bckn->bcqk", Cb, Bb)                    # (B,nc,Q,Q)
    y_diag = jnp.einsum("bcqk,bchqk,bckh,bckhp->bcqhp",
                        scores, Lmat, dtb, xb)
    # decay from step q to end of chunk: sum_{i>q} a_i
    a_cum = jnp.cumsum(a_t, axis=-1)                                  # (B,nc,H,Q)
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)                   # (B,nc,H,Q)
    states = jnp.einsum("bchq,bcqh,bcqn,bcqhp->bchpn",
                        decay_to_end, dtb, Bb, xb)                    # (B,nc,H,P,N)
    chunk_decay = jnp.exp(a_cum[..., -1])                             # (B,nc,H)

    def scan_fn(h, inp):
        st, dec = inp
        h_new = h * dec[..., None, None] + st
        return h_new, h
    init = (ssm_state if ssm_state is not None
            else jnp.zeros((B, H, P, N), jnp.float32))
    new_state, h_prev = jax.lax.scan(
        scan_fn,
        init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)                          # (B,nc,H,P,N)
    decay_from_start = jnp.exp(a_cum)                                 # (B,nc,H,Q)
    y_off = jnp.einsum("bcqn,bchq,bchpn->bcqhp",
                       Cb, decay_from_start, h_prev)
    y = (y_diag + y_off).reshape(B, S, H, P)
    y = y + D_skip[None, None, :, None] * xh
    return y.reshape(B, S, H * P), new_state
