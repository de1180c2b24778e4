"""Decoder LM assembly for every architecture family.

One functional model with four entry points:

  init(key, cfg)                          -> (params, axes)
  forward(params, cfg, tokens, ...)       -> logits, aux      (train path)
  prefill(params, cfg, tokens, cache)     -> logits, cache    (inference)
  decode_step(params, cfg, token, cache, live=None)
                                          -> logits, cache    (inference)
  prefill_into_slot(params, cfg, tokens, cache, slot)
                                          -> logits, cache    (serving)

The decode cache tracks a per-slot ``(batch,)`` position vector, and
``reset_slot`` / ``prefill_into_slot`` give the continuous-batching
scheduler (launch/scheduler.py) slot-level admission into a shared pool.

Layer stacks are scanned (stacked params, jax.lax.scan) so compile time is
depth-independent -- required for 40-cell dry-runs on CPU and the right
call for production.  Training bodies are rematerialized (jax.checkpoint)
so the dry-run memory analysis reflects a deployable activation footprint.

Families:
  dense    : [attn, mlp] x L
  moe      : [attn, moe] x L
  ssm      : [mamba2] x L
  hybrid   : groups of `period` mamba layers + ONE shared attn+mlp block
             (zamba2 -- weight co-location showcase, see DESIGN.md); or,
             with ``cfg.layer_types``, one pre-norm residual block per
             character -- "M" mamba2, "E" held-expert MoE, "*" attention
             (nemotron_h) -- each kind stacked on its own leading axis
  vlm      : dense backbone + frontend patch-embedding stub, prefix-LM
  audio    : dense backbone over codec-token frames (frontend stub)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.engine import FusedPackedCimWeights
from ..obs import taps
from ..obs.scopes import scoped
from ..obs.trace import span
from . import layers as L
from .config import LAYER_KINDS, ModelConfig

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stacked_init(init_fn, key, n: int):
    """vmap a per-layer init over n layer keys -> stacked params + axes."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, axes = init_fn(jax.random.PRNGKey(0))  # axes from one instantiation
    axes = jax.tree.map(lambda a: ("layers",) + a, axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    return params, axes


def _block_init(cfg: ModelConfig, dtype):
    """Per-layer (attn/mixer + mlp/moe + norms) init for the scanned stack."""

    def init(key):
        ks = jax.random.split(key, 4)
        p, a = {}, {}
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            p["attn"], a["attn"] = L.attention_init(ks[0], cfg, dtype)
            p["ln1"], a["ln1"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
            p["ln2"], a["ln2"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
            if cfg.family == "moe":
                p["moe"], a["moe"] = L.moe_init(ks[1], cfg, dtype)
                if cfg.d_ff:  # dense residual branch (arctic)
                    p["mlp"], a["mlp"] = L.mlp_init(ks[2], cfg, cfg.d_ff, dtype)
            else:
                p["mlp"], a["mlp"] = L.mlp_init(ks[1], cfg, cfg.d_ff, dtype)
        elif cfg.family in ("ssm", "hybrid"):
            p["mamba"], a["mamba"] = L.mamba2_init(ks[0], cfg, dtype)
            p["ln1"], a["ln1"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
        else:
            raise ValueError(cfg.family)
        return p, a

    return init


def _kind_block_init(cfg: ModelConfig, kind: str, dtype):
    """Per-layer init of one ``layer_types`` kind: its mixer and norm."""
    mixer = {"M": ("mamba", L.mamba2_init), "E": ("moe", L.held_moe_init),
             "*": ("attn", L.attention_init)}[kind]

    def init(key):
        p, a = {}, {}
        p[mixer[0]], a[mixer[0]] = mixer[1](key, cfg, dtype)
        p["ln1"], a["ln1"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
        return p, a

    return init


def _pattern_init(cfg: ModelConfig, key, dtype):
    """params["layers"] of a ``layer_types`` stack: {kind: that kind's
    layers stacked in order}; a layer's index there is its rank among the
    layers of its kind, as in the cache."""
    p, a = {}, {}
    for kind, k in zip(LAYER_KINDS, jax.random.split(key, len(LAYER_KINDS))):
        if cfg.layer_count(kind):
            p[kind], a[kind] = _stacked_init(_kind_block_init(cfg, kind, dtype),
                                             k, cfg.layer_count(kind))
    return p, a


def _shared_block_init(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 2)
    p, a = {}, {}
    p["attn"], a["attn"] = L.attention_init(ks[0], cfg, dtype)
    p["mlp"], a["mlp"] = L.mlp_init(ks[1], cfg, cfg.d_ff, dtype)
    p["ln1"], a["ln1"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
    p["ln2"], a["ln2"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
    return p, a


def init(key, cfg: ModelConfig, pack_cim: bool = False) -> Tuple[Params, Dict]:
    """Initialise params.  ``pack_cim=True`` (requires cfg.cim_mode) runs
    the PTQ weight-conditioning pipeline on every projection at load time,
    returning ``PackedCimWeights`` leaves -- the write-once/compute-many
    deployment shape (see pack_cim_params).  The axes tree describes the
    UNPACKED float params (sharding rules apply to training layouts)."""
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_shared, k_head = jax.random.split(key, 4)
    p, a = {}, {}
    p["embed"] = (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype)
    a["embed"] = ("vocab", "head_embed")
    if cfg.layer_types:
        p["layers"], a["layers"] = _pattern_init(cfg, k_layers, dtype)
    else:
        blk_init = _block_init(cfg, dtype)
        p["layers"], a["layers"] = _stacked_init(blk_init, k_layers,
                                                 cfg.n_layers)
    if cfg.family == "hybrid" and not cfg.layer_types:
        p["shared"], a["shared"] = _shared_block_init(cfg, k_shared, dtype)
    p["ln_f"], a["ln_f"] = jnp.zeros((cfg.d_model,), dtype), ("embed",)
    if not cfg.tie_embeddings:
        p["lm_head"], a["lm_head"] = L._init_dense(
            k_head, cfg.d_model, cfg.vocab_size, ("head_embed", "vocab"), dtype=dtype)
    if pack_cim:
        p = pack_cim_params(p, cfg)
    return p, a


# ---------------------------------------------------------------------------
# prepacked CIM weights (weight-stationary serving)
# ---------------------------------------------------------------------------


# Projection leaves consumed by layers._dense -- the matmuls the macro
# executes.  Everything else (embeddings, lm_head, MoE expert einsums,
# routers, convs, norms) stays float.
_CIM_PACKABLE = frozenset({
    "wq", "wk", "wv", "wo",                      # attention
    "w1", "w2", "w3",                            # (shared-)MLP
    "w_z", "w_x", "w_bc", "w_dt", "out_proj",    # mamba2 projections
    "w_up", "w_down",                            # relu^2 (held) experts
})
# Keys of stacked layers, dropped from plan paths: "layers", and a
# layer_types stack's kinds under it.
_STACK_KEYS = frozenset(("layers",) + LAYER_KINDS)


def _walk_packable(tree, visit, path=()):
    """Rebuild ``tree`` with ``visit(plan_path, leaf)`` applied to every
    _dense-consumed projection leaf.  ``plan_path`` is the deployment-plan
    path convention: tree keys joined with "/", the scanned-stack key
    "layers" dropped (one entry covers every depth of a scanned stack) --
    e.g. "attn/wq", "moe/shared/w1", "shared/mlp/w2", "mamba/out_proj"
    (a layer_types stack's kind keys are dropped the same way).
    MoE expert tensors reuse the w1/w2/w3 names but feed einsums, not
    _dense, so the level directly under "moe" is skipped (the shared
    expert under moe/shared IS packable, as are the held experts under
    moe/experts, which run through _dense).
    """
    out = {}
    for k, v in tree.items():
        sub = path if k in _STACK_KEYS else path + (k,)
        if isinstance(v, dict):
            out[k] = _walk_packable(v, visit, sub)
        elif k in _CIM_PACKABLE and not (len(path) >= 1 and path[-1] == "moe"):
            out[k] = visit("/".join(sub), v)
        else:
            out[k] = v
    return out


def iter_packable_paths(params: Params) -> Dict[str, Tuple[int, ...]]:
    """Deployment-plan path -> leaf shape for every _dense projection.

    The planner's site list: each path is one plan-addressable projection
    (scanned stacks appear once, with their (layers, K, N) stacked shape).
    """
    sites: Dict[str, Tuple[int, ...]] = {}

    def visit(path, v):
        sites[path] = tuple(v.shape)
        return v

    _walk_packable(params, visit)
    return sites


# Projection groups that consume the same input activation -- the fusion
# candidates (models.layers._dense_group consumes the fused leaves).  Which
# members actually fuse is decided per group by the deployment plan: only
# members resolving to the SAME PlanEntry pack together.
_FUSE_GROUPS = (("wq", "wk", "wv"),            # attention QKV
                ("w1", "w3"),                  # SwiGLU gate/up
                ("w_z", "w_x", "w_bc", "w_dt"))  # mamba2 input projections


def _pack_single(path: str, v, cfg: ModelConfig):
    eng = L.cim_engine(cfg, path)
    if eng.fidelity == "float":              # plan keeps this site off-macro
        return v
    # (K, N) shared-block weights; (layers, K, N) a scanned stack;
    # (layers, experts, K, N) held experts
    pack = eng.pack
    for _ in range(v.ndim - 2):
        pack = jax.vmap(pack)
    return pack(v)


def _pack_tree(tree: Params, cfg: ModelConfig, path=()) -> Params:
    """Fusion-aware packing walk (see pack_cim_params).

    At every dict level, each fusion-candidate group splits into
    partitions by resolved PlanEntry; partitions of two or more sites
    concatenate along N and pack as ONE ``FusedPackedCimWeights`` under a
    "wq+wk+wv"-style key (per-channel scales and quantization are column-
    local, so the fused pack is bit-identical per segment to the separate
    packs).  Everything else packs -- or stays raw -- exactly as before.
    """
    def packable(k):
        return (k in _CIM_PACKABLE
                and not (len(path) >= 1 and path[-1] == "moe"))

    out = dict(tree)
    consumed = set()
    if cfg.cim_fuse:
        for members in _FUSE_GROUPS:
            present = [m for m in members
                       if not isinstance(tree.get(m), dict)
                       and getattr(tree.get(m), "ndim", 0) in (2, 3)
                       and packable(m)]
            if len(present) < 2:
                continue
            prefix = "/".join(path) + "/" if path else ""
            for ecfg, fid, g in L.fusion_partitions(cfg, prefix, present):
                eng = L.CimEngine(cfg=ecfg)
                wcat = jnp.concatenate([tree[m] for m in g], axis=-1)
                pk = (jax.vmap(eng.pack)(wcat) if wcat.ndim == 3
                      else eng.pack(wcat))
                out[L.FUSED_SEP.join(g)] = FusedPackedCimWeights(
                    packed=pk, seg_names=tuple(g),
                    seg_dims=tuple(int(tree[m].shape[-1]) for m in g))
                consumed.update(g)
    for k, v in tree.items():
        if k in consumed:
            del out[k]
            continue
        sub = path if k in _STACK_KEYS else path + (k,)
        if isinstance(v, dict):
            out[k] = _pack_tree(v, cfg, sub)
        elif packable(k):
            out[k] = _pack_single("/".join(sub), v, cfg)
    return out


@partial(jax.jit, static_argnames=("cfg",))
def _pack_cim_params_jit(params: Params, cfg: ModelConfig) -> Params:
    return _pack_tree(params, cfg)


def pack_cim_params(params: Params, cfg: ModelConfig) -> Params:
    """Replace every _dense-consumed projection with PackedCimWeights.

    This is the software analogue of writing the SRAM arrays: per-channel
    SMF scales, integer weights and folded MSB bit-planes are computed
    ONCE here, as int8 tiles; prefill/decode then run activation-only
    quantization.  Stacked (scanned) layer weights are packed under vmap,
    so the packed leaves keep their leading layer axis and drop straight
    into the scanned stacks.  Bit-identical to unpacked cim_mode execution.

    The packing pipeline is jit-compiled HERE (cfg is static): eager and
    outer-jit callers get the same fused scale arithmetic, so the packed
    leaves are bit-identical however packing is invoked (regression-tested
    in tests/test_engine.py -- eager packing used to differ in the last
    scale ulp, flipping occasional magnitudes).

    Under a deployment plan (cfg.cim_plan, repro.plan) each projection
    packs under ITS OWN entry's CCIMConfig -- the packed leaf carries that
    config as static pytree metadata, so mixed packs coexist in one
    compiled step -- and plan-fidelity "float" sites stay raw float
    matrices (served as plain matmuls).

    With cfg.cim_fuse (the default) plan-compatible projections that share
    an input activation (QKV; gate/up; the mamba2 input projections) pack
    as ONE wide ``FusedPackedCimWeights`` leaf with per-segment N-offsets
    -- the decode hot path then runs ~3 wide GEMMs per block instead of ~7
    skinny ones, with per-projection outputs (and noise streams) still
    bit-identical to the unfused pack (see DESIGN.md section 9).

    The call is host span ``launch.pack`` and returns once the pack is
    on the device (under an outer jit it only traces).
    """
    if not cfg.cim_mode:
        raise ValueError("pack_cim_params requires cfg.cim_mode=True")
    with span("launch.pack"):
        return jax.block_until_ready(_pack_cim_params_jit(params, cfg))


# ---------------------------------------------------------------------------
# shared forward machinery
# ---------------------------------------------------------------------------


def _is_local_arr(cfg: ModelConfig) -> Array:
    return jnp.asarray(
        [cfg.layer_is_local(i) for i in range(cfg.n_layers)], jnp.bool_)


@scoped("lm.embed")
def _embed(p, cfg: ModelConfig, tokens: Array,
           frontend_embs: Optional[Array]) -> Tuple[Array, int]:
    x = jnp.take(p["embed"], tokens, axis=0)
    if cfg.family in ("vlm", "audio") and frontend_embs is not None:
        x = jnp.concatenate([frontend_embs.astype(x.dtype), x], axis=1)
    n_prefix = (frontend_embs.shape[1]
                if (cfg.prefix_lm and frontend_embs is not None) else 0)
    return x, n_prefix


@scoped("lm.head")
def _logits(p, cfg: ModelConfig, x: Array) -> Array:
    x = L.rms_norm(x, p["ln_f"], cfg.norm_eps)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = x @ head.astype(x.dtype)
    return L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def _attn_block(blk, x, cfg, positions, is_local, kv=None, cache_pos=None,
                n_prefix=0, return_kv=False, prefix="", block_table=None,
                write_mask=None):
    """``prefix`` qualifies the deployment-plan projection paths: the
    scanned per-layer stacks use "" (paths "attn/wq", "mlp/w1", ...), the
    zamba2 shared block passes "shared/".  ``block_table`` switches the
    KV cache to paged pools and ``write_mask`` redirects non-live rows'
    paged writes to the trash block (see layers.attention_apply)."""
    h, new_kv = L.attention_apply(
        blk["attn"], L.rms_norm(x, blk["ln1"], cfg.norm_eps), cfg, positions,
        is_local, kv_cache=kv, cache_pos=cache_pos, n_prefix=n_prefix,
        return_kv=return_kv, path=prefix + "attn", block_table=block_table,
        write_mask=write_mask)
    x = x + h
    if "moe" in blk:
        h, aux = L.moe_apply(blk["moe"], L.rms_norm(x, blk["ln2"], cfg.norm_eps),
                             cfg, path=prefix + "moe")
        if "mlp" in blk:  # arctic: dense residual in parallel with MoE
            h = h + L.mlp_apply(blk["mlp"], L.rms_norm(x, blk["ln2"], cfg.norm_eps),
                                cfg, path=prefix + "mlp")
    elif "mlp" in blk:
        h, aux = L.mlp_apply(blk["mlp"], L.rms_norm(x, blk["ln2"], cfg.norm_eps)
                             , cfg, path=prefix + "mlp"), jnp.float32(0.0)
    else:
        h, aux = 0.0, jnp.float32(0.0)
    return x + h, new_kv, aux


# ---------------------------------------------------------------------------
# train/eval forward (no cache)
# ---------------------------------------------------------------------------


def hidden_states(params, cfg: ModelConfig, tokens: Array,
                  frontend_embs: Optional[Array] = None,
                  remat: bool = True) -> Tuple[Array, Array]:
    """tokens (B, S_text) -> final hidden (B, S_total, D), aux_loss."""
    x, n_prefix = _embed(params, cfg, tokens, frontend_embs)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    if cfg.family in ("ssm", "hybrid"):
        x = _ssm_stack(params, cfg, x, positions, remat)
        aux = jnp.float32(0.0)
    else:
        def body(x, scanned):
            blk, is_local = scanned
            x, _, aux = _attn_block(blk, x, cfg, positions, is_local,
                                    n_prefix=n_prefix)
            return x, aux
        if remat:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, (params["layers"], _is_local_arr(cfg)))
        aux = jnp.sum(auxs)
    return x, aux


def forward(params, cfg: ModelConfig, tokens: Array,
            frontend_embs: Optional[Array] = None,
            remat: bool = True) -> Tuple[Array, Array]:
    """tokens (B, S_text) -> logits (B, S_total, V), aux_loss (scalar)."""
    x, aux = hidden_states(params, cfg, tokens, frontend_embs, remat)
    return _logits(params, cfg, x), aux


def _slice_layers(tree, lo: int, hi: int):
    return jax.tree.map(lambda v: v[lo:hi], tree)


def _ssm_stack(params, cfg: ModelConfig, x, positions, remat,
               period_blocks=True):
    """Mamba2 stack; for 'hybrid', one SHARED attn block every `period`,
    or the ``layer_types`` stack."""
    if cfg.layer_types:
        return _pattern_stack(params, cfg, x, positions)[0]

    def body(x, blk):
        h, _ = L.mamba2_apply(
            blk["mamba"], L.rms_norm(x, blk["ln1"], cfg.norm_eps), cfg)
        return x + h, None
    if remat:
        body = jax.checkpoint(body)

    if cfg.family == "ssm" or not cfg.shared_attn_period:
        x, _ = jax.lax.scan(body, x, params["layers"])
        return x

    period = cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    done = 0
    for g in range(n_groups):
        grp = _slice_layers(params["layers"], g * period, (g + 1) * period)
        x, _ = jax.lax.scan(body, x, grp)
        done = (g + 1) * period
        x, _, _ = _attn_block(params["shared"], x, cfg, positions,
                              jnp.bool_(False), prefix="shared/")
    if done < cfg.n_layers:
        grp = _slice_layers(params["layers"], done, cfg.n_layers)
        x, _ = jax.lax.scan(body, x, grp)
    return x


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> Dict[str, Array]:
    """Allocate the decode cache for `batch` sequences of up to `max_seq`,
    KV and conv state in ``dtype`` (default: ``cfg.act_dtype``).

    ``pos`` is a per-slot ``(batch,)`` vector: every sequence in the pool
    tracks its own write position, so slots at different depths (continuous
    batching, launch/scheduler.py) share one cache and one compiled step.
    """
    c: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    dtype = jnp.dtype(cfg.act_dtype) if dtype is None else dtype
    hkv, dh = cfg.padded_kv_heads, cfg.head_dim
    if cfg.attn_layers:
        shape = (cfg.attn_layers, batch, max_seq, hkv, dh)
        c["k"] = jnp.zeros(shape, dtype)
        c["v"] = jnp.zeros(shape, dtype)
    c.update(_state_cache(cfg, batch, dtype))
    if cfg.family == "hybrid" and cfg.shared_attn_period:
        n_inv = cfg.n_layers // cfg.shared_attn_period
        c["shared_k"] = jnp.zeros((n_inv, batch, max_seq, hkv, dh), dtype)
        c["shared_v"] = jnp.zeros((n_inv, batch, max_seq, hkv, dh), dtype)
    return c


def _state_cache(cfg: ModelConfig, batch: int, dtype) -> Dict[str, Array]:
    """Per-slot SSM and conv state of the layers that keep it."""
    c: Dict[str, Any] = {}
    if cfg.ssm_layers:
        H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        W, n = cfg.ssm_conv_width, cfg.ssm_layers
        c["ssm"] = jnp.zeros((n, batch, H, P, N), jnp.float32)
        c["conv_x"] = jnp.zeros((n, batch, W - 1, cfg.d_inner), dtype)
        c["conv_bc"] = jnp.zeros((n, batch, W - 1, cfg.ssm_bc_dim), dtype)
    return c


def init_paged_cache(cfg: ModelConfig, batch: int, n_blocks: int,
                     block_size: int, n_tbl: int,
                     dtype=None) -> Dict[str, Array]:
    """Allocate the PAGED decode cache: per-layer global KV block pools
    plus a per-slot block table.

    KV memory no longer scales with ``batch * max_seq`` -- the pools are
    ``(attention layers, n_blocks, block_size, hkv, dh)`` shared by every slot,
    and slot b's logical row p resolves through ``table[b, p //
    block_size]``.  The presence of the ``"table"`` key is what flips
    prefill/decode_step/verify_step (and the slot helpers) into paged
    mode; SSM/conv state stays per-slot dense (it is O(1) per slot, not
    O(max_seq)).  KV and conv state are kept in ``dtype`` (default:
    ``cfg.act_dtype``).  Block 0 is reserved as the trash block by the
    allocator (launch/scheduler.py); an all-zero table row -- the reset
    state -- therefore points every position at garbage no live slot
    reads.
    """
    c: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32),
                         "table": jnp.zeros((batch, n_tbl), jnp.int32)}
    dtype = jnp.dtype(cfg.act_dtype) if dtype is None else dtype
    hkv, dh = cfg.padded_kv_heads, cfg.head_dim
    if cfg.attn_layers:
        shape = (cfg.attn_layers, n_blocks, block_size, hkv, dh)
        c["k"] = jnp.zeros(shape, dtype)
        c["v"] = jnp.zeros(shape, dtype)
    c.update(_state_cache(cfg, batch, dtype))
    if cfg.family == "hybrid" and cfg.shared_attn_period:
        n_inv = cfg.n_layers // cfg.shared_attn_period
        c["shared_k"] = jnp.zeros((n_inv, n_blocks, block_size, hkv, dh),
                                  dtype)
        c["shared_v"] = jnp.zeros((n_inv, n_blocks, block_size, hkv, dh),
                                  dtype)
    return c


# ---------------------------------------------------------------------------
# slot-level cache ops (continuous batching, launch/scheduler.py)
# ---------------------------------------------------------------------------
# Every cache entry carries the pool (batch) dimension at axis 1 -- they are
# stacked per layer/group -- except "pos", which is the (batch,) position
# vector itself.  ``slot`` may be a traced scalar, so one compiled
# reset/refill executable serves every slot in the pool.
#
# In PAGED caches (init_paged_cache) the KV entries are global block pools
# with NO batch axis: the slot helpers pass them through whole (every slot
# addresses the pools through its table row), and per-slot state is just
# "pos", "table" and the SSM/conv entries.


_POOL_KEYS = frozenset({"k", "v", "shared_k", "shared_v"})


def is_paged(cache: Dict) -> bool:
    return "table" in cache


def _slot_axis(key: str) -> int:
    return 0 if key in ("pos", "table") else 1


def slot_slice(cache: Dict, slot) -> Dict:
    """Extract a batch-1 view of one pool slot (same structure, batch=1).
    Paged KV pools are returned whole -- they are shared, and the slot's
    table row is what scopes them."""
    paged = is_paged(cache)
    return {k: (v if paged and k in _POOL_KEYS else
                jax.lax.dynamic_slice_in_dim(v, slot, 1, _slot_axis(k)))
            for k, v in cache.items()}


def slot_update(cache: Dict, sub: Dict, slot) -> Dict:
    """Write a batch-1 sub-cache back into pool slot ``slot``."""
    paged = is_paged(cache)
    return {k: (sub[k].astype(cache[k].dtype)
                if paged and k in _POOL_KEYS else
                jax.lax.dynamic_update_slice_in_dim(
                    cache[k], sub[k].astype(cache[k].dtype), slot,
                    _slot_axis(k)))
            for k in cache}


def _zeroed_slot(cache: Dict, slot) -> Dict:
    """A zeroed batch-1 sub-cache for ``slot`` -- the reset state.

    KV rows do not strictly need zeroing -- the attention validity mask
    hides everything at or beyond ``pos`` -- but SSM/conv state feeds the
    recurrence as an initial value, so a freed slot MUST be cleared before
    its next prefill.  One op clears both uniformly.

    Paged caches keep the pools (shared!) AND the slot's table row: block
    mapping is owned by the scheduler's allocator, which arms the table
    BEFORE prefilling and clears it at harvest -- a reset between the two
    must not sever the mapping.
    """
    paged = is_paged(cache)
    sub = slot_slice(cache, slot)
    return {k: (v if paged and k in _POOL_KEYS | {"table"}
                else jnp.zeros_like(v))
            for k, v in sub.items()}


def reset_slot(cache: Dict, slot) -> Dict:
    """Zero one slot's state (KV rows, SSM/conv state, position)."""
    return slot_update(cache, _zeroed_slot(cache, slot), slot)


@scoped("lm.prefill")
def prefill_into_slot(params, cfg: ModelConfig, tokens: Array, cache: Dict,
                      slot, frontend_embs: Optional[Array] = None
                      ) -> Tuple[Array, Dict]:
    """Prefill ONE request (tokens (1, P)) into pool slot ``slot``.

    The slot is reset, the prompt runs a batch-1 prefill against the
    slot-sliced cache, and the result is scattered back -- other slots'
    state is untouched, shapes are static, and ``slot`` may be traced, so
    the scheduler refills any freed slot through one AOT-compiled
    executable without recompiling.
    """
    logits, sub = prefill(params, cfg, tokens, _zeroed_slot(cache, slot),
                          frontend_embs)
    return logits, slot_update(cache, sub, slot)


@scoped("lm.prefill")
def prefill_chunk_into_slot(params, cfg: ModelConfig, tokens: Array,
                            cache: Dict, slot) -> Tuple[Array, Dict]:
    """Advance ONE slot's prefill by one chunk (tokens (1, C)).

    The chunk starts at the slot's current ``cache["pos"]`` (the
    scheduler arms pos before the first chunk and tracks progress through
    it), runs a batch-1 forward at absolute positions [pos, pos+C), and
    leaves pos at pos+C.  Unlike ``prefill_into_slot`` the slot is NOT
    reset -- earlier chunks' KV rows (or a shared prefix's refcounted
    blocks) are the context this chunk attends to.

    Returns logits for ALL C chunk positions: the scheduler samples the
    request's first token from row ``plen-1 - start`` of the final chunk.
    For attention families every row is bit-identical to the same row of
    a single-shot prefill (row-local GEMMs + per-row softmax over an
    identical masked key stream), so chunked admission preserves the
    token contract.  SSM/hybrid chunks carry conv+SSM state across calls
    and are bit-identical when chunk boundaries align with
    ``cfg.ssm_chunk`` and prompt lengths are chunk multiples (the
    scheduler enforces this; a garbage chunk tail would corrupt the
    recurrent state, unlike attention where the validity horizon masks
    it).
    """
    sub = slot_slice(cache, slot)
    x, n_prefix = _embed(params, cfg, tokens, None)
    B, S, _ = x.shape
    pos = sub["pos"]
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    sub = dict(sub)
    tbl = sub.get("table")

    if cfg.family in ("ssm", "hybrid"):
        x, sub = _ssm_stack_cached(params, cfg, x, positions, sub,
                                   decode=False, chunked=True)
    else:
        def body(x, scanned):
            blk, is_local, ck, cv = scanned
            x, new_kv, _ = _attn_block(blk, x, cfg, positions, is_local,
                                       kv=(ck, cv), cache_pos=pos,
                                       n_prefix=n_prefix, block_table=tbl)
            return x, new_kv
        x, (ck, cv) = taps.scan(
            body, x, (params["layers"], _is_local_arr(cfg), sub["k"],
                      sub["v"]))
        sub["k"], sub["v"] = ck, cv
    sub["pos"] = pos + S
    logits = _logits(params, cfg, x)
    return logits, slot_update(cache, sub, slot)


# ---------------------------------------------------------------------------
# inference: prefill + decode
# ---------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, tokens: Array, cache: Dict,
            frontend_embs: Optional[Array] = None) -> Tuple[Array, Dict]:
    """Run the prompt, fill the cache, return last-position logits."""
    x, n_prefix = _embed(params, cfg, tokens, frontend_embs)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    cache = dict(cache)
    tbl = cache.get("table")

    if cfg.family in ("ssm", "hybrid"):
        x, cache = _ssm_stack_cached(params, cfg, x, positions, cache,
                                     decode=False)
    else:
        pos0 = jnp.zeros((B,), jnp.int32)
        def body(x, scanned):
            blk, is_local, ck, cv = scanned
            x, new_kv, _ = _attn_block(blk, x, cfg, positions, is_local,
                                       kv=(ck, cv), cache_pos=pos0,
                                       n_prefix=n_prefix, block_table=tbl)
            return x, new_kv
        x, (ck, cv) = taps.scan(
            body, x, (params["layers"], _is_local_arr(cfg), cache["k"], cache["v"]))
        cache["k"], cache["v"] = ck, cv
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    logits = _logits(params, cfg, x[:, -1:, :])
    return logits, cache


@scoped("lm.step")
def decode_step(params, cfg: ModelConfig, token: Array, cache: Dict,
                live: Optional[Array] = None) -> Tuple[Array, Dict]:
    """token (B, 1) -> logits (B, 1, V); cache advanced by one position.

    Each slot decodes at its own ``cache["pos"]`` entry.  ``live`` ((B,)
    bool) freezes finished slots: their position does not advance, so a
    dead slot idles at a fixed depth until the scheduler refills it
    (``prefill_into_slot``) -- its logits are computed but discarded.
    In PAGED caches ``live`` additionally masks the side effects a
    frozen slot must not have: its KV write is redirected to the trash
    block (its table may alias blocks a live request reads -- shared
    prefixes, or its own half-prefilled chunks) and its SSM/conv state
    is held (a filling slot's recurrence must survive interleaved pool
    steps until its next chunk).
    """
    with jax.named_scope("lm.embed"):
        x = jnp.take(params["embed"], token, axis=0)
    pos = cache["pos"]
    positions = pos[:, None].astype(jnp.int32)
    cache = dict(cache)
    tbl = cache.get("table")
    wmask = live if (live is not None and tbl is not None) else None

    if cfg.family in ("ssm", "hybrid"):
        old = {k: cache[k] for k in ("ssm", "conv_x", "conv_bc")
               if k in cache}
        x, cache = _ssm_stack_cached(params, cfg, x, positions, cache,
                                     decode=True, write_mask=wmask,
                                     live=live)
        if wmask is not None:
            m = wmask[None, :, None, None]
            for k, v in old.items():
                keep = m[..., None] if cache[k].ndim == 5 else m
                cache[k] = jnp.where(keep, cache[k], v)
    else:
        def body(x, scanned):
            blk, is_local, ck, cv = scanned
            x, new_kv, _ = _attn_block(blk, x, cfg, positions, is_local,
                                       kv=(ck, cv), cache_pos=pos,
                                       block_table=tbl, write_mask=wmask)
            return x, new_kv
        x, (ck, cv) = taps.scan(
            body, x, (params["layers"], _is_local_arr(cfg), cache["k"], cache["v"]))
        cache["k"], cache["v"] = ck, cv
    adv = jnp.int32(1) if live is None else live.astype(jnp.int32)
    cache["pos"] = pos + adv
    return _logits(params, cfg, x), cache


@scoped("lm.verify")
def verify_step(params, cfg: ModelConfig, tokens: Array, cache: Dict,
                live: Optional[Array] = None) -> Tuple[Array, Dict]:
    """tokens (B, S) -> logits (B, S, V): the speculative-verify forward.

    All S = k+1 positions of a draft block go through the model in ONE
    call -- every projection sees an (B*S, K) GEMM, which at k+1 <= 32
    stays on the prepacked skinny-M kernel path -- writing KV rows at
    per-slot positions ``cache["pos"] + [0..S)``.  ``cache["pos"]`` is
    NOT advanced here: the caller commits the accepted prefix by setting
    pos itself, which is also the whole rollback story -- rows written
    beyond the committed pos are invisible (the attention validity
    horizon masks ``k_pos >= pos + S_query``) and are simply overwritten
    by the next round's writes.

    Position i's logits are bit-identical to what ``decode_step`` would
    produce after committing tokens[:, :i+1]: the attention route is
    pinned to the plain kernel (decode's own S==1 route; flash's online
    softmax has a different reduction order), and everything else is
    row-local float math.  ``live`` matters only for paged caches: it
    redirects non-live rows' draft-block writes to the trash block so a
    pooled verify cannot scribble into blocks a mid-prefill or harvested
    slot's table still aliases.  Restricted to positional-cache families:
    SSM/conv recurrent state advances destructively and cannot be rolled
    back by masking.
    """
    if cfg.family in ("ssm", "hybrid"):
        raise NotImplementedError(
            "speculative verify needs positional KV rollback; the "
            f"{cfg.family!r} family carries recurrent SSM/conv state that "
            "a draft block cannot roll back")
    if cfg.attn_impl != "plain":
        cfg = dataclasses.replace(cfg, attn_impl="plain")
    with jax.named_scope("lm.embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
    S = tokens.shape[1]
    pos = cache["pos"]
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    cache = dict(cache)
    tbl = cache.get("table")
    wmask = live if (live is not None and tbl is not None) else None

    def body(x, scanned):
        blk, is_local, ck, cv = scanned
        x, new_kv, _ = _attn_block(blk, x, cfg, positions, is_local,
                                   kv=(ck, cv), cache_pos=pos,
                                   block_table=tbl, write_mask=wmask)
        return x, new_kv
    x, (ck, cv) = taps.scan(
        body, x, (params["layers"], _is_local_arr(cfg), cache["k"],
                  cache["v"]))
    cache["k"], cache["v"] = ck, cv
    return _logits(params, cfg, x), cache


def _ssm_stack_cached(params, cfg: ModelConfig, x, positions, cache,
                      decode: bool, chunked: bool = False,
                      write_mask=None, live=None):
    """``chunked=True`` is the mid-prompt prefill mode: conv + SSM state
    carry across chunk calls (a fresh prefill passes zero conv state via
    None -- bit-identical to explicit zeros) and the hybrid shared-attn
    block writes at the slot's current ``pos`` instead of 0.
    ``write_mask`` guards the paged shared-attn KV write for non-live
    rows (decode_step holds their SSM/conv state itself); ``live``
    marks the decode rows whose expert choices count."""
    if cfg.layer_types:
        return _pattern_stack(params, cfg, x, positions, cache, decode,
                              chunked, write_mask, live)
    pos = cache["pos"]
    tbl = cache.get("table")

    def body(x, scanned):
        blk, ssm_st, cx, cbc = scanned
        h, (new_ssm, new_conv) = L.mamba2_apply(
            blk["mamba"], L.rms_norm(x, blk["ln1"], cfg.norm_eps), cfg,
            ssm_state=ssm_st,
            conv_state=(cx, cbc) if (decode or chunked) else None,
            decode=decode)
        return x + h, (new_ssm, new_conv[0], new_conv[1])

    if cfg.family == "ssm" or not cfg.shared_attn_period:
        x, (ssm, cx, cbc) = taps.scan(
            body, x, (params["layers"], cache["ssm"], cache["conv_x"],
                      cache["conv_bc"]))
        cache["ssm"], cache["conv_x"], cache["conv_bc"] = ssm, cx, cbc
        return x, cache

    period = cfg.shared_attn_period
    n_groups = cfg.n_layers // period
    new_ssm, new_cx, new_cbc, new_k, new_v = [], [], [], [], []
    done = 0

    def run_group(x, lo, hi):
        return taps.scan(
            body, x, (_slice_layers(params["layers"], lo, hi),
                      cache["ssm"][lo:hi], cache["conv_x"][lo:hi],
                      cache["conv_bc"][lo:hi]))

    for g in range(n_groups):
        x, (s_ssm, s_cx, s_cbc) = run_group(x, g * period, (g + 1) * period)
        new_ssm.append(s_ssm)
        new_cx.append(s_cx)
        new_cbc.append(s_cbc)
        x, kv, _ = _attn_block(
            params["shared"], x, cfg, positions, jnp.bool_(False),
            kv=(cache["shared_k"][g], cache["shared_v"][g]),
            cache_pos=pos if (decode or chunked) else jnp.zeros_like(pos),
            prefix="shared/", block_table=tbl, write_mask=write_mask)
        new_k.append(kv[0])
        new_v.append(kv[1])
        done = (g + 1) * period
    if done < cfg.n_layers:
        x, (s_ssm, s_cx, s_cbc) = run_group(x, done, cfg.n_layers)
        new_ssm.append(s_ssm)
        new_cx.append(s_cx)
        new_cbc.append(s_cbc)
    cache["ssm"] = jnp.concatenate(new_ssm, axis=0)
    cache["conv_x"] = jnp.concatenate(new_cx, axis=0)
    cache["conv_bc"] = jnp.concatenate(new_cbc, axis=0)
    cache["shared_k"] = jnp.stack(new_k, axis=0)
    cache["shared_v"] = jnp.stack(new_v, axis=0)
    return x, cache


def _pattern_stack(params, cfg: ModelConfig, x, positions, cache=None,
                   decode: bool = False, chunked: bool = False,
                   write_mask=None, live=None):
    """The ``layer_types`` stack: x + mixer(rms_norm(x)) per character,
    unrolled (no kind repeats back to back in nemotron_h's pattern, so
    no run is worth a scan).  A layer reads its params and cache entries
    at its rank among the layers of its kind.  The residual stream is
    kept in ``cfg.act_dtype`` (float32).  ``cache`` None is the training
    forward; otherwise modes, masks and the state returned are
    ``_ssm_stack_cached``'s.  Returns (x, cache)."""
    layers, rank = params["layers"], dict.fromkeys(LAYER_KINDS, 0)
    x = x.astype(cfg.act_dtype)
    if cache is not None:
        cache = dict(cache)
        pos, tbl = cache["pos"], cache.get("table")
        cache_pos = pos if (decode or chunked) else jnp.zeros_like(pos)
        new = {k: [] for k in ("ssm", "conv_x", "conv_bc", "k", "v")}
    for kind in cfg.layer_types:
        r = rank[kind]
        rank[kind] += 1
        blk = jax.tree.map(lambda v: v[r], layers[kind])
        h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
        if kind == "M":
            st = conv = None
            if cache is not None:
                st = cache["ssm"][r]
                if decode or chunked:
                    conv = (cache["conv_x"][r], cache["conv_bc"][r])
            h, (st, (cx, cbc)) = L.mamba2_apply(
                blk["mamba"], h, cfg, ssm_state=st, conv_state=conv,
                decode=decode)
            if cache is not None:
                new["ssm"].append(st)
                new["conv_x"].append(cx)
                new["conv_bc"].append(cbc)
        elif kind == "*":
            kv = None if cache is None else (cache["k"][r], cache["v"][r])
            h, kv = L.attention_apply(
                blk["attn"], h, cfg, positions, jnp.bool_(False), kv_cache=kv,
                cache_pos=None if cache is None else cache_pos,
                block_table=None if cache is None else tbl,
                write_mask=write_mask)
            if cache is not None:
                new["k"].append(kv[0])
                new["v"].append(kv[1])
        else:
            h = L.held_moe_apply(blk["moe"], h, cfg, live=live)
        x = x + h
    if cache is not None:
        cache.update({k: jnp.stack(v) for k, v in new.items() if v})
    return x, cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


CE_TARGET_ELEMS = 2e9  # global fp32 logits elements per CE chunk


def _ce_chunk(batch: int, seq: int, vocab: int) -> int:
    """Vocab/batch-adaptive CE chunk: bound the transient logits tensor to
    ~CE_TARGET_ELEMS global elements (8 GB fp32 -> ~32 MB/device on the
    production mesh)."""
    c = int(CE_TARGET_ELEMS / max(batch * vocab, 1))
    c = 1 << max(c.bit_length() - 1, 5)  # floor pow2, >= 32
    return max(32, min(1024, c, seq))


def lm_loss(params, cfg: ModelConfig, tokens: Array,
            frontend_embs: Optional[Array] = None,
            remat: bool = True) -> Array:
    """Next-token CE over the text positions (frontend prefix excluded).

    The (B, S, V) logits tensor is never materialised: CE is a remat'd
    scan over sequence chunks, so peak temp is (B, CE_CHUNK, V) -- at
    gemma2's 256k vocab this is the difference between 40 GB and 1.3 GB of
    per-device loss workspace.
    """
    x, aux = hidden_states(params, cfg, tokens, frontend_embs, remat)
    n_front = x.shape[1] - tokens.shape[1]
    x = x[:, n_front:, :]
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(x.dtype)

    xs, tgt = x[:, :-1, :], tokens[:, 1:]
    B, S1, D = xs.shape
    c = _ce_chunk(B, S1, cfg.vocab_size)
    nc = (S1 + c - 1) // c
    pad = nc * c - S1
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tgt = jnp.pad(tgt, ((0, 0), (0, pad)), constant_values=-1)
    xs = xs.reshape(B, nc, c, D).swapaxes(0, 1)       # (nc, B, c, D)
    tgt = tgt.reshape(B, nc, c).swapaxes(0, 1)

    def chunk(carry, inp):
        xc, tc = inp
        logits = L.softcap((xc @ head).astype(jnp.float32), cfg.logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        sel = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
        valid = (tc >= 0).astype(jnp.float32)
        return carry + jnp.sum((lse - sel) * valid), None

    total, _ = jax.lax.scan(jax.checkpoint(chunk), jnp.float32(0.0), (xs, tgt))
    return total / (B * S1) + aux
