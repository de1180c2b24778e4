"""Prepacked-weight CIM execution engine: quantize/decompose once, serve many.

The silicon macro is weight-stationary: quantized signed-magnitude weights
are written into the SRAM array once and every subsequent MAC only streams
activations.  The software stack mirrors that here -- ``pack_cim_weights``
runs the full weight conditioning pipeline (per-channel SMF scale ->
integer quantization -> folded MSB DCIM planes -> block-padded int8
tiles) ONE time, and ``packed_cim_matmul`` serves every
later call with activation-only work.  Outputs are bit-identical to the
unpacked path for every fidelity, including the noise draw: packing is a
caching transform, not an approximation.

The one stored layout of ``PackedCimWeights`` is the Pallas kernels' own:

  pallas_w        (Kp, Np) int8 integer weights, block-padded with zeros
                  (padding is M-independent by construction, see
                  kernels.ccim_matmul.ops.pick_weight_blocks);
  pallas_planes   (n_j, Kp, Np) int8 folded signed DCIM planes, one per
                  distinct activation bit-plane j.

At 1 + n_j bytes a weight (3 for the default top-3 split) it is what the
TPU route reads.  Every other route derives its operands from the same
tiles inside the graph, exactly (an int8 value converts exactly to
float32, and the padding is zeros): the XLA fast GEMM takes the chunked
float32 views (ccim.fast_gemm_weight_ops), and the cold fidelities and
fault injection take the raw integers through ``wq()``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .ccim import (
    CCIMConfig,
    DEFAULT_CONFIG,
    MacroInstance,
    _dcim_by_j,
    cim_matmul_int,
    fast_gemm_weight_ops,
    fold_dcim_planes,
    hybrid_mac_fast_gemm_prepacked,
    quantize_smf,
    smf_scale,
)
from ..resilience import faults as rfaults

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PackedCimWeights:
    """One weight matrix, conditioned once for every macro execution path.

    A registered pytree: jit/vmap/scan slice and trace through it, so a
    stack of packed projections (leading layer axis) drops straight into
    the model zoo's scanned layer stacks.  ``k_dim``/``n_dim`` ride along
    as static metadata (the padded buffers lose the logical shape).
    """

    scale: Array                      # smf_scale output: (1, N) or scalar
    pallas_w: Array                   # (Kp, Np) int8, block-padded
    pallas_planes: Array              # (n_j, Kp, Np) int8 folded planes
    k_dim: int                        # static: logical K
    n_dim: int                        # static: logical N
    cfg: CCIMConfig                   # static: the macro config packed FOR
                                      # (plane fold + chunking are cfg-
                                      # specific; use-time mismatch errors)

    def wq(self) -> Array:
        """The raw integer SMF weights (..., K, N) int32, cut from the
        tiles (leading scan/expert axes pass through)."""
        return self.pallas_w[..., :self.k_dim, :self.n_dim].astype(jnp.int32)

    def dequantized(self) -> Array:
        """float32 (K, N) dequantized weights (e.g. for the STE backward)."""
        return self.wq().astype(jnp.float32) * jnp.reshape(self.scale, (1, -1))


jax.tree_util.register_dataclass(
    PackedCimWeights,
    data_fields=["scale", "pallas_w", "pallas_planes"],
    meta_fields=["k_dim", "n_dim", "cfg"],
)


@dataclasses.dataclass(frozen=True)
class FusedPackedCimWeights:
    """A horizontally fused projection group packed as ONE wide array.

    Several projections that consume the SAME input activation and resolve
    to the SAME deployment-plan entry (QKV, gate/up, the mamba2 input
    projections -- see models.lm.pack_cim_params) concatenate along N and
    pack as a single ``PackedCimWeights``: one activation quantization,
    one macro GEMM and one dequant serve the whole group, which is the
    decode hot path's dominant win at skinny M (7 -> ~3 GEMMs per block).

    ``seg_names``/``seg_dims`` are STATIC metadata: the leaf self-
    describes its per-segment N-offsets, so consumers split the wide
    output back into per-projection results with static slices -- bit-
    identical to the unfused calls (per-channel scales, quantization and
    the fast path's per-column arithmetic are all column-local, and noisy
    serving draws per-segment noise streams, see ccim._fast_gemm_noise).
    """

    packed: PackedCimWeights
    seg_names: Tuple[str, ...]        # static: member projection names
    seg_dims: Tuple[int, ...]         # static: per-segment logical N sizes

    @property
    def n_dim(self) -> int:
        return self.packed.n_dim


jax.tree_util.register_dataclass(
    FusedPackedCimWeights, data_fields=["packed"],
    meta_fields=["seg_names", "seg_dims"])


@dataclasses.dataclass(frozen=True)
class PackedComplexCimWeights:
    """Co-located (Re, Im) weight pair packed once, one shared full-scale.

    Mirrors the complex bit-cell: both components live in the same array
    and share the bitline full-scale, so one pack serves all four real
    sub-MACs of (a+bi)(c+di)."""

    re: PackedCimWeights
    im: PackedCimWeights


jax.tree_util.register_dataclass(
    PackedComplexCimWeights, data_fields=["re", "im"], meta_fields=[])


# ---------------------------------------------------------------------------
# Packing (the write-the-array step; run once per weight matrix)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def pack_quantized_cim_weights(
    wq: Array,                        # (K, N) ints in [-127, 127]
    scale: Array,                     # the smf_scale the ints were made with
    cfg: CCIMConfig = DEFAULT_CONFIG,
) -> PackedCimWeights:
    """Pack already-quantized integer weights (the array-write step).

    jit-compiled with ``cfg`` static: eager and traced callers share one
    fused scale/decompose pipeline, so packs are bit-identical however
    packing is invoked (eager packing used to differ in the last ulp).
    """
    from ..kernels.ccim_matmul.ops import pick_weight_blocks

    K, N = wq.shape
    # block-padded once (M-independent by construction); the pad geometry
    # follows the config's accumulate length, and an all-analog split
    # (n_dcim_products=0) simply has zero folded planes
    _, _, Np, Kp = pick_weight_blocks(K, N, cfg.acc_len)
    pad = ((0, Kp - K), (0, Np - N))
    return PackedCimWeights(
        scale=scale,
        pallas_w=jnp.pad(wq, pad).astype(jnp.int8),
        pallas_planes=jnp.pad(fold_dcim_planes(wq, cfg),
                              ((0, 0),) + pad).astype(jnp.int8),
        k_dim=K,
        n_dim=N,
        cfg=cfg,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "per_channel"))
def pack_cim_weights(
    w: Array,                         # (K, N) float weights
    cfg: CCIMConfig = DEFAULT_CONFIG,
    per_channel: bool = True,
) -> PackedCimWeights:
    """Quantize + decompose a float weight matrix once (PTQ array write).

    Matches ``cim_matmul``'s weight conditioning exactly (same scale, same
    rounding), so packed and unpacked execution are bit-identical.
    jit-compiled by default (cfg static) -- see pack_quantized_cim_weights.
    """
    w = w.astype(jnp.float32)
    sw = (smf_scale(w, axis=0, keepdims=True, cfg=cfg) if per_channel
          else smf_scale(w, cfg=cfg))
    return pack_quantized_cim_weights(quantize_smf(w, sw, cfg), sw, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def pack_complex_cim_weights(
    w_re: Array, w_im: Array,         # (K, N) float weights
    cfg: CCIMConfig = DEFAULT_CONFIG,
) -> PackedComplexCimWeights:
    """Pack a co-located complex weight pair with one shared full-scale
    (Re and Im share the array's bitlines in silicon)."""
    w_re = w_re.astype(jnp.float32)
    w_im = w_im.astype(jnp.float32)
    sw = smf_scale(jnp.maximum(jnp.abs(w_re), jnp.abs(w_im)), axis=0,
                   keepdims=True, cfg=cfg)
    return PackedComplexCimWeights(
        re=pack_quantized_cim_weights(quantize_smf(w_re, sw, cfg), sw, cfg),
        im=pack_quantized_cim_weights(quantize_smf(w_im, sw, cfg), sw, cfg),
    )


# ---------------------------------------------------------------------------
# Packed execution (the serve-many step)
# ---------------------------------------------------------------------------


def _prepacked_kernel_supported(cfg: CCIMConfig) -> bool:
    """Configs the GENERALIZED prepacked Pallas kernel can serve: the D/A
    split, ADC width and accumulate length ride in as static meta, so any
    deployment-plan design point qualifies -- the remaining constraints
    are the int8 storage format (7 magnitude bits, folded plane values
    <= 7 for splits up to top-6) and a block-divisible accumulate length.
    """
    d = DEFAULT_CONFIG
    return (cfg.n_mag_bits == d.n_mag_bits
            and cfg.n_dcim_products <= 6
            and cfg.acc_len in (8, 16, 32, 64))


def pack_compatible(packed_cfg: CCIMConfig, cfg: CCIMConfig) -> bool:
    """True when weights packed under ``packed_cfg`` can be SERVED under
    ``cfg`` without repacking.

    Besides trivial equality, the one relaxation is an *analog subset*: a
    serving config with NO DCIM products whose quantization
    (``n_mag_bits``) and chunk geometry (``acc_len``) match the pack.
    Pack-time layout depends only on those two knobs plus the plane fold,
    and a zero-product serving config never reads the folded planes (the
    DCIM dot is skipped entirely) while ``adc_bits`` only enters the
    runtime conversion epilogue.  This is what lets a speculative DRAFT
    plan (all-analog, cheap conversions) serve the SAME packed arrays its
    hybrid VERIFY plan uses -- one pack, two speed/accuracy operating
    points, the software twin of both splits sharing every bit-cell of
    the 2D array in silicon.
    """
    if packed_cfg == cfg:
        return True
    return (cfg.n_dcim_products == 0
            and dataclasses.replace(
                cfg, n_dcim_products=packed_cfg.n_dcim_products,
                adc_bits=packed_cfg.adc_bits) == packed_cfg)


def packed_cim_matmul_int(
    x_q: Array,                       # (M, K) ints in [-127, 127]
    packed: PackedCimWeights,
    macro: Optional[MacroInstance] = None,
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_key: Optional[Array] = None,
    fidelity: str = "fast",
    *,
    use_pallas: Optional[bool] = None,
    noise_segments: Optional[Tuple[int, ...]] = None,
    chunk_block: Optional[int] = None,
) -> Array:
    """Integer GEMM against prepacked weights; bit-identical to
    ``cim_matmul_int(x_q, packed.wq(), ...)`` for every fidelity.

    ``noise_segments`` (with a matching tuple of keys as ``noise_key``)
    draws one analog-noise stream per fused projection segment, keeping
    fused execution bit-identical to the unfused per-projection calls.
    ``chunk_block`` overrides the fast path's tuned scan block (the
    autotuner forces candidates through it; results are invariant).
    """
    M, K = x_q.shape
    assert K == packed.k_dim, (K, packed.k_dim)
    if not pack_compatible(packed.cfg, cfg):
        raise ValueError(
            "PackedCimWeights were packed for a different CCIMConfig than "
            "they are being served with (plane fold and chunk layout are "
            f"config-specific): packed for {packed.cfg}, serving {cfg}. "
            "Re-pack the weights for the serving config, or serve an "
            "all-analog subset (n_dcim_products=0, same n_mag_bits and "
            "acc_len), which never touches the folded planes.")
    if fidelity != "fast":
        # cold-path fidelities cut the raw ints from the tiles (one O(K*N)
        # convert, dwarfed by their own per-bit-product work)
        return cim_matmul_int(x_q, packed.wq(), macro, cfg, noise_key,
                              fidelity, use_pallas=use_pallas,
                              noise_segments=noise_segments)
    # analog-subset serving of a hybrid pack: no activation bit planes,
    # so both fast routes get a zero-plane weight operand
    x_bits = tuple(_dcim_by_j(cfg))
    planes = packed.pallas_planes[:len(x_bits)]
    # the Pallas kernel implements the NOMINAL macro only: with a fault
    # model armed (resilience/faults), the drifted conversion epilogue
    # exists solely in the XLA fast path, so route there
    if (noise_key is None and _prepacked_kernel_supported(cfg)
            and not rfaults.active()):
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        if use_pallas:
            from ..kernels.ccim_matmul.ops import ccim_matmul_int_prepacked
            return ccim_matmul_int_prepacked(
                x_q, packed.pallas_w, planes,
                k_dim=packed.k_dim, n_dim=packed.n_dim,
                acc_len=cfg.acc_len, x_bits=x_bits,
                dcim_lsb=cfg.dcim_lsb, adc_bits=cfg.adc_bits,
                use_pallas=True)
    wf, w_pl = fast_gemm_weight_ops(packed.pallas_w, planes, K,
                                    packed.n_dim, cfg.acc_len)
    C = wf.shape[0]
    xq = jnp.pad(x_q, ((0, 0), (0, C * cfg.acc_len - K))).reshape(
        M, C, cfg.acc_len)
    return hybrid_mac_fast_gemm_prepacked(
        xq, wf, w_pl, noise_key, cfg,
        noise_segments=noise_segments, chunk_block=chunk_block,
    ) * cfg.dcim_lsb


def packed_cim_matmul(
    x: Array,                         # (M, K) float activations
    packed: PackedCimWeights,
    cfg: CCIMConfig = DEFAULT_CONFIG,
    noise_key: Optional[Array] = None,
    macro: Optional[MacroInstance] = None,
    fidelity: str = "fast",
    use_pallas: Optional[bool] = None,
    noise_segments: Optional[Tuple[int, ...]] = None,
    chunk_block: Optional[int] = None,
) -> Array:
    """float (M,K) @ packed -> (M,N): per-row activation quantization is
    the ONLY conditioning left on the hot path (weights sit in the array)."""
    sx = smf_scale(x, axis=-1, keepdims=True, cfg=cfg)
    xq = quantize_smf(x, sx, cfg)
    y_int = packed_cim_matmul_int(xq, packed, macro, cfg, noise_key, fidelity,
                                  use_pallas=use_pallas,
                                  noise_segments=noise_segments,
                                  chunk_block=chunk_block)
    return y_int.astype(jnp.float32) * sx * jnp.reshape(packed.scale, (1, -1))


# ---------------------------------------------------------------------------
# The engine handle (what model configs carry instead of a bare CCIMConfig)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CimEngine:
    """Execution-policy handle: macro config + fidelity + kernel routing.

    One engine serves both operand kinds -- ``matmul`` dispatches on
    whether the weight is raw floats or a ``PackedCimWeights`` -- so model
    code (``models.layers._dense``) stays a one-liner and serving stacks
    can swap packed weights in without touching the layers.
    """

    cfg: CCIMConfig = DEFAULT_CONFIG
    fidelity: str = "fast"
    use_pallas: Optional[bool] = None
    macro: Optional[MacroInstance] = None

    def pack(self, w: Array, per_channel: bool = True) -> PackedCimWeights:
        return pack_cim_weights(w, self.cfg, per_channel)

    def pack_complex(self, w_re: Array, w_im: Array) -> PackedComplexCimWeights:
        return pack_complex_cim_weights(w_re, w_im, self.cfg)

    def matmul(self, x: Array, w, noise_key: Optional[Array] = None,
               noise_segments: Optional[Tuple[int, ...]] = None) -> Array:
        """(..., K) @ w -> (..., N) with STE gradients; w raw, packed or a
        fused projection group (``noise_segments`` then carries the static
        per-segment N sizes matching a tuple of per-segment noise keys)."""
        from .qat import cim_linear, cim_linear_packed
        if isinstance(w, FusedPackedCimWeights):
            segs = w.seg_dims if noise_key is not None else None
            return cim_linear_packed(x, w.packed, noise_key, self.cfg,
                                     self.fidelity, self.use_pallas, segs)
        if isinstance(w, PackedCimWeights):
            return cim_linear_packed(x, w, noise_key, self.cfg, self.fidelity,
                                     self.use_pallas, noise_segments)
        return cim_linear(x, w, noise_key, self.cfg, self.fidelity,
                          self.use_pallas, noise_segments)

    def matmul_int(self, x_q: Array, w,
                   noise_key: Optional[Array] = None) -> Array:
        if isinstance(w, PackedCimWeights):
            return packed_cim_matmul_int(
                x_q, w, self.macro, self.cfg, noise_key, self.fidelity,
                use_pallas=self.use_pallas)
        return cim_matmul_int(x_q, w, self.macro, self.cfg, noise_key,
                              self.fidelity, use_pallas=self.use_pallas)
