"""Static Pallas kernel checker: VMEM budgets, block divisibility and
grid-aliasing safety -- proved from the BlockSpecs, never by running.

Mechanism: ``pl.pallas_call`` is monkeypatched with a spy while the real
dispatch wrappers (ops.py entry points) run under ``jax.eval_shape``, so
every record holds the *actual* grid/BlockSpecs the serving path would
launch for that shape -- including autotuned block overrides -- at zero
execution cost.  The spy's fake kernel returns zeros of ``out_shape``,
which keeps the surrounding padding/slicing trace intact.

Checks per recorded dispatch:

- KERNEL-BLOCK  block shapes tile their operands exactly and respect the
  TPU layout floor (lane 128, int8 sublane 32) unless the block spans
  the whole axis (resident whole-axis blocks need no alignment).
- KERNEL-VMEM   per-grid-step footprint: 2x each revolving block (Pallas
  double-buffers any operand whose index map moves across the grid), 1x
  each grid-invariant resident block, plus scratch -- against the 16 MiB
  VMEM budget.
- KERNEL-RACE   every output tile's writer set must be a contiguous run
  of the linearized (row-major, last-axis-innermost) grid -- the only
  order in which revisiting a tile is accumulation-safe on the
  sequential TPU grid (init at first visit, flush at last).
- KERNEL-LAYOUT no reshape inside the kernel body changes the minor
  (lane) dimension.  Mosaic refuses such a shape cast ("unsupported
  shape cast", e.g. (32, 256) -> (32, 16, 16)), which interpret mode
  never notices; the macro kernels slice each ADC conversion out of the
  tile instead.

The sweep covers all five kernel families at every plan design point
(n_dcim 0-6 x adc 7-9b x L16/32) and every shape recorded in
TUNING_CACHE.json, so a geometry the DSE roadmap sweeps is verified the
moment it is expressible.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import json
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as real_pl

from ..core.ccim import CCIMConfig, _dcim_by_j
from ..kernels.ccim_matmul import autotune
from ..kernels.ccim_matmul import ops as cm_ops
from ..kernels.ccim_matmul.ops import pick_weight_blocks
from .report import AnalysisReport

VMEM_BUDGET = 16 * 1024 * 1024     # bytes per core
LANE = 128
INT8_SUBLANE = 32
_GRID_ENUM_CAP = 32768             # full-enumeration cap for the race check

DESIGN_N_DCIM = tuple(range(0, 7))
DESIGN_ADC_BITS = (7, 8, 9)
DESIGN_ACC_LEN = (16, 32)


# ---------------------------------------------------------------------------
# interception
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpecView:
    """One BlockSpec joined with the operand it blocks."""

    block_shape: Tuple[int, ...]
    index_map: Optional[Callable]
    array_shape: Tuple[int, ...]
    dtype: Any
    is_output: bool = False


@dataclasses.dataclass
class PallasCallRecord:
    """Everything the checker needs about one pallas_call dispatch."""

    name: str
    grid: Tuple[int, ...]
    specs: List[SpecView]
    scratch_bytes: int
    num_scalar_prefetch: int
    scalar_shapes: List[Tuple[Tuple[int, ...], Any]]
    kernel_jaxpr: Any = None          # the traced kernel body (KERNEL-LAYOUT)

    @property
    def where(self) -> str:
        shapes = "/".join(
            "x".join(map(str, s.array_shape))
            for s in self.specs if not s.is_output)
        return f"{self.name}@grid{self.grid}[{shapes}]"


def _kernel_name(kernel) -> str:
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return getattr(kernel, "__name__", repr(kernel))


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _scratch_bytes(scratch_shapes) -> int:
    total = 0
    for s in _as_tuple(scratch_shapes):
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is not None and dtype is not None:
            total += math.prod(shape) * jnp.dtype(dtype).itemsize
    return total


@contextlib.contextmanager
def record_pallas_calls(records: List[PallasCallRecord]):
    """Swap ``pl.pallas_call`` for a spy; run wrappers under eval_shape.

    Kernel modules all bind the *module* (``from jax.experimental import
    pallas as pl``), so patching the module attribute intercepts every
    dispatch without touching their code.
    """
    orig = real_pl.pallas_call

    def spy(kernel, *, out_shape, grid=None, in_specs=None, out_specs=None,
            grid_spec=None, scratch_shapes=(), **kw):
        given = dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                     grid_spec=grid_spec, scratch_shapes=scratch_shapes)
        real = orig(kernel, out_shape=out_shape, **kw,
                    **{k: v for k, v in given.items() if v is not None})
        if grid_spec is not None:
            g = _as_tuple(grid_spec.grid)
            ins, outs = grid_spec.in_specs, grid_spec.out_specs
            scratch = grid_spec.scratch_shapes
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        else:
            g = _as_tuple(grid)
            ins, outs, scratch, nsp = in_specs, out_specs, scratch_shapes, 0
        in_list, out_list = list(_as_tuple(ins)), list(_as_tuple(outs))
        out_shapes = _as_tuple(out_shape)

        def fake(*operands):
            scalars = operands[:nsp]
            arrays = operands[nsp:]
            specs: List[SpecView] = []
            for spec, op in zip(in_list, arrays):
                bs = tuple(op.shape[i] if b is None else int(b)
                           for i, b in enumerate(spec.block_shape))
                specs.append(SpecView(bs, spec.index_map, tuple(op.shape),
                                      op.dtype))
            for spec, osh in zip(out_list, out_shapes):
                bs = tuple(osh.shape[i] if b is None else int(b)
                           for i, b in enumerate(spec.block_shape))
                specs.append(SpecView(bs, spec.index_map, tuple(osh.shape),
                                      osh.dtype, is_output=True))
            records.append(PallasCallRecord(
                name=_kernel_name(kernel), grid=g, specs=specs,
                scratch_bytes=_scratch_bytes(scratch),
                num_scalar_prefetch=nsp,
                scalar_shapes=[(tuple(s.shape), s.dtype) for s in scalars],
                kernel_jaxpr=_kernel_jaxpr(real, operands)))
            return jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shape)

        return fake

    real_pl.pallas_call = spy
    try:
        yield records
    finally:
        real_pl.pallas_call = orig


def _kernel_jaxpr(call, operands):
    """The kernel body's jaxpr, traced (never lowered) from the real call."""
    avals = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in operands]
    outer = jax.make_jaxpr(call)(*avals)
    for eqn in outer.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
    return None


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def _scalar_args(rec: PallasCallRecord, fill: int) -> list:
    return [np.full(shape, fill, dtype=np.dtype(dt).name
                    if np.issubdtype(np.dtype(dt), np.integer) else dt)
            for shape, dt in rec.scalar_shapes]


def _eval_index_map(spec: SpecView, idx: Tuple[int, ...],
                    scalars: list) -> Optional[Tuple[int, ...]]:
    if spec.index_map is None:
        return tuple(0 for _ in spec.block_shape)
    try:
        out = spec.index_map(*idx, *scalars)
    except Exception:
        return None
    return tuple(int(v) for v in _as_tuple(out))


def _grid_points(grid: Tuple[int, ...]):
    return itertools.product(*(range(g) for g in grid))


def _grid_corners(grid: Tuple[int, ...]):
    """A small grid-point sample: all corners plus the origin-adjacent
    steps -- enough to observe whether an index map moves at all."""
    pts = set(itertools.product(*((0, g - 1) for g in grid)))
    for ax in range(len(grid)):
        if grid[ax] > 1:
            p = [0] * len(grid)
            p[ax] = 1
            pts.add(tuple(p))
    return sorted(pts)


def _is_grid_invariant(rec: PallasCallRecord, spec: SpecView) -> bool:
    """True when the block never revolves: same tile at every grid step
    and no dependence on scalar-prefetch contents (Pallas keeps it
    resident instead of double-buffering)."""
    seen = set()
    for fill in (0, 1):
        scalars = _scalar_args(rec, fill)
        for idx in _grid_corners(rec.grid):
            tile = _eval_index_map(spec, idx, scalars)
            if tile is None:
                return False
            seen.add(tile)
            if len(seen) > 1:
                return False
    return True


def check_blocking(rec: PallasCallRecord, report: AnalysisReport) -> None:
    for si, spec in enumerate(rec.specs):
        report.check("KERNEL-BLOCK")
        kind = "out" if spec.is_output else f"in{si}"
        if len(spec.block_shape) != len(spec.array_shape):
            report.add("KERNEL-BLOCK", f"{rec.where}:{kind}",
                       f"block rank {spec.block_shape} != operand rank "
                       f"{spec.array_shape}")
            continue
        for d, (b, a) in enumerate(zip(spec.block_shape, spec.array_shape)):
            if b <= 0 or a % b != 0:
                report.add(
                    "KERNEL-BLOCK", f"{rec.where}:{kind}",
                    f"dim {d}: block {b} does not tile operand dim {a} "
                    f"(callers pad to block multiples before dispatch)")
        if len(spec.block_shape) < 2:
            continue
        lane, sub = spec.block_shape[-1], spec.block_shape[-2]
        lane_full = lane == spec.array_shape[-1]
        sub_full = sub == spec.array_shape[-2]
        if lane % LANE != 0 and not lane_full:
            report.add("KERNEL-BLOCK", f"{rec.where}:{kind}",
                       f"lane dim {lane} not a multiple of {LANE} and not "
                       "the whole axis")
        if (jnp.dtype(spec.dtype) == jnp.int8
                and sub % INT8_SUBLANE != 0 and not sub_full):
            report.add("KERNEL-BLOCK", f"{rec.where}:{kind}",
                       f"int8 sublane dim {sub} not a multiple of "
                       f"{INT8_SUBLANE} and not the whole axis")


def check_vmem(rec: PallasCallRecord, report: AnalysisReport) -> None:
    report.check("KERNEL-VMEM")
    total = 0
    blocks = []
    for spec in rec.specs:
        nbytes = math.prod(spec.block_shape) * jnp.dtype(spec.dtype).itemsize
        mult = 1 if _is_grid_invariant(rec, spec) else 2
        total += nbytes * mult
        blocks.append({"block": list(spec.block_shape),
                       "dtype": str(jnp.dtype(spec.dtype)),
                       "buffers": mult,
                       "bytes": nbytes * mult,
                       "output": spec.is_output})
    total += rec.scratch_bytes
    report.vmem_table.append({
        "kernel": rec.name, "grid": list(rec.grid),
        "vmem_bytes": total, "budget_bytes": VMEM_BUDGET,
        "scratch_bytes": rec.scratch_bytes, "blocks": blocks,
        "ok": total <= VMEM_BUDGET,
    })
    if total > VMEM_BUDGET:
        report.add("KERNEL-VMEM", rec.where,
                   f"per-grid-step footprint {total} B exceeds the "
                   f"{VMEM_BUDGET} B VMEM budget")


def check_grid_aliasing(rec: PallasCallRecord,
                        report: AnalysisReport) -> None:
    n_steps = math.prod(rec.grid) if rec.grid else 1
    if n_steps > _GRID_ENUM_CAP:
        report.note(f"KERNEL-RACE: {rec.where} grid too large to "
                    f"enumerate ({n_steps} steps > {_GRID_ENUM_CAP}); "
                    "skipped")
        return
    scalars = _scalar_args(rec, 0)
    for spec in rec.specs:
        if not spec.is_output:
            continue
        report.check("KERNEL-RACE")
        writers: Dict[Tuple[int, ...], List[int]] = {}
        for step, idx in enumerate(_grid_points(rec.grid)):
            tile = _eval_index_map(spec, idx, scalars)
            if tile is None:
                report.add("KERNEL-RACE", rec.where,
                           "output index map not statically evaluable")
                return
            writers.setdefault(tile, []).append(step)
        for tile, steps in writers.items():
            if steps[-1] - steps[0] + 1 != len(steps):
                report.add(
                    "KERNEL-RACE", rec.where,
                    f"output tile {tile} written at non-contiguous grid "
                    f"steps {steps[:6]}{'...' if len(steps) > 6 else ''} -- "
                    "a revisit after the tile was flushed clobbers the "
                    "accumulated value")


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params
    (the branches of pl.when, loop bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def check_layout(rec: PallasCallRecord, report: AnalysisReport) -> None:
    if rec.kernel_jaxpr is None:
        return
    report.check("KERNEL-LAYOUT")
    for eqn in _eqns(rec.kernel_jaxpr):
        if eqn.primitive.name != "reshape":
            continue
        src = tuple(eqn.invars[0].aval.shape)
        dst = tuple(eqn.outvars[0].aval.shape)
        if max(len(src), len(dst)) >= 2 and src[-1:] != dst[-1:]:
            report.add("KERNEL-LAYOUT", rec.where,
                       f"in-kernel reshape {src} -> {dst} changes the lane "
                       "dim; Mosaic refuses this shape cast on the TPU")


def check_record(rec: PallasCallRecord, report: AnalysisReport) -> None:
    check_blocking(rec, report)
    check_vmem(rec, report)
    check_grid_aliasing(rec, report)
    check_layout(rec, report)


# ---------------------------------------------------------------------------
# sweep drivers
# ---------------------------------------------------------------------------


def design_points() -> List[CCIMConfig]:
    """Every plan design point the kernels claim to serve statically."""
    return [CCIMConfig(n_dcim_products=nd, adc_bits=adc, acc_len=acc)
            for nd in DESIGN_N_DCIM
            for adc in DESIGN_ADC_BITS
            for acc in DESIGN_ACC_LEN]


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def _capture(records: List[PallasCallRecord], fn, *args) -> bool:
    # the dispatch wrappers are jitted: a design point whose static
    # signature matches an earlier one would hit the trace cache and
    # never reach the spy, so every capture starts from a cold cache
    jax.clear_caches()
    with record_pallas_calls(records):
        jax.eval_shape(fn, *args)
    return True


def capture_ccim_matmul(records, *, M: int, K: int, N: int,
                        cfg: CCIMConfig) -> None:
    """One prepacked real-GEMM dispatch (skinny or general route, exactly
    as the engine would pick it for this M)."""
    x_bits = tuple(_dcim_by_j(cfg))
    _, _, Np, Kp = pick_weight_blocks(K, N, cfg.acc_len)
    fn = functools.partial(
        cm_ops.ccim_matmul_int_prepacked, k_dim=K, n_dim=N,
        acc_len=cfg.acc_len, x_bits=x_bits, adc_bits=cfg.adc_bits,
        use_pallas=True, interpret=True)
    _capture(records, fn,
             _sds((M, K), jnp.int32),
             _sds((Kp, Np), jnp.int8),
             _sds((len(x_bits), Kp, Np), jnp.int8))


def capture_ccim_complex(records, *, M: int, K: int, N: int) -> None:
    from ..kernels.ccim_complex import ops as cx_ops
    _, _, Np, Kp = pick_weight_blocks(K, N)
    fn = functools.partial(
        cx_ops.ccim_complex_matmul_int_prepacked, k_dim=K, n_dim=N,
        use_pallas=True, interpret=True)
    plane = _sds((Kp, Np), jnp.int8)
    _capture(records, fn,
             _sds((M, K), jnp.int32), _sds((M, K), jnp.int32),
             plane, plane, plane, plane, plane, plane)


def capture_paged_attn(records, *, B: int, Hq: int, Hkv: int, Dh: int,
                       bs: int, n_blocks: int, n_tbl: int) -> None:
    from ..kernels.paged_attn.kernel import paged_attention_pallas
    fn = functools.partial(paged_attention_pallas, window=8, interpret=True)
    _capture(records, fn,
             _sds((B, Hq, Dh), jnp.float32),
             _sds((n_blocks, bs, Hkv, Dh), jnp.bfloat16),
             _sds((n_blocks, bs, Hkv, Dh), jnp.bfloat16),
             _sds((B, n_tbl), jnp.int32),
             _sds((B,), jnp.int32),
             _sds((), jnp.bool_))


def capture_int8(records, *, M: int, K: int, N: int) -> None:
    from ..kernels.int8_matmul.ops import int8_matmul
    fn = functools.partial(int8_matmul, use_pallas=True, interpret=True)
    _capture(records, fn, _sds((M, K), jnp.float32),
             _sds((K, N), jnp.float32))


# shape classes: one M per TUNING_CACHE bucket (gemv/skinny/wide) -- the
# decode, verify and prefill/train regimes respectively
SHAPE_CLASS_MS = {"gemv": 4, "skinny": 32, "wide": 256}
_SWEEP_K, _SWEEP_N = 512, 512


def tuning_cache_shapes() -> List[Tuple[int, int, int]]:
    """(M, K, N) for every fast_gemm entry in the tuning cache -- real
    serving shapes this host tuned for, re-audited on the Pallas path."""
    shapes = []
    for key, e in sorted(autotune._entries().items()):
        if "|fast_gemm|" in key and all(k in e for k in ("M", "K", "N")):
            shapes.append((int(e["M"]), int(e["K"]), int(e["N"])))
    return sorted(set(shapes))


def validate_tuning_cache(report: AnalysisReport) -> None:
    """Run the autotune loader's legality screen over the RAW cache file.

    The loader itself (autotune._entries) silently drops illegal entries
    at load time -- correct for serving, but the committed artifact
    should not carry any: surfacing them here makes ``--strict`` force a
    cleanup instead of letting a stale entry ride along forever.
    """
    try:
        with open(autotune.cache_path()) as f:
            raw = json.load(f).get("entries", {})
    except (OSError, ValueError, AttributeError):
        report.note("KERNEL-TUNING: no readable tuning cache; skipped")
        return
    if not isinstance(raw, dict):
        raw = {}
    for key, entry in sorted(raw.items()):
        report.check("KERNEL-TUNING")
        why = autotune.entry_violation(key, entry)
        if why:
            report.add("KERNEL-TUNING", key, why)


def sweep_kernels(report: AnalysisReport) -> List[PallasCallRecord]:
    """All five kernel families x every design point x shape classes."""
    records: List[PallasCallRecord] = []

    # families 1+2: real prepacked GEMM, general + skinny routes, at
    # every macro geometry the planner can emit
    for cfg in design_points():
        for M in SHAPE_CLASS_MS.values():
            capture_ccim_matmul(records, M=M, K=_SWEEP_K, N=_SWEEP_N,
                                cfg=cfg)
    # tuned shapes from this host's cache, prototype geometry
    proto = CCIMConfig()
    for (M, K, N) in tuning_cache_shapes():
        capture_ccim_matmul(records, M=M, K=K, N=N, cfg=proto)

    # family 3: fused complex kernel (prototype geometry; Re+Im in one
    # conversion is fixed 2-plane-per-part)
    for M in SHAPE_CLASS_MS.values():
        capture_ccim_complex(records, M=M, K=_SWEEP_K, N=_SWEEP_N)

    # family 4: paged-attention decode read at serving shapes
    capture_paged_attn(records, B=4, Hq=8, Hkv=2, Dh=128, bs=16,
                       n_blocks=64, n_tbl=8)
    capture_paged_attn(records, B=2, Hq=4, Hkv=4, Dh=128, bs=32,
                       n_blocks=16, n_tbl=4)

    # family 5: W8A8 GEMM
    for M in SHAPE_CLASS_MS.values():
        capture_int8(records, M=M, K=_SWEEP_K, N=_SWEEP_N)

    for rec in records:
        check_record(rec, report)
    validate_tuning_cache(report)

    report.census["kernel_dispatches"] = len(records)
    report.census["kernel_names"] = sorted({r.name for r in records})
    report.census["design_points"] = len(design_points())
    return records
