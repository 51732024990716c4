"""Tiled MM2IM planning (paper Alg. 1) — the host-driver role.

Given a TCONV problem and a VMEM budget, produce the full tile plan the
Pallas kernel executes: output-row block (``block_oh = S*bi``), output
channel block (``block_oc`` — the ``filter_step`` / #PM analogue), the
input-row slab geometry (``i_end_row`` relation), grid order, and the
modeled VMEM footprint.  ``kernels/ops.py`` consumes this implicitly via
``plan_blocks``; benchmarks, tests and the autotuner
(``core/autotune.py``) consume the explicit plan: :func:`plan` accepts
explicit ``block_oh``/``block_oc``/``grid_order`` overrides and
:func:`candidate_plans` enumerates every legal tile geometry under the
budget for empirical tuning.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import List, Optional

import jax
from jax._src.pallas.mosaic.error_handling import MosaicError
from jax.experimental.pallas import tpu as pltpu

from repro.core.maps import TConvProblem, rows_slab
from repro.core.perf_model import HW, V5E, mm2im_estimate
from repro.kernels.mm2im_pallas import (LANE, SUBLANE, lane_block,
                                        mm2im_vmem_bytes, plan_blocks)
from repro.kernels.ref import crop_offsets

# What the chip's compiler raises when it refuses a kernel: Pallas' Mosaic
# lowering (unsupported primitive), Mosaic itself (layout inference, e.g.
# "unsupported shape cast"), and XLA's TPU compiler (scoped VMEM
# exhausted).  Anything else is a bug in the caller and propagates.
COMPILER_REFUSALS = (pltpu.LoweringException, MosaicError,
                     jax.errors.JaxRuntimeError)


@dataclasses.dataclass(frozen=True)
class TilePlan:
    problem: TConvProblem
    block_oh: int
    block_oc: int
    n_slab: int
    n_row_blocks: int
    n_oc_blocks: int
    grid_order: str
    vmem_bytes: int
    halo_overhead: float  # recomputed-slab fraction vs ideal (dense-MXU cost)
    method: str = "mm2im"  # 'mm2im' | 'mm2im_db' | 'mm2im_ks' | 'mm2im_og'
    fold_batch: bool = False  # plan v2: batch folded into the MatMul M-dim

    def describe(self) -> str:
        p = self.problem
        return (f"tconv({p.ih},{p.iw},{p.ic},{p.ks},{p.oc},{p.stride}) "
                f"[{self.method}{'+fold' if self.fold_batch else ''}] "
                f"block_oh={self.block_oh} block_oc={self.block_oc} "
                f"slab={self.n_slab} grid={self.grid_order} "
                f"vmem={self.vmem_bytes/2**20:.2f}MiB halo=+{self.halo_overhead:.0%}")


def _geometry(p: TConvProblem, block_oh: int):
    """Shared slab/grid geometry for a given output-row block."""
    s = p.stride
    bi = block_oh // s
    ct, _ = crop_offsets(p.ks, s, p.padding)
    delta = -(-max(p.ks - 1 - ct, 0) // s)
    eps = (ct - 1) // s
    n_slab = bi + delta + eps + 1
    n_j = -(-p.oh // block_oh)
    ihp = (n_j - 1) * bi + n_slab
    ow_p = -(-p.ow // s) * s
    return bi, n_slab, n_j, ihp, ow_p


def vmem_bytes(p: TConvProblem, block_oh: int, block_oc: int,
               *, bits: int = 8, method: str = "mm2im",
               batch: int = 1, fold_batch: bool = False) -> int:
    """Modeled VMEM footprint of one grid cell.

    ``'mm2im'`` keeps the whole padded input resident
    (``mm2im_pallas`` residency); ``'mm2im_db'`` holds only the two-slot
    slab + output scratch of the DMA pipeline (``mm2im_db_pallas``), which
    is what lets the double-buffered variant run blocks the single-buffered
    kernel cannot fit.  Both are ``mm2im_pallas.mm2im_vmem_bytes``, the
    model the kernel's own heuristic plans with.

    ``'mm2im_ks'`` and ``'mm2im_og'`` keep the whole input resident too,
    counted like ``mm2im_vmem_bytes``: Pallas double-buffers the input
    block (``Iw`` padded to the sublane tile, ``Ic`` to the 128-lane
    tile), the ``(Ks², Ic, boc)`` weight block and the output block, and
    ``block_oc`` runs rounded up to the lane tile.  Inside the cell KS
    holds its per-sub-kernel dense products (each over only the slab rows
    its taps touch) plus the residue planes; OG holds a *gathered*
    operand — ``(bi·Iw', Jh·Jw·Ic)`` for the widest sub-kernel, one class
    staged at a time — plus the S² residue planes it writes once.  Both
    models omit Mosaic's own scratch, so a geometry within budget may
    still be refused: :func:`plan_compiles` is the authority on the chip.

    ``fold_batch=True`` multiplies the batch-concatenated residencies by
    ``batch``: the folded single-buffered kernel holds the whole
    ``(B, Ihp, Iw, Ic)`` input block, the folded pipeline two
    ``(B, n_slab, Iw, Ic)`` slab slots, and both hold the ``B``-deep
    folded MatMul product and output block — this is the per-variant
    budget that gates ``fold_batch`` candidates in :func:`candidate_plans`.
    """
    ebytes = bits // 8
    if method not in ("mm2im_ks", "mm2im_og"):
        return mm2im_vmem_bytes(
            p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding,
            block_oh=block_oh, block_oc=block_oc, in_bytes=ebytes,
            batch=batch, fold_batch=fold_batch,
            whole_input=method != "mm2im_db")
    from repro.core.segregate import segregate  # local: avoid cycle

    bi, n_slab, _, ihp, ow_p = _geometry(p, block_oh)
    bmul = batch if fold_batch else 1
    boc = lane_block(block_oc, p.oc)
    ic_l = -(-p.ic // LANE) * LANE
    iw_s = -(-p.iw // SUBLANE) * SUBLANE
    planes = bmul * block_oh * ow_p * boc * 4
    blocks = 2 * (bmul * ihp * iw_s * ic_l * ebytes            # whole input
                  + p.ks**2 * ic_l * boc * ebytes              # weights
                  + planes)                                    # output
    seg = segregate(p.ks, p.stride, p.padding)
    if method == "mm2im_ks":
        scratch = sum(bmul * (bi + sk.jh - 1) * iw_s * sk.taps * boc * 4
                      for sk in seg.subkernels if sk.taps)
    else:  # "mm2im_og": the widest class's gathered operand
        gmax = max((sk.taps for sk in seg.subkernels), default=0)
        scratch = bmul * bi * (ow_p // p.stride) * gmax * ic_l * ebytes
    return blocks + scratch + planes


def plan(p: TConvProblem, *, batch: int = 1, bits: int = 8, hw: HW = V5E,
         block_oh: Optional[int] = None, block_oc: Optional[int] = None,
         grid_order: Optional[str] = None,
         method: str = "mm2im", fold_batch: bool = False) -> TilePlan:
    """Tile plan for ``p`` — heuristic by default, explicit when overridden.

    Passing ``block_oh``/``block_oc`` (and optionally ``grid_order`` /
    ``method``) bypasses the ``plan_blocks`` heuristic; this is how
    autotuned plans are rendered back into a full :class:`TilePlan` with
    their modeled VMEM footprint and halo overhead.
    """
    ebytes = bits // 8
    if block_oh is None or block_oc is None:
        # plan_blocks owns the folded-budget rule (B-deep residency =>
        # budget/B): heuristic folded blocks fit VMEM with the fold on.
        h_oh, h_oc = plan_blocks(
            p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding,
            vmem_budget=int(hw.vmem_bytes * 0.75), in_bytes=ebytes,
            batch=batch, fold_batch=fold_batch)
        block_oh = block_oh if block_oh is not None else h_oh
        block_oc = block_oc if block_oc is not None else h_oc
    s = p.stride
    if block_oh % s != 0 or block_oh < s:
        raise ValueError(f"block_oh={block_oh} must be a positive multiple "
                         f"of stride {s}")
    bi, n_slab, n_j, ihp, ow_p = _geometry(p, block_oh)
    n_c = -(-p.oc // lane_block(block_oc, p.oc))

    if grid_order is None or grid_order == "auto":
        w_bytes = p.ic * p.ks**2 * n_c * block_oc * ebytes
        x_bytes = batch * ihp * p.iw * p.ic * ebytes
        grid_order = "cbj" if w_bytes > x_bytes else "bcj"

    vmem = vmem_bytes(p, block_oh, block_oc, bits=bits, method=method,
                      batch=batch, fold_batch=fold_batch)
    halo = (n_j * n_slab) / max(p.ih, 1) - 1.0
    return TilePlan(p, block_oh, block_oc, n_slab, n_j, n_c, grid_order,
                    vmem, max(halo, 0.0), method, fold_batch)


# Candidate grids mirror plan_blocks' search space; the autotuner measures
# instead of guessing, so it also explores both explicit grid orders and
# every plan-capable kernel variant.
_CAND_BI = (1, 2, 4, 8, 16, 32, 64)
_CAND_BOC = (128, 256, 512)  # multiples of the 128-lane tile (lane_block)
# Fallback when the registry has not been populated yet (built-ins register
# on `kernels.ops` import).
_CAND_METHODS = ("mm2im", "mm2im_db")


def _registered_plan_methods() -> tuple:
    """Plan-capable methods currently in the kernel registry.

    This is what makes a third-party ``supports_plan=True`` variant
    autotunable with zero wiring: registering it is enough for the
    enumeration stage to produce candidates carrying its name.  Unknown
    variants are budget-modeled with the (conservative) whole-input
    residency of the single-buffered kernel.
    """
    from repro.kernels import ops  # noqa: F401  (registers the built-ins)
    from repro.kernels import registry as kernel_registry

    names = tuple(s.name for s in kernel_registry.specs() if s.supports_plan)
    return names or _CAND_METHODS


def plan_compiles(p: TConvProblem, plan, *, batch: int = 1,
                  bits: int = 8) -> bool:
    """Whether this backend's compiler accepts ``plan`` (a
    ``registry.Plan``, or a :class:`TilePlan`) for ``p``.

    On a TPU the plan's kernel is compiled by Mosaic through the
    dispatcher's execution path, once per (family, geometry, fold) and
    problem; the grid order only permutes the index maps and shares the
    answer.  A refusal (:data:`COMPILER_REFUSALS`) warns once, naming the
    family, the problem and the compiler's message; any other error
    propagates.  Off the chip the interpreter runs every plan.
    """
    if jax.default_backend() != "tpu":
        return True
    return _compiles_on_device(plan.method or "mm2im", p, batch, bits,
                               plan.block_oh, plan.block_oc,
                               bool(plan.fold_batch))


def family_compiles(method: str, p: TConvProblem, *, batch: int = 1,
                    bits: int = 8) -> bool:
    """Whether ``method`` compiles for ``p`` at the heuristic block
    geometry: the cheap per-family probe that keeps a family the chip's
    compiler refuses outright (e.g. an unsupported reshape) out of the
    candidates with one compile instead of one per geometry."""
    from repro.kernels.registry import Plan

    block_oh, block_oc = plan_blocks(p.ih, p.iw, p.ic, p.ks, p.oc, p.stride,
                                     p.padding, in_bytes=bits // 8,
                                     batch=batch)
    return plan_compiles(p, Plan(block_oh, block_oc, method=method),
                         batch=batch, bits=bits)


@functools.lru_cache(maxsize=None)
def _compiles_on_device(method: str, p: TConvProblem, batch: int, bits: int,
                        block_oh: int, block_oc: int,
                        fold_batch: bool) -> bool:
    import jax.numpy as jnp

    from repro.core.autotune import measure_epilogue
    from repro.core.epilogue import Epilogue
    from repro.kernels import ops
    from repro.kernels.registry import Plan

    dtype = jnp.int8 if bits == 8 else jnp.float32
    bias, out_scale = measure_epilogue(p, dtype)
    plan = Plan(block_oh, block_oc, fold_batch=fold_batch)
    fn = jax.jit(lambda x, w: ops.run_registered(
        method, x, w, stride=p.stride, padding=p.padding,
        epilogue=Epilogue(bias=bias, out_scale=out_scale), plan=plan))
    try:
        fn.lower(jax.ShapeDtypeStruct((batch, p.ih, p.iw, p.ic), dtype),
                 jax.ShapeDtypeStruct((p.ks, p.ks, p.oc, p.ic), dtype)
                 ).compile()
    except COMPILER_REFUSALS as e:
        msg = str(e).strip().splitlines()[0] if str(e).strip() else repr(e)
        warnings.warn(
            f"{method} does not compile for {p} (batch {batch}, {bits}-bit, "
            f"block_oh={block_oh} block_oc={block_oc} fold={fold_batch}): "
            f"{msg}; the autotuner skips it", RuntimeWarning, stacklevel=2)
        return False
    return True


def candidate_plans(
    p: TConvProblem, *, batch: int = 1, bits: int = 8, hw: HW = V5E,
    vmem_fraction: float = 0.75,
    methods: Optional[tuple] = None,
) -> List[TilePlan]:
    """Every legal (method, block_oh, block_oc, grid_order, fold) under
    the budget.

    This is the autotuner's enumeration stage (paper Alg. 1 evaluated
    per-problem instead of once): all stride-aligned output-row blocks that
    don't overrun the output, every lane-legal channel block, both explicit
    grid orders, and every plan-capable registered kernel variant
    (``methods=None`` queries the registry — see
    :func:`_registered_plan_methods`) that the backend's compiler accepts
    at its heuristic geometry (:func:`family_compiles`).  Other
    geometries of an accepted family can still be refused (VMEM); the
    autotuner checks each plan it times with :func:`plan_compiles`.
    Where the pipeline has fewer than
    two row blocks to overlap, the double-buffered variant is skipped.
    Each variant is budget-filtered under its *own* VMEM residency model,
    so 'mm2im_db' legally reaches block geometries 'mm2im' cannot hold.

    For ``batch > 1`` each geometry is additionally enumerated with
    ``fold_batch=True`` where the ``B``-deep folded residency still fits
    the budget (plan v2 — batch collapsed into the MatMul M-dimension).
    Folded plans carry a single canonical ``'bcj'`` grid order: the
    bcj/cbj distinction collapses with the batch grid axis, so enumerating
    both would measure the same program twice.
    Deduplicated; order is deterministic.
    """
    if methods is None:
        methods = _registered_plan_methods()
    methods = tuple(m for m in methods
                    if family_compiles(m, p, batch=batch, bits=bits))
    budget = int(hw.vmem_bytes * vmem_fraction)
    s = p.stride
    seen = set()
    out: List[TilePlan] = []
    bocs = sorted({lane_block(b, p.oc) for b in _CAND_BOC})
    folds = (False,) if batch <= 1 else (False, True)
    for bi in _CAND_BI:
        block_oh = s * bi
        if block_oh > max(p.oh, s):
            continue  # row block would exceed the whole output
        n_j = -(-p.oh // block_oh)
        for boc in bocs:
            for method in methods:
                if method == "mm2im_db" and n_j < 2:
                    continue  # nothing to pipeline against
                for fold in folds:
                    if vmem_bytes(p, block_oh, boc, bits=bits, method=method,
                                  batch=batch, fold_batch=fold) > budget:
                        continue
                    orders = ("bcj",) if fold else ("bcj", "cbj")
                    for order in orders:
                        key = (method, block_oh, boc, order, fold)
                        if key in seen:
                            continue
                        seen.add(key)
                        out.append(plan(p, batch=batch, bits=bits, hw=hw,
                                        block_oh=block_oh, block_oc=boc,
                                        grid_order=order, method=method,
                                        fold_batch=fold))
    return out


def rank_plans(p: TConvProblem, plans: Optional[List[TilePlan]] = None,
               *, batch: int = 1, bits: int = 8, hw: HW = V5E,
               fit=None) -> List[TilePlan]:
    """Candidates sorted best-first by modeled cost, calibrated when possible.

    ``fit`` is a :class:`~repro.core.model_fit.FittedHW` (measurement-
    calibrated coefficients), ``"auto"`` to use the shipped calibration
    for the current JAX backend, or None for the uncalibrated roofline.
    With a fit, every candidate — any method, folded or not — scores in
    the same fitted microsecond scale, which is what makes a small
    ``max_measure`` in the autotuner trustworthy; without one the
    datasheet roofline still orders geometries sanely but has the
    recorded sb/db and fold/grid misranks (see ``BENCH_mm2im.json`` and
    docs/AUTOTUNER.md §Calibration).
    """
    # Lazy import: model_fit imports this module for default-geometry
    # reconstruction, so the dependency must not be circular at import time.
    from repro.core import model_fit
    from repro.core.perf_model import estimate_for_plan
    from repro.kernels.registry import Plan

    if plans is None:
        plans = candidate_plans(p, batch=batch, bits=bits, hw=hw)
    if fit == "auto":
        fit = model_fit.shipped_fit()

    def score(tp: TilePlan) -> float:
        pl = Plan(tp.block_oh, tp.block_oc, tp.grid_order, tp.method,
                  tp.fold_batch)
        if fit is not None:
            return fit.predict_us(p, pl, batch=batch, bits=bits, hw=hw)
        return estimate_for_plan(p, batch, plan=pl, bits=bits,
                                 hw=hw).t_overlapped

    return sorted(plans, key=score)


def slab_table(p: TConvProblem, block_oh: int) -> list[tuple[int, int]]:
    """Per-row-block (start, end) input slab ranges — Alg. 1's i_end_row."""
    return [rows_slab(p, oh0, block_oh) for oh0 in range(0, p.oh, block_oh)]
