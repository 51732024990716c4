"""Analytical performance model for MM2IM on TPU (paper §III-C, adapted).

The paper models ``T_total = T_PM + T_Data`` for its FPGA (Eq. 3/4) and uses
the model to guide design (validated within 10%, §V-F).  On TPU the same
three-term structure becomes a roofline:

    T_compute    = issued_FLOPs            / peak_FLOPs
    T_memory     = HBM bytes moved         / HBM bandwidth
    T_collective = collective bytes        / ICI link bandwidth  (0 on-chip)

    T_total      = max(...)  (overlapped)   /   sum(...) (unoverlapped bound)

The model knows the *exact* dataflow of every implementation method, so it
can predict method-vs-method speedups (the role Fig. 6 / Table II play in
the paper) without hardware.  §V-F's validation becomes: model FLOPs/bytes
vs the XLA-compiled ``cost_analysis()`` (tests assert agreement), and the
hillclimbing loop in docs/EXPERIMENTS.md §Perf iterates on whichever term
this model says dominates.

The MM2IM family additionally models the **overlapped-copy term**
(``Estimate.t_fill``): the part of data movement that compute cannot hide.
For the single-buffered kernel that is the serial whole-input VMEM landing
(the SECDA data-in stall the paper pipelines away); for the double-buffered
variant (``'mm2im_db'``) it shrinks to one slab copy — the pipeline fill —
at the price of halo re-reads in ``t_memory``.  This is what lets
``core/autotune.py`` rank single- vs double-buffered candidates before
measuring, and ``benchmarks/bench_autotune.py`` compare predicted vs
measured rankings.

Hardware constants are TPU v5e per the assignment: 197 TFLOP/s bf16
(we model int8 at 2x), 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.maps import TConvProblem, drop_stats, max_slab_rows
from repro.kernels.baselines import tdc_macs, zero_insertion_macs


@dataclasses.dataclass(frozen=True)
class HW:
    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12
    peak_flops_int8: float = 394e12
    hbm_bw: float = 819e9
    ici_bw_per_link: float = 50e9
    # Scoped VMEM the kernels ask Mosaic for (kernels/mm2im_pallas.py
    # VMEM_LIMIT); a v5e core has 128 MiB.  Plans budget 3/4 of it.
    vmem_bytes: int = 64 * 2**20
    mxu_dim: int = 128


V5E = HW()


@dataclasses.dataclass
class Estimate:
    """Roofline terms (seconds) + bookkeeping for one op/method.

    ``t_fill`` is the non-overlappable slice OF ``t_memory`` (pipeline
    fill): bytes that must land before the first MAC can issue.  It is not
    extra traffic — ``t_memory`` already contains it — so the overlapped
    bound overlaps compute only with the *rest* of the memory time:
    ``max(compute, memory - fill, collective) + fill``.
    """

    method: str
    t_compute: float
    t_memory: float
    t_collective: float = 0.0
    t_fill: float = 0.0
    issued_macs: int = 0
    effectual_macs: int = 0
    hbm_bytes: int = 0
    # Raw cost-model terms, exposed for coefficient fitting
    # (core/model_fit.py): the calibration layer regresses measured wall
    # time against (issued_tiles, hbm_bytes, fill_bytes, n_launches)
    # instead of the hardware-datasheet-derived t_* seconds above.
    n_launches: int = 0
    fill_bytes: int = 0
    issued_tiles: int = 0

    @property
    def t_overlapped(self) -> float:
        return (max(self.t_compute, self.t_memory - self.t_fill,
                    self.t_collective) + self.t_fill)

    @property
    def t_serial(self) -> float:
        return self.t_compute + self.t_memory + self.t_collective

    @property
    def bottleneck(self) -> str:
        """Dominant term of the overlapped bound.

        ``t_fill`` competes as its own term: it is the non-overlappable
        slice of ``t_memory``, so the memory term here is the *remainder*
        (what compute can hide).  A fill-dominated problem reports
        ``'fill'`` — previously it was misattributed to plain
        ``'memory'``, hiding that the cure is pipelining (the db variant),
        not less traffic.
        """
        terms = {"compute": self.t_compute,
                 "memory": self.t_memory - self.t_fill,
                 "fill": self.t_fill,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def mxu_utilization(self) -> float:
        """Effectual fraction of issued MXU work (the GOPs/DSP analogue)."""
        return self.effectual_macs / max(self.issued_macs, 1)


def _dtype_peak(hw: HW, bits: int) -> float:
    return hw.peak_flops_int8 if bits == 8 else hw.peak_flops_bf16


def mxu_tiles(m: int, n: int, k: int, mxu: int) -> int:
    """Issued MXU tiles for an (m, k) @ (k, n) product.

    The systolic array computes whole ``mxu x mxu`` tiles: a matmul with
    ``m < mxu`` rows issues the same tile row as one with ``m == mxu``
    rows.  This quantization is exactly what batch folding exploits — the
    folded M-dimension packs ``B`` starved row slabs into the tiles the
    grid-batch dataflow would issue ``B`` times over.
    """
    return -(-m // mxu) * (-(-n // mxu)) * (-(-k // mxu))


def mm2im_estimate(
    p: TConvProblem,
    batch: int = 1,
    *,
    block_oh: Optional[int] = None,
    block_oc: Optional[int] = None,
    bits: int = 8,
    grid_order: str = "auto",
    hw: HW = V5E,
    double_buffered: bool = False,
    fold_batch: bool = False,
    requant: Optional[bool] = None,
) -> Estimate:
    """Model the fused Pallas MM2IM kernel's dataflow exactly.

    ``double_buffered=False`` models ``kernels/mm2im_pallas`` (whole input
    resident in VMEM; fill = the serial whole-input landing).
    ``double_buffered=True`` models ``kernels/mm2im_db_pallas`` (two-slot
    slab pipeline: fill shrinks to one slab copy, but every row block
    re-reads its halo rows from HBM).

    ``t_compute`` counts **issued MXU tiles** (:func:`mxu_tiles` — the
    ``ceil(M/128)·ceil(N/128)·ceil(K/128)`` quantization of the systolic
    array), not raw MACs, so a starved M-dimension costs what it costs on
    the hardware.  ``fold_batch=True`` models the plan-v2 folded dataflow:
    M grows to ``B·n_slab·Iw`` and the per-batch grid multiplicity
    disappears — this is what lets the autotuner rank folded vs grid-batch
    candidates a priori.

    ``requant`` selects the output store width for int8 problems: the
    paper's requantizing mode stores int8 (1 byte), int8 *without* a
    requant epilogue stores the int32 accumulator (4 bytes).  ``None``
    defaults to requantizing when ``bits == 8`` (the paper's precision).
    """
    from repro.kernels.mm2im_pallas import plan_blocks  # avoid cycle

    if block_oh is None or block_oc is None:
        block_oh, block_oc = plan_blocks(
            p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding,
            in_bytes=bits // 8, vmem_budget=int(hw.vmem_bytes * 0.75))
    s = p.stride
    bi = block_oh // s
    n_j = -(-p.oh // block_oh)
    n_c = -(-p.oc // block_oc)
    # Static slab height (mm2im_pallas geometry).
    from repro.kernels.ref import crop_offsets

    ct, _ = crop_offsets(p.ks, s, p.padding)
    delta = -(-max(p.ks - 1 - ct, 0) // s)
    eps = (ct - 1) // s
    n_slab = bi + delta + eps + 1

    ebytes = bits // 8
    oc_p = n_c * block_oc
    ihp = (n_j - 1) * bi + n_slab

    # MXU work actually issued, tile-quantized (halo overlap + Oc padding
    # + M/N/K tile padding included).  Folded: one (B*n_slab*Iw, Ic)
    # product per (j, c) cell; grid-batch: B starved (n_slab*Iw, Ic)
    # products.  mxu_utilization is the GOPs/DSP analogue: the effectual
    # fraction of the dense tile work the systolic array actually clocks.
    m_rows = (batch if fold_batch else 1) * n_slab * p.iw
    tiles = mxu_tiles(m_rows, p.ks**2 * block_oc, p.ic, hw.mxu_dim)
    n_launches = n_c * n_j * (1 if fold_batch else batch)
    issued = n_launches * tiles * hw.mxu_dim**3
    eff = drop_stats(p)["effectual_macs"] * batch

    # HBM traffic under the chosen grid order (resident-block model).
    w_bytes = p.ic * p.ks**2 * oc_p * ebytes
    slab_bytes = n_slab * p.iw * p.ic * ebytes
    if double_buffered:
        # Slab-granular reads: each row block re-fetches its halo rows.
        x_bytes_once = n_j * slab_bytes
    else:
        x_bytes_once = ihp * p.iw * p.ic * ebytes
    # Output store width follows the epilogue: the paper's int8 mode
    # requantizes to int8 (1 byte); int8 WITHOUT requant stores the int32
    # accumulator (4 bytes) — previously mis-modeled as 1 byte.
    if requant is None:
        requant = bits == 8
    out_store = 1 if (bits == 8 and requant) else 4
    out_bytes = batch * n_j * block_oh * (-(-p.ow // s) * s) * oc_p * out_store
    if grid_order == "auto":
        grid_order = "cbj" if w_bytes > batch * x_bytes_once else "bcj"
    if fold_batch:
        # Folding removes the per-batch grid multiplicity: weights are
        # fetched once, and the batch-concatenated input lands once for
        # the single-buffered kernel (resident across the (c, j) sweep) or
        # once per oc-block for the pipeline (slabs re-DMA'd per cell).
        hbm = (w_bytes + (n_c if double_buffered else 1) * batch
               * x_bytes_once + out_bytes)
    elif double_buffered:
        # The pipeline never keeps x resident: every (batch, oc-block) grid
        # cell re-DMAs all its slabs from HBM under BOTH grid orders, so
        # the x term always carries the n_c multiplicity; grid order only
        # decides whether the weight blocks are re-fetched per batch.
        hbm = ((w_bytes if grid_order == "cbj" else batch * w_bytes)
               + n_c * batch * x_bytes_once + out_bytes)
    elif grid_order == "cbj":
        hbm = w_bytes + n_c * batch * x_bytes_once + out_bytes
    else:
        hbm = batch * (x_bytes_once + w_bytes) + out_bytes

    # Overlapped-copy term: what the compute pipeline cannot hide.  The
    # single-buffered kernel stalls until the whole padded input landed in
    # VMEM; the double-buffered pipeline stalls only for its first slab.
    # Folded variants move batch-concatenated blocks, so the fill scales
    # with B either way.
    fill_once = slab_bytes if double_buffered else ihp * p.iw * p.ic * ebytes
    fill_bytes = (batch if fold_batch else 1) * fill_once

    return Estimate(
        method="mm2im_db" if double_buffered else "mm2im",
        t_compute=2 * issued / _dtype_peak(hw, bits),
        t_memory=hbm / hw.hbm_bw,
        t_fill=fill_bytes / hw.hbm_bw,
        issued_macs=issued,
        effectual_macs=eff,
        hbm_bytes=hbm,
        n_launches=n_launches,
        fill_bytes=fill_bytes,
        issued_tiles=n_launches * tiles,
    )


def mm2im_db_estimate(p: TConvProblem, batch: int = 1, **kw) -> Estimate:
    """Double-buffered MM2IM pipeline (``kernels/mm2im_db_pallas``)."""
    return mm2im_estimate(p, batch, double_buffered=True, **kw)


def mm2im_ks_estimate(
    p: TConvProblem,
    batch: int = 1,
    *,
    block_oh: Optional[int] = None,
    block_oc: Optional[int] = None,
    bits: int = 8,
    grid_order: str = "auto",
    hw: HW = V5E,
    fold_batch: bool = False,
    requant: Optional[bool] = None,
) -> Estimate:
    """Kernel-segregated MM2IM (``kernels/mm2im_ks_pallas``).

    Host staging, grid structure and HBM traffic are identical to the
    single-buffered MM2IM (the weight relayout is a permutation — same
    bytes), so every memory-side term is inherited.  Only the compute
    term differs: instead of one ``(n_slab·Iw, Ks²·boc)`` MatMul per grid
    cell, each non-empty sub-kernel issues a dense
    ``((bi + Jh - 1)·Iw, Jh·Jw·boc)`` product over exactly the slab rows
    its taps touch — the tile count sums only **effectual** MXU work
    (empty residue classes of a gapped stride > kernel TCONV issue
    nothing).  At stride 1 the sum degenerates to MM2IM's single-MatMul
    tile count, and ``fold_batch`` scales each sub-MatMul's M by B just
    like the plan-v2 folded family.
    """
    from repro.core.segregate import segregate  # avoid cycle
    from repro.kernels.mm2im_pallas import plan_blocks

    base = mm2im_estimate(
        p, batch, block_oh=block_oh, block_oc=block_oc, bits=bits,
        grid_order=grid_order, hw=hw, fold_batch=fold_batch, requant=requant)
    if block_oh is None or block_oc is None:
        block_oh, block_oc = plan_blocks(
            p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding,
            in_bytes=bits // 8, vmem_budget=int(hw.vmem_bytes * 0.75))
    bi = block_oh // p.stride
    seg = segregate(p.ks, p.stride, p.padding)
    m_unit = batch if fold_batch else 1
    tiles = sum(
        mxu_tiles(m_unit * (bi + sk.jh - 1) * p.iw, sk.taps * block_oc,
                  p.ic, hw.mxu_dim)
        for sk in seg.subkernels if sk.taps)
    issued = base.n_launches * tiles * hw.mxu_dim**3
    return dataclasses.replace(
        base,
        method="mm2im_ks",
        t_compute=2 * issued / _dtype_peak(hw, bits),
        issued_macs=issued,
        issued_tiles=base.n_launches * tiles,
    )


def mm2im_og_estimate(
    p: TConvProblem,
    batch: int = 1,
    *,
    block_oh: Optional[int] = None,
    block_oc: Optional[int] = None,
    bits: int = 8,
    grid_order: str = "auto",
    hw: HW = V5E,
    fold_batch: bool = False,
    requant: Optional[bool] = None,
) -> Estimate:
    """Output-gathered implicit GEMM (``kernels/mm2im_og_pallas``).

    Host staging and HBM-resident traffic match the single-buffered MM2IM
    (whole input lands once, weights are a permuted relayout — same
    bytes), but both roofline terms change shape:

    * **compute** — per residue class one ``(bi·Iw', Jh·Jw·Ic) @
      (Jh·Jw·Ic, boc)`` product: M covers exactly the output pixels
      (no ``n_slab`` halo rows, no ``Ks²``-wide N), and the tap reduction
      rides the K-dimension.  Tile count sums only effectual work, like
      the KS family, but with output-exact M and tap-deep K.
    * **memory** — the differentiating term is **gather-read bytes vs
      scatter-write bytes**: staging the gathered operand re-reads each
      input element once per tap that uses it (``Σ_sk Jh·Jw·bi·Iw'·Ic``
      bytes per grid cell), where the scatter-style families instead pay
      accumulator/plane read-modify-write traffic.  The gather bytes are
      added to ``hbm_bytes`` so the calibration layer
      (``core/model_fit.py``) can fit the trade as a regime-distinct
      coefficient; in exchange every output element is written exactly
      once and no partial sum is ever re-read.

    At stride 1 the single residue class gathers all ``Ks²`` taps — the
    amplification is maximal and MM2IM should win; at large stride and
    large image the per-class tap count collapses toward 1 while MM2IM's
    slab residency (and KS's halo-extended M) keep growing — the regime
    this family exists for.
    """
    from repro.core.segregate import segregate  # avoid cycle
    from repro.kernels.mm2im_pallas import plan_blocks

    base = mm2im_estimate(
        p, batch, block_oh=block_oh, block_oc=block_oc, bits=bits,
        grid_order=grid_order, hw=hw, fold_batch=fold_batch, requant=requant)
    if block_oh is None or block_oc is None:
        block_oh, block_oc = plan_blocks(
            p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding,
            in_bytes=bits // 8, vmem_budget=int(hw.vmem_bytes * 0.75))
    bi = block_oh // p.stride
    iw_p = -(-p.ow // p.stride)  # padded residue-plane width (ow_p / S)
    seg = segregate(p.ks, p.stride, p.padding)
    m_unit = batch if fold_batch else 1
    tiles = sum(
        mxu_tiles(m_unit * bi * iw_p, block_oc, sk.taps * p.ic, hw.mxu_dim)
        for sk in seg.subkernels if sk.taps)
    issued = base.n_launches * tiles * hw.mxu_dim**3
    gather_bytes = (base.n_launches * m_unit
                    * sum(sk.taps * bi * iw_p * p.ic
                          for sk in seg.subkernels if sk.taps)
                    * (bits // 8))
    hbm = base.hbm_bytes + gather_bytes
    return dataclasses.replace(
        base,
        method="mm2im_og",
        t_compute=2 * issued / _dtype_peak(hw, bits),
        t_memory=hbm / hw.hbm_bw,
        issued_macs=issued,
        hbm_bytes=hbm,
        issued_tiles=base.n_launches * tiles,
    )


def iom_unfused_estimate(p: TConvProblem, batch: int = 1, *, bits: int = 8,
                         hw: HW = V5E) -> Estimate:
    """Unfused IOM: dense MatMul -> HBM intermediate -> col2im scatter pass.

    The MatMul is tile-quantized like the MM2IM family's (same MXU, same
    starved-M penalty for small images) so cross-method modeled speedups
    compare equal model fidelities — one ``(Ih·Iw, Ic) @ (Ic, Ks²·Oc)``
    launch per batch element.
    """
    ebytes = bits // 8
    macs = (batch * mxu_tiles(p.m, p.n, p.k, hw.mxu_dim) * hw.mxu_dim**3)
    inter = batch * p.m * p.n * 4  # f32/i32 partial-product matrix
    hbm = (batch * p.m * p.k * ebytes + p.k * p.n * ebytes  # mm reads
           + inter                                            # mm write
           + inter                                            # col2im read
           + batch * p.oh * p.ow * p.oc * 4)                  # scatter out
    return Estimate(
        method="iom_unfused",
        t_compute=2 * macs / _dtype_peak(hw, bits),
        t_memory=hbm / hw.hbm_bw,
        issued_macs=macs,
        effectual_macs=drop_stats(p)["effectual_macs"] * batch,
        hbm_bytes=hbm,
        n_launches=2 * batch,  # one MatMul + one col2im pass per element
        issued_tiles=macs // hw.mxu_dim**3,
    )


def zero_insertion_estimate(p: TConvProblem, batch: int = 1, *, bits: int = 8,
                            hw: HW = V5E) -> Estimate:
    """§II-A method (i).  Direct-conv dataflow: raw dense MAC count (the
    paper's convention) — XLA's implicit-im2col conv tiling differs from a
    plain matmul's, so no MXU tile quantization is applied here (a modeled
    lower bound on compute time, same for :func:`tdc_estimate`)."""
    macs = batch * zero_insertion_macs(p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding)
    ebytes = bits // 8
    sd = p.stride * (p.ih - 1) + 1
    hbm = (batch * sd * sd * p.ic * ebytes + p.ks**2 * p.oc * p.ic * ebytes
           + batch * p.oh * p.ow * p.oc * 4)
    return Estimate(
        method="zero_insertion",
        t_compute=2 * macs / _dtype_peak(hw, bits),
        t_memory=hbm / hw.hbm_bw,
        issued_macs=macs,
        effectual_macs=drop_stats(p)["effectual_macs"] * batch,
        hbm_bytes=hbm,
        n_launches=batch,
    )


def tdc_estimate(p: TConvProblem, batch: int = 1, *, bits: int = 8,
                 hw: HW = V5E) -> Estimate:
    macs = batch * tdc_macs(p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding)
    ebytes = bits // 8
    # S^2 conv passes re-read the input once each; sub-filters read once;
    # interleave pass rewrites the output once.
    hbm = (batch * min(p.stride, p.oh) * min(p.stride, p.ow) * p.ih * p.iw * p.ic * ebytes
           + p.ks**2 * p.oc * p.ic * ebytes
           + 2 * batch * p.oh * p.ow * p.oc * 4)
    return Estimate(
        method="tdc",
        t_compute=2 * macs / _dtype_peak(hw, bits),
        t_memory=hbm / hw.hbm_bw,
        issued_macs=macs,
        effectual_macs=drop_stats(p)["effectual_macs"] * batch,
        hbm_bytes=hbm,
        n_launches=p.stride**2 * batch,  # one conv pass per sub-kernel
    )


ESTIMATORS = {
    "mm2im": mm2im_estimate,
    "mm2im_db": mm2im_db_estimate,
    "mm2im_ks": mm2im_ks_estimate,
    "mm2im_og": mm2im_og_estimate,
    "iom_unfused": iom_unfused_estimate,
    "zero_insertion": zero_insertion_estimate,
    "tdc": tdc_estimate,
}


#: Methods whose estimators accept the full plan-geometry kwargs
#: (``block_oh``/``block_oc``/``grid_order``/``fold_batch``).
PLAN_AWARE_METHODS = frozenset({"mm2im", "mm2im_db", "mm2im_ks", "mm2im_og"})


def estimate_for_plan(p: TConvProblem, batch: int = 1, *, plan=None,
                      method: Optional[str] = None, bits: int = 8,
                      hw: HW = V5E) -> Estimate:
    """Estimate for the exact dataflow a concrete ``Plan`` selects.

    ``plan`` is a :class:`repro.kernels.registry.Plan` (or None for the
    heuristic default).  ``plan.method`` picks the estimator
    (``method=`` overrides it — e.g. to model a non-MM2IM baseline);
    the block geometry, grid order and ``fold_batch`` knob are threaded
    through for the plan-aware MM2IM family, so the modeled time is the
    time of the plan that actually runs, not the heuristic
    single-buffered default.  Unknown registered methods fall back to the
    single-buffered estimate (same convention as the autotuner's
    :data:`repro.core.autotune.METHOD_ESTIMATORS`).
    """
    m = method or (plan.method if plan is not None and plan.method
                   else "mm2im")
    est = ESTIMATORS.get(m)
    if est is None:  # third-party variant: rank with the sb estimate
        est, m = mm2im_estimate, "mm2im"
    if m in PLAN_AWARE_METHODS and plan is not None:
        return est(p, batch, bits=bits, hw=hw,
                   block_oh=plan.block_oh, block_oc=plan.block_oc,
                   grid_order=plan.grid_order, fold_batch=plan.fold_batch)
    return est(p, batch, bits=bits, hw=hw)


def modeled_speedup(p: TConvProblem, batch: int = 1, *, bits: int = 8,
                    baseline: str = "iom_unfused", hw: HW = V5E,
                    plan=None, baseline_plan=None) -> float:
    """Predicted speedup of a plan's dataflow over a baseline (Fig. 6).

    Both sides of the ratio honour an explicit plan: ``plan`` selects the
    MM2IM-side kernel variant (single- vs double-buffered), block
    geometry and ``fold_batch`` — previously this side silently modeled
    the heuristic single-buffered dataflow even when a tuned
    double-buffered/folded plan was the one measured.  ``baseline_plan``
    does the same for a plan-aware ``baseline`` method (ignored for the
    unfused/direct baselines, which have no plan knobs).
    """
    t_b = estimate_for_plan(p, batch, plan=baseline_plan, method=baseline,
                            bits=bits, hw=hw).t_overlapped
    t_m = estimate_for_plan(p, batch, plan=plan, bits=bits,
                            hw=hw).t_overlapped
    return t_b / t_m
