"""MM2IM — fused MatMul + col2im transposed convolution, as a Pallas TPU kernel.

This is the TPU-native adaptation of the paper's accelerator
(docs/DESIGN.md §2):

* **Tiled MM2IM (Alg. 1)** -> the Pallas ``grid = (batch, O_c blocks, O_h
  row-blocks)``.  Each grid cell is *weight-stationary* in its O_c block
  (``filter_step`` == ``block_oc``) and *output-stationary* in VMEM
  accumulators holding ``block_oh`` complete output rows.  The contiguous
  input-row slab needed per output row-block (the ``i_end_row`` relation)
  is loaded with a dynamic VMEM slice — the analogue of ``SendInputRows``.

* **MM2IM Mapper (Alg. 2)** -> compile-time affine arithmetic.  For a fixed
  kernel offset ``(kh, kw)`` every partial product lands at
  ``oh = S*ih - ct + kh``, ``ow = S*iw - cl + kw``, i.e. in stride residue
  ``(a, b) = ((kh-ct) mod S, (kw-cl) mod S)`` at a fixed shift.  The kernel
  unrolls the ``Ks^2`` offsets and turns cmap/omap into *static slice
  offsets* (:class:`Col2im`) — zero bytes of map traffic.

* **Out-Muxer / overlapping sums** -> one VMEM accumulator per stride
  residue.  The input slab is staged flat, ``(rows * pitch, Ic)`` with a
  zero halo on every side, so each offset's product ``slab @ W[kh, kw]``
  contributes one *contiguous* static slice of itself to its residue's
  accumulator: the row shift is a multiple of the (sublane-aligned) pitch
  and the column shift a few elements more.  Overlaps accumulate in VMEM;
  every final output is written to HBM exactly once and **no partial
  product is ever materialized in HBM** (paper P2/P3).  The residues leave
  the kernel as a ``(bi, S, pitch, S·boc)`` block, which the host-side
  reshape interleaves into ``(S·bi, S·Iw', boc)`` output rows.

Everything the chip's compiler (Mosaic) needs is decided on the host: the
O_c block is a multiple of the 128-lane tile (O_c is zero-padded up to it),
the pitch a multiple of the 8-row sublane tile, and the bias/scale vectors
are ``(1, O_c)`` rows.  Interpret mode runs the same program.

The kernel supports f32 / bf16 inputs (f32 accumulation) and the paper's
8-bit mode (int8 x int8 -> int32 accumulation, optional requantization), and
fuses the PPU epilogue (bias + activation + requant).

The host-side staging (:func:`prepare_mm2im`, :func:`stage_slabs`) and the
per-block math (:func:`mm2im_block`, :func:`residue_outputs`) are shared
with the double-buffered pipeline variant (``kernels/mm2im_db_pallas.py``),
so the two kernels are bit-identical by construction — they differ only in
how the input slab reaches VMEM (resident whole-input block here vs.
pipelined two-slot DMA there; docs/DESIGN.md §2.4).

**Batch folding** (plan schema v2, ``fold_batch=True``): for batched
small-spatial problems (DCGAN's first TCONV has ``n_slab·Iw`` ≈ 24 MatMul
rows against a 128-wide MXU) the per-element MatMul runs mostly empty.
Folding stages the batch *between* the input rows and columns — row pitch
``B·Iw'`` — so one ``(rows·B·Iw', Ic)`` product per offset covers every
element, the grid drops its batch axis, and the same static slice offsets
serve all elements at once.  Each output element still sums the same
products in the same order, so folded and unfolded results are
bit-identical (docs/DESIGN.md §2.5).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.epilogue import ACTIVATIONS
from repro.kernels.ref import crop_offsets, out_size

# Back-compat alias: the activation table (and the leaky-relu slope) moved
# to the shared PPU epilogue module so the kernel forward, the dispatcher's
# unfused remainder and the custom_vjp backward agree by construction.
_ACTIVATIONS = ACTIVATIONS

LANE = 128      # vreg lanes: the O_c block is a multiple of this
SUBLANE = 8     # vreg sublanes (32-bit): the slab row pitch is a multiple
# VMEM a block plan may use (the heuristic's budget), and the scoped-VMEM
# limit handed to Mosaic.  A TPU v5e core has 128 MiB of VMEM; the gap
# between the two is headroom for the compiler's own scratch.
VMEM_BUDGET = 48 * 2**20
VMEM_LIMIT = 64 * 2**20


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, m: int) -> int:
    return _ceil_div(a, m) * m


def lane_block(block_oc: int, oc: int) -> int:
    """The O_c block a kernel actually runs: ``block_oc`` rounded up to the
    128-lane tile, and no wider than O_c rounded up to it."""
    return min(_round_up(max(int(block_oc), 1), LANE), _round_up(oc, LANE))


def grid_semantics(n_parallel: int,
                   inner_arbitrary: bool = True) -> "pltpu.CompilerParams":
    """Mosaic dimension semantics (and VMEM limit) for an MM2IM grid.

    Every outer grid dimension (batch / oc-block — and, folded, just the
    oc-block) indexes independent work, so Mosaic may partition those grid
    cells across TensorCores (``"parallel"``).  The single-buffered
    kernel's inner output-row sweep stays ``"arbitrary"`` (it revisits the
    resident input block across ``j`` steps); the double-buffered kernel
    pipelines ``j`` in-kernel, so its grid is outer dims only
    (``inner_arbitrary=False``).  Interpret mode accepts and ignores the
    annotation, so one call site serves both backends.
    """
    sem = ("parallel",) * n_parallel
    if inner_arbitrary:
        sem += ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=VMEM_LIMIT)


@dataclasses.dataclass(frozen=True)
class Col2im:
    """Static col2im schedule of one row-block (paper Alg. 2 as offsets).

    The slab is ``n_rows`` input rows of ``pitch`` elements each
    (``pitch = batch_pitch · iwp``; ``iwp`` columns per element, the first
    ``dw`` of them zero halo).  ``taps[t] = (a, b, row0, shift)`` for
    kernel offset ``t = kh·Ks + kw``: its MXU product covers the
    ``tap_rows`` slab rows from flat offset ``row0`` (a multiple of the
    sublane-aligned pitch), and adds ``product[shift : shift + bi·pitch]``
    to residue ``(a, b)``.  Columns of an accumulator row past the
    output's ``ceil(Ow/S)`` are halo, cropped on the host.
    """

    s: int
    bi: int
    iwp: int
    dw: int
    n_rows: int
    tap_rows: int
    pitch: int
    taps: tuple

    @property
    def block_rows(self) -> int:
        return self.bi * self.pitch

    @property
    def tap_len(self) -> int:
        """Flat slab rows one kernel offset's product covers."""
        return self.tap_rows * self.pitch


def col2im_schedule(*, ks: int, s: int, ct: int, cl: int, delta: int,
                    iw: int, ow: int, bi: int,
                    batch_pitch: int = 1) -> Col2im:
    """Slice offsets of every kernel offset for one row-block.

    Output row ``S*q + a`` of the block reads slab row
    ``q + delta - (kh-ct)//S`` (``delta`` = top halo rows of the staged
    input); output column ``S*r + b`` reads staged column
    ``r + dw - (kw-cl)//S``.  The column halo ``dw`` keeps every shift
    non-negative, and ``iwp`` leaves room for the largest shift past the
    ``ceil(Ow/S)`` kept columns, so a shifted row never wraps into a kept
    column.
    One extra slab row per product keeps its shifted slice in bounds.
    """
    qw = [(kw - cl) // s for kw in range(ks)]
    dw = max(qw)
    max_shift = dw - min(qw)
    wacc = _ceil_div(ow, s)
    iwp = _round_up(max(wacc + max_shift, dw + iw), SUBLANE)
    pitch = batch_pitch * iwp
    row_off = [delta - (kh - ct) // s for kh in range(ks)]
    tap_rows = bi + (1 if max_shift else 0)
    taps = tuple(((kh - ct) % s, (kw - cl) % s, row_off[kh] * pitch,
                  dw - qw[kw])
                 for kh in range(ks) for kw in range(ks))
    return Col2im(s=s, bi=bi, iwp=iwp, dw=dw,
                  n_rows=max(row_off) + tap_rows, tap_rows=tap_rows,
                  pitch=pitch, taps=taps)


def _schedule_for(ih: int, iw: int, ks: int, s: int, padding: str, bi: int,
                  batch_pitch: int) -> tuple[Col2im, int]:
    """(schedule, staged input rows) of a problem at row-block ``bi``."""
    ct, cl = crop_offsets(ks, s, padding)
    oh = out_size(ih, ks, s, padding)
    ow = out_size(iw, ks, s, padding)
    delta = _ceil_div(max(ks - 1 - ct, 0), s)
    sched = col2im_schedule(ks=ks, s=s, ct=ct, cl=cl, delta=delta, iw=iw,
                            ow=ow, bi=bi, batch_pitch=batch_pitch)
    n_j = _ceil_div(oh, s * bi)
    ihp = max((n_j - 1) * bi + sched.n_rows, delta + ih)
    return sched, ihp


def mm2im_vmem_bytes(ih: int, iw: int, ic: int, ks: int, oc: int, s: int,
                     padding: str, *, block_oh: int, block_oc: int,
                     in_bytes: int = 4, batch: int = 1,
                     fold_batch: bool = False,
                     whole_input: bool = True) -> int:
    """VMEM one grid cell holds.

    Pallas double-buffers every blocked operand: the staged input (its
    ``Ic`` lanes padded to the 128-lane tile) — resident whole
    (``whole_input``, the single-buffered kernel) or as two slab slots
    (the double-buffered kernel) — the ``(Ks², Ic, boc)`` weight block and
    the ``(bi, S, pitch, S·boc)`` output block.  Inside the cell live one
    32-bit product per offset and the S² residue accumulators.
    """
    bi = block_oh // s
    boc = lane_block(block_oc, oc)
    sched, ihp = _schedule_for(ih, iw, ks, s, padding, bi,
                               batch if fold_batch else 1)
    rows = ihp if whole_input else sched.n_rows
    x_blk = rows * sched.pitch * _round_up(ic, LANE) * in_bytes
    w_blk = ks * ks * _round_up(ic, LANE) * boc * in_bytes
    product = sched.tap_len * boc * 4
    acc = s * s * sched.block_rows * boc * 4
    return 2 * x_blk + 2 * w_blk + 2 * product + 3 * acc


def plan_blocks(
    ih: int, iw: int, ic: int, ks: int, oc: int, stride: int, padding: str,
    *, vmem_budget: int = VMEM_BUDGET, in_bytes: int = 4,
    override: Optional[tuple[int, int]] = None,
    batch: int = 1, fold_batch: bool = False,
) -> tuple[int, int]:
    """Pick (block_oh, block_oc) within a VMEM budget.

    block_oh = S * bi (aligned so the input slab per block is a static-size
    contiguous row range); block_oc tiles the N dimension of the MatMul and
    is always a multiple of the 128-lane tile.  This is the host-driver
    role of the paper's 0x01 Configure instruction.

    ``fold_batch=True`` sizes the blocks for the folded launch, whose
    staged input, products and accumulators are ``B`` elements wide
    (:func:`mm2im_vmem_bytes` models it exactly).

    ``override=(block_oh, block_oc)`` bypasses the heuristic entirely (the
    autotuner's explicit-plan path); it is validated, not second-guessed.
    """
    s = stride
    if override is not None:
        boh, boc = int(override[0]), int(override[1])
        if boh % s != 0 or boh < s:
            raise ValueError(
                f"override block_oh={boh} must be a positive multiple of "
                f"stride {s}")
        if boc < 1:
            raise ValueError(f"override block_oc={boc} must be positive")
        return boh, boc
    oh = out_size(ih, ks, s, padding)

    def vmem(bi: int, boc: int) -> int:
        return mm2im_vmem_bytes(ih, iw, ic, ks, oc, s, padding,
                                block_oh=s * bi, block_oc=boc,
                                in_bytes=in_bytes, batch=batch,
                                fold_batch=fold_batch)

    # Prefer the largest bi * boc (fewest grid steps, least halo recompute).
    best = None
    for boc in sorted({lane_block(b, oc) for b in (128, 256, 512)},
                      reverse=True):
        for bi in (64, 32, 16, 8, 4, 2, 1):
            if s * bi > max(oh, s):
                continue
            if vmem(bi, boc) <= vmem_budget:
                if best is None or bi * boc > (best[0] // s) * best[1]:
                    best = (s * bi, boc)
                break
    if best is None:
        best = (s, LANE)
    return best


def mxu_precision(dtype) -> jax.lax.Precision:
    """Contract precision of every MXU product of the MM2IM families.

    f32 operands multiply at full f32 precision (``HIGHEST``); bf16 and
    int8 operands at their native one (``DEFAULT``: the MXU has no
    f32-precision int8 product, and Mosaic refuses to lower one).  Fixed
    here, whatever ``jax.default_matmul_precision`` says, so every family
    serves the same numerics and the autotuner ranks them on dataflow
    alone.
    """
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def mm2im_block(slab_ref, base, w_ref, sched: Col2im, *, acc_dtype) -> dict:
    """MatMul + col2im for one row-block: ``{(a, b): (bi*pitch, boc)}``.

    ``slab_ref[base:]`` holds the block's ``(n_rows*pitch, Ic)`` slab.
    One MXU product per kernel offset, over only the ``tap_rows`` slab
    rows its slice reads — ``(tap_rows*pitch, Ic) @ (Ic, boc)`` — and
    that static slice added to the offset's residue accumulator.  Shared
    by the single- and double-buffered kernels; identical operand shapes
    and summation order is what makes the two variants bit-identical.
    """
    n = sched.block_rows
    precision = mxu_precision(slab_ref.dtype)
    acc = {}
    for t, (a, b, row0, shift) in enumerate(sched.taps):
        prod = jax.lax.dot_general(
            slab_ref[pl.ds(base + row0, sched.tap_len), :], w_ref[t],
            (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=acc_dtype)
        part = prod[shift:shift + n]
        acc[a, b] = part if (a, b) not in acc else acc[a, b] + part
    return acc


def residue_outputs(acc: dict, sched: Col2im, bias, scales, *, boc: int,
                    acc_dtype, out_dtype, activation: str, out_scale,
                    per_channel: bool):
    """Yield ``(a, b, (bi, pitch, boc) block)`` after the PPU epilogue.

    A residue no kernel offset reaches (gapped strides) is bias-only.
    """
    zero = jnp.zeros((sched.block_rows, boc), acc_dtype)
    for a in range(sched.s):
        for b in range(sched.s):
            out = ppu_epilogue(
                acc.get((a, b), zero), bias, scales, acc_dtype=acc_dtype,
                activation=activation, out_scale=out_scale,
                per_channel=per_channel, out_dtype=out_dtype)
            yield a, b, out.reshape(sched.bi, sched.pitch, boc)


def ppu_epilogue(out, bias_row, scales_row, *, acc_dtype, activation: str,
                 out_scale, per_channel: bool, out_dtype):
    """PPU epilogue: bias + (per-tensor or per-channel, TFLite-style)
    requant + activation, fused before the single HBM write.

    ``bias_row`` / ``scales_row`` are ``(1, boc)`` and broadcast over the
    block's trailing channel axis.  Same stage order and rounding as the
    dispatcher-side ``core.epilogue.apply_epilogue`` (an integer store
    rounds, never truncates), so fused and unfused execution of one
    epilogue agree.
    """
    out = out + bias_row.astype(acc_dtype)
    if per_channel:
        out = jnp.round(out.astype(jnp.float32) * scales_row)
        out = jnp.clip(out, -128.0, 127.0)
    elif out_scale is not None:
        out = jnp.round(out.astype(jnp.float32) * out_scale)
        out = jnp.clip(out, -128.0, 127.0)
    out = ACTIVATIONS[activation](out)
    if (jnp.issubdtype(jnp.dtype(out_dtype), jnp.integer)
            and not jnp.issubdtype(out.dtype, jnp.integer)):
        out = jnp.round(out)
    return out.astype(out_dtype)


def _mm2im_kernel(x_ref, w_ref, b_ref, s_ref, o_ref, *, sched: Col2im,
                  boc: int, **epilogue):
    """One grid cell: output rows [j*S*bi, (j+1)*S*bi) x one O_c block.

    Grid order is (batch, oc-block, oh-block) or (oc-block, batch,
    oh-block) — the paper's Alg. 1 loop nest: the weight block index is
    constant while j advances (weight-stationary), and the whole staged
    input of a batch element stays resident across the row sweep.
    """
    j = pl.program_id(2)  # inner output-row sweep (both grid orders)

    # --- SendInputRows: the contiguous slab feeding this output row-block.
    start = pl.multiple_of(j * sched.block_rows, SUBLANE)
    acc = mm2im_block(x_ref.at[0], start, w_ref, sched,
                      acc_dtype=epilogue["acc_dtype"])
    for a, b, out in residue_outputs(acc, sched, b_ref[...], s_ref[...],
                                     boc=boc, **epilogue):
        o_ref[0, :, a, :, pl.ds(b * boc, boc)] = out


@dataclasses.dataclass
class MM2IMPrep:
    """Staged operands + resolved tile geometry for one MM2IM launch.

    Produced by :func:`prepare_mm2im` and consumed by every Pallas family
    (``mm2im``, ``mm2im_db``, ``mm2im_ks``, ``mm2im_og``), so the host-side
    staging — padding, weight relayout, block validation, grid-order
    resolution — is decided in exactly one place.
    """

    # Staged arrays.
    x_p: jax.Array        # (B, Ihp, Iw, Ic) zero-padded input
    w3: jax.Array         # (Ic, Ks^2, Oc_p) relaid-out filters
    bias_p: jax.Array     # (1, Oc_p) accumulator-dtype bias
    scales_p: jax.Array   # (1, Oc_p) per-channel requant scales (or ones)
    # Problem geometry.
    b: int; ih: int; iw: int; ic: int; ks: int; oc: int
    s: int; ct: int; cl: int; oh: int; ow: int
    # Tile geometry (paper Alg. 1).
    block_oh: int; boc: int; bi: int; delta: int
    n_slab: int; n_j: int; n_c: int; ihp: int; ow_p: int; oc_p: int
    # Dtypes / epilogue.
    acc_dtype: object; out_dtype: object
    per_channel: bool; out_scale: Optional[float]; activation: str
    grid_order: str; interpret: bool
    # Plan v2: batch folded into the MatMul M-dimension (grid drops batch).
    fold_batch: bool = False

    def kernel_kwargs(self) -> dict:
        """The static kwargs of the ``mm2im_ks`` / ``mm2im_og`` bodies."""
        return dict(
            s=self.s, ks=self.ks, ct=self.ct, cl=self.cl, bi=self.bi,
            n_slab=self.n_slab, iw=self.iw, ow=self.ow, ow_p=self.ow_p,
            boc=self.boc, delta=self.delta, **self.epilogue_kwargs())

    def epilogue_kwargs(self) -> dict:
        """The static PPU-epilogue kwargs every kernel body takes."""
        return dict(
            acc_dtype=self.acc_dtype, out_dtype=self.out_dtype,
            activation=self.activation,
            out_scale=None if self.per_channel else self.out_scale,
            per_channel=self.per_channel)


def prepare_mm2im(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array],
    *,
    stride: int,
    padding: str,
    block_oh: Optional[int],
    block_oc: Optional[int],
    activation: str,
    out_scale,
    out_dtype,
    grid_order: str,
    interpret: Optional[bool],
    fold_batch: bool = False,
) -> MM2IMPrep:
    """Host-side staging (the driver role / 0x01 Configure instruction)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, ih, iw, ic = x.shape
    ks, ks2, oc, wic = w.shape
    assert ks == ks2 and wic == ic, (w.shape, x.shape)
    s = stride
    ct, cl = crop_offsets(ks, s, padding)
    oh = out_size(ih, ks, s, padding)
    ow = out_size(iw, ks, s, padding)

    integer = jnp.issubdtype(x.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    per_channel = out_scale is not None and not isinstance(out_scale, float)
    if out_dtype is None:
        out_dtype = jnp.int8 if (integer and out_scale is not None) else acc_dtype

    if block_oh is None or block_oc is None:
        p_oh, p_oc = plan_blocks(ih, iw, ic, ks, oc, s, padding,
                                 in_bytes=x.dtype.itemsize,
                                 batch=b, fold_batch=fold_batch)
        block_oh = block_oh or p_oh
        block_oc = block_oc or p_oc
    # Explicit-plan path: plan_blocks validates the override (stride
    # alignment, positivity) in one place for every caller.
    block_oh, block_oc = plan_blocks(ih, iw, ic, ks, oc, s, padding,
                                     override=(block_oh, block_oc))
    bi = block_oh // s
    boc = lane_block(block_oc, oc)

    # Geometry of the input slab per output row-block (docs/DESIGN.md §2).
    delta = _ceil_div(max(ks - 1 - ct, 0), s)  # top halo (in input rows)
    eps = (ct - 1) // s                        # bottom halo correction
    n_slab = bi + delta + eps + 1
    n_j = _ceil_div(oh, block_oh)
    n_c = _ceil_div(oc, boc)
    ow_p = _ceil_div(ow, s) * s

    # Host-side data staging: zero-pad so every slab and every block index
    # is in range; jit fuses these pads into the caller.
    ihp = (n_j - 1) * bi + n_slab
    x_p = jnp.pad(x, ((0, 0), (delta, max(ihp - delta - ih, 0)),
                      (0, 0), (0, 0)))
    ihp = x_p.shape[1]
    oc_p = n_c * boc
    w3 = jnp.transpose(w, (3, 0, 1, 2)).reshape(ic, ks * ks, oc)  # (K, Ks^2, Oc)
    w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, oc_p - oc)))
    if bias is None:
        bias = jnp.zeros((oc,), acc_dtype)
    bias_p = jnp.pad(bias.astype(acc_dtype), (0, oc_p - oc))[None]
    if per_channel:
        scales_p = jnp.pad(jnp.asarray(out_scale, jnp.float32),
                           (0, oc_p - oc), constant_values=1.0)[None]
    else:
        scales_p = jnp.ones((1, oc_p), jnp.float32)

    # Grid order (Alg. 1 loop-nest choice): j (output rows) is always the
    # inner sweep; the outer pair decides which operand stays resident in
    # VMEM across the most steps.  'bcj' = activation-stationary (input
    # fetched once per batch element), 'cbj' = weight-stationary (each
    # filter block fetched exactly once, the paper's Alg. 1 order).  'auto'
    # picks by which operand carries more HBM traffic.
    if grid_order == "auto":
        w_bytes = ic * ks * ks * oc_p * w.dtype.itemsize
        x_bytes = b * ihp * iw * ic * x.dtype.itemsize
        grid_order = "cbj" if w_bytes > x_bytes else "bcj"
    if grid_order not in ("bcj", "cbj"):
        raise ValueError(
            f"grid_order must be 'auto'|'bcj'|'cbj', got {grid_order!r}")

    return MM2IMPrep(
        x_p=x_p, w3=w3, bias_p=bias_p, scales_p=scales_p,
        b=b, ih=ih, iw=iw, ic=ic, ks=ks, oc=oc, s=s, ct=ct, cl=cl,
        oh=oh, ow=ow, block_oh=block_oh, boc=boc, bi=bi, delta=delta,
        n_slab=n_slab, n_j=n_j, n_c=n_c, ihp=ihp, ow_p=ow_p, oc_p=oc_p,
        acc_dtype=acc_dtype, out_dtype=out_dtype, per_channel=per_channel,
        out_scale=out_scale, activation=activation, grid_order=grid_order,
        interpret=interpret, fold_batch=bool(fold_batch))


def stage_slabs(p: MM2IMPrep) -> tuple[jax.Array, jax.Array, Col2im]:
    """Flat slab staging of the ``mm2im`` / ``mm2im_db`` kernels.

    Returns ``(x_flat, w_taps, schedule)``: the input zero-padded to the
    schedule's rows and columns and flattened to ``(B_grid, rows*pitch,
    Ic_p)`` — batch-major, or with the batch between rows and columns when
    folded (``B_grid = 1``, ``pitch = B*iwp``) — and the filters as one
    ``(Ic_p, boc)``-blockable matrix per kernel offset, ``(Ks², Ic_p,
    Oc_p)``.  ``Ic_p`` is ``Ic`` zero-padded to the 128-lane tile, the
    width VMEM holds anyway and the one the slab DMA can address.
    """
    sched = col2im_schedule(ks=p.ks, s=p.s, ct=p.ct, cl=p.cl, delta=p.delta,
                            iw=p.iw, ow=p.ow, bi=p.bi,
                            batch_pitch=p.b if p.fold_batch else 1)
    rows = max((p.n_j - 1) * p.bi + sched.n_rows, p.ihp)
    ic_p = _round_up(p.ic, LANE)
    x = jnp.pad(p.x_p, ((0, 0), (0, rows - p.ihp),
                        (sched.dw, sched.iwp - sched.dw - p.iw),
                        (0, ic_p - p.ic)))
    if p.fold_batch:
        x = jnp.transpose(x, (1, 0, 2, 3))
    x = x.reshape(-1, rows * sched.pitch, ic_p)
    w_taps = jnp.pad(jnp.transpose(p.w3, (1, 0, 2)),
                     ((0, 0), (0, ic_p - p.ic), (0, 0)))
    return x, w_taps, sched


def slab_out_shape(p: MM2IMPrep, sched: Col2im) -> jax.ShapeDtypeStruct:
    """``(B_grid, n_j*bi, S, pitch, n_c*S*boc)``: residue-major output."""
    return jax.ShapeDtypeStruct(
        (1 if p.fold_batch else p.b, p.n_j * p.bi, p.s, sched.pitch,
         p.n_c * p.s * p.boc), p.out_dtype)


def unstage_output(out: jax.Array, p: MM2IMPrep,
                   sched: Col2im) -> jax.Array:
    """Interleave the residue-major kernel output into ``(B, Oh, Ow, Oc)``."""
    q, s = p.n_j * p.bi, p.s
    if p.fold_batch:
        y = out.reshape(q, s, p.b, sched.iwp, p.n_c, s, p.boc)
        y = jnp.transpose(y, (2, 0, 1, 3, 5, 4, 6))
    else:
        y = out.reshape(p.b, q, s, sched.iwp, p.n_c, s, p.boc)
        y = jnp.transpose(y, (0, 1, 2, 3, 5, 4, 6))
    y = y.reshape(p.b, q * s, sched.iwp * s, p.oc_p)
    return y[:, :p.oh, :p.ow, :p.oc]


def mm2im_tconv(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    stride: int,
    padding: str = "SAME",
    block_oh: Optional[int] = None,
    block_oc: Optional[int] = None,
    activation: str = "none",
    out_scale: Optional[float] = None,
    out_dtype=None,
    grid_order: str = "auto",
    interpret: Optional[bool] = None,
    fold_batch: bool = False,
) -> jax.Array:
    """Fused MM2IM transposed convolution.

    Args:
      x: (B, Ih, Iw, Ic) activations — f32, bf16 or int8.
      w: (Ks, Ks, Oc, Ic) filters (HWOI, paper layout).
      bias: (Oc,) or None.
      stride / padding: TCONV geometry (padding in {'SAME','VALID'}).
      block_oh / block_oc: Tiled-MM2IM block sizes; auto-planned if None.
        ``block_oc`` runs rounded up to the 128-lane tile (:func:`lane_block`).
      activation: fused epilogue nonlinearity.
      out_scale: if set (int8 mode), requantize int32 accum -> int8.
      interpret: force Pallas interpret mode (defaults to True off-TPU).
      fold_batch: stage the batch into the MatMul M-dimension — the grid
        drops its batch axis and one (rows*B*Iw', Ic) product per kernel
        offset serves every element (bit-identical to unfolded;
        docs/DESIGN.md §2.5).
    """
    p = prepare_mm2im(
        x, w, bias, stride=stride, padding=padding, block_oh=block_oh,
        block_oc=block_oc, activation=activation, out_scale=out_scale,
        out_dtype=out_dtype, grid_order=grid_order, interpret=interpret,
        fold_batch=fold_batch)
    x_flat, w_taps, sched = stage_slabs(p)
    kernel = functools.partial(_mm2im_kernel, sched=sched, boc=p.boc,
                               **p.epilogue_kwargs())

    # Folded, the batch lives inside the slab and the batch axis is 1 —
    # grid_order's bcj/cbj distinction collapses with it.
    n_b = x_flat.shape[0]
    if p.grid_order == "bcj" or p.fold_batch:
        grid = (n_b, p.n_c, p.n_j)
        ids = lambda b_, c, j: (b_, c, j)
    else:  # "cbj"
        grid = (p.n_c, n_b, p.n_j)
        ids = lambda c, b_, j: (b_, c, j)
    ix = lambda *g: (ids(*g)[0], 0, 0)
    iw_ = lambda *g: (0, 0, ids(*g)[1])
    ib = lambda *g: (0, ids(*g)[1])
    io = lambda *g: (ids(*g)[0], ids(*g)[2], 0, 0, ids(*g)[1])

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,) + x_flat.shape[1:], ix),
            pl.BlockSpec(w_taps.shape[:2] + (p.boc,), iw_),
            pl.BlockSpec((1, p.boc), ib),
            pl.BlockSpec((1, p.boc), ib),
        ],
        out_specs=pl.BlockSpec((1, p.bi, p.s, sched.pitch, p.s * p.boc), io),
        out_shape=slab_out_shape(p, sched),
        compiler_params=grid_semantics(2),
        interpret=p.interpret,
    )(x_flat, w_taps, p.bias_p, p.scales_p)
    return unstage_output(out, p, sched)
