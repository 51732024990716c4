"""MM2IM-KS — kernel-segregated zero-skipping TCONV as a Pallas TPU kernel.

Third kernel family of the registry (after ``mm2im`` / ``mm2im_db``),
implementing kernel segregation (Tida et al., PAPERS.md;
``core/segregate.py`` for the math and docs/DESIGN.md §2.6 for the
dataflow).  Per grid cell — one output row-block x one Oc block — the
``Ks²`` taps are regrouped into ``S²`` stride-1 sub-kernels and each
sub-problem runs as **one dense MatMul** over exactly the input rows that
feed it:

    (B_fold · (bi + Jh - 1) · Iw, Ic) @ (Ic, Jh·Jw·boc)

followed by stride-1 shifted adds into a *plane* and a single interleaved
view write ``acc[:, a', :, b', :] = plane``.  Compared to MM2IM's
dataflow this

* issues no ineffectual MACs: each sub-MatMul's M covers only the
  ``bi + Jh - 1`` slab rows its taps touch (MM2IM's single MatMul runs
  all ``n_slab`` rows against all ``Ks²`` taps and drops the misses), and
  a residue class with no taps (stride > kernel) issues nothing;
* needs no col2im scatter and no inter-sub-kernel accumulation: residue
  classes partition the output, so every accumulator element is written
  by exactly one sub-kernel (the overlapping-sums problem disappears by
  construction instead of being resolved in VMEM);
* degenerates to plain MM2IM at stride 1: one sub-kernel owning all taps,
  one full-slab MatMul, one plane covering the whole block.

Host staging is shared with the MM2IM family (``prepare_mm2im`` — same
padding, same slab geometry, same grid orders, same folded-batch rule);
only the weight relayout differs: the ``(Ic, Ks², Oc)`` tap axis is
permuted so each sub-kernel's taps form one contiguous static slice
(``core/segregate.pack_weights``).  The epilogue (bias + requant +
activation, f32/bf16 and the paper's int8 mode) and the custom_vjp
training path come from the same shared pieces as the other two kernels,
so the family is registered through the ordinary ``KernelSpec`` entry
point with full plan/int8/fold support.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.segregate import Segregation, segregate
from repro.kernels.mm2im_pallas import (MM2IMPrep, grid_semantics,
                                        mxu_precision, ppu_epilogue,
                                        prepare_mm2im)


def _sub_matmul(slab, w_ref, sk, *, m_rows: int, iw: int, boc: int,
                acc_dtype):
    """One sub-kernel's dense MatMul: (m_rows*iw, ic) @ (ic, Jh*Jw*boc).

    ``slab`` is the sub-kernel's exact input-row window (already sliced to
    ``bi + Jh - 1`` rows per batch element — possibly batch-concatenated
    when folded); the weight slice is the sub-kernel's contiguous tap
    range in the packed layout.
    """
    ic = slab.shape[-1]
    wsub = w_ref[:, sk.offset:sk.offset + sk.taps, :]  # (ic, Jh*Jw, boc)
    mm = jax.lax.dot_general(
        slab.reshape(m_rows * iw, ic), wsub.reshape(ic, sk.taps * boc),
        (((1,), (0,)), ((), ())),
        precision=mxu_precision(slab.dtype),
        preferred_element_type=acc_dtype,
    )
    return mm.reshape(m_rows, iw, sk.jh, sk.jw, boc)


def _sub_plane(mm5, sk, *, bi: int, iw: int, iw_p: int, boc: int, acc_dtype):
    """Stride-1 shifted adds: fold one sub-kernel's taps into its plane.

    ``mm5`` is ``(bi + Jh - 1, Iw, Jh, Jw, boc)`` for ONE batch element;
    plane cell ``(r, p)`` sums ``mm5[Jh-1-jh + r, p + mw - jw, jh, jw]``
    over the taps, with out-of-image columns clamped (zero contribution).
    All slice bounds are static — the Mapper-as-affine-arithmetic idea of
    the MM2IM kernel, at stride 1.
    """
    plane = jnp.zeros((bi, iw_p, boc), acc_dtype)
    for jh in range(sk.jh):
        r0 = sk.jh - 1 - jh  # top tap reads the deepest slab row
        for jw in range(sk.jw):
            c_ofs = sk.col_shift - jw
            p0, p1 = max(0, -c_ofs), min(iw_p, iw - c_ofs)
            if p1 <= p0:
                continue  # tap never intersects the image columns
            part = mm5[r0:r0 + bi, p0 + c_ofs:p1 + c_ofs, jh, jw, :]
            # Pad-and-add rather than .at[].add — the scatter-add lowering
            # captures an index-array constant, which pallas_call rejects.
            plane = plane + jnp.pad(
                part, ((0, 0), (p0, iw_p - p1), (0, 0)))
    return plane


def _ks_accumulate(slab, seg: Segregation, w_ref, *, b_fold: int, s: int,
                   bi: int, n_slab: int, iw: int, ow_p: int, boc: int,
                   delta: int, acc_dtype):
    """All S² sub-kernels for one row-block -> (b_fold, block_oh, ow_p, boc).

    ``slab`` is ``(b_fold, n_slab, iw, ic)``.  The accumulator is viewed
    ``(bi, S, Iw', S, boc)`` exactly like MM2IM's, but each ``(a', b')``
    lane is *written once* by its sub-kernel's plane — interleave, not
    accumulation.  Empty residue classes (stride > kernel) stay zero: the
    genuine gaps of the gapped TCONV output.
    """
    iw_p = ow_p // s
    zero = jnp.zeros((bi, iw_p, boc), acc_dtype)
    planes = [{} for _ in range(b_fold)]
    for sk in seg.subkernels:
        if sk.taps == 0:
            continue
        # Exact input-row window of this sub-kernel: plane row r (tap jh)
        # reads slab row delta + row_shift - jh + r  ∈  [rlo, rlo+bi+Jh-1).
        rlo = delta + sk.row_shift - (sk.jh - 1)
        m_rows = bi + sk.jh - 1
        window = slab[:, rlo:rlo + m_rows]  # (b_fold, m_rows, iw, ic)
        mm5 = _sub_matmul(window, w_ref, sk, m_rows=b_fold * m_rows, iw=iw,
                          boc=boc, acc_dtype=acc_dtype)
        for e in range(b_fold):
            planes[e][sk.row_phase, sk.col_phase] = _sub_plane(
                mm5[e * m_rows:(e + 1) * m_rows], sk, bi=bi, iw=iw,
                iw_p=iw_p, boc=boc, acc_dtype=acc_dtype)
    outs = []
    for e in range(b_fold):
        # Interleave by construction: stack the residue planes into
        # (bi, S, Iw', S, boc) — each (a', b') lane is exactly one plane,
        # no scatter, no inter-sub-kernel accumulation.
        acc = jnp.stack(
            [jnp.stack([planes[e].get((a, b), zero) for b in range(s)],
                       axis=2)
             for a in range(s)], axis=1)
        outs.append(acc.reshape(s * bi, ow_p, boc))
    return outs


def _mm2im_ks_kernel(
    x_ref, w_ref, b_ref, s_ref, o_ref, *, seg: Segregation,
    s: int, ks: int, ct: int, cl: int,
    bi: int, n_slab: int, iw: int, ow: int, ow_p: int, boc: int,
    delta: int, acc_dtype, out_dtype, activation: str, out_scale,
    per_channel: bool,
):
    """One grid cell of the unfolded grid (same loop nest as mm2im)."""
    j = pl.program_id(2)
    slab = x_ref[:, pl.dslice(j * bi, n_slab)]  # (1, n_slab, iw, ic)
    (out,) = _ks_accumulate(slab, seg, w_ref, b_fold=1, s=s, bi=bi,
                            n_slab=n_slab, iw=iw, ow_p=ow_p, boc=boc,
                            delta=delta, acc_dtype=acc_dtype)
    o_ref[0] = ppu_epilogue(
        out, b_ref[...], s_ref[...], acc_dtype=acc_dtype,
        activation=activation, out_scale=out_scale, per_channel=per_channel,
        out_dtype=out_dtype)


def _mm2im_ks_folded_kernel(
    x_ref, w_ref, b_ref, s_ref, o_ref, *, seg: Segregation, b: int,
    s: int, ks: int, ct: int, cl: int,
    bi: int, n_slab: int, iw: int, ow: int, ow_p: int, boc: int,
    delta: int, acc_dtype, out_dtype, activation: str, out_scale,
    per_channel: bool,
):
    """Batch-folded cell: each sub-MatMul's M carries all B elements."""
    j = pl.program_id(1)
    slab = x_ref[:, pl.dslice(j * bi, n_slab)]  # (B, n_slab, iw, ic)
    outs = _ks_accumulate(slab, seg, w_ref, b_fold=b, s=s, bi=bi,
                          n_slab=n_slab, iw=iw, ow_p=ow_p, boc=boc,
                          delta=delta, acc_dtype=acc_dtype)
    for e in range(b):
        o_ref[e] = ppu_epilogue(
            outs[e], b_ref[...], s_ref[...], acc_dtype=acc_dtype,
            activation=activation, out_scale=out_scale,
            per_channel=per_channel, out_dtype=out_dtype)


def _pack_prep_weights(p: MM2IMPrep, seg: Segregation) -> jax.Array:
    """Permute the staged ``(Ic, Ks², Oc_p)`` relayout into sub-kernel order."""
    return jnp.take(p.w3, jnp.asarray(seg.permutation()), axis=1)


def mm2im_ks_tconv(
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
    """Kernel-segregated transposed convolution (same contract as
    ``mm2im_tconv`` — drop-in third family behind the registry).

    Args match ``mm2im_pallas.mm2im_tconv``; see the module docstring for
    the dataflow difference.  ``fold_batch=True`` folds the batch into
    every sub-MatMul's M-dimension (plan schema v2), composing the
    MXU-filling trick with the zero-skipping decomposition.
    """
    p = prepare_mm2im(
        x, w, bias, stride=stride, padding=padding, block_oh=block_oh,
        block_oc=block_oc, activation=activation, out_scale=out_scale,
        out_dtype=out_dtype, grid_order=grid_order, interpret=interpret,
        fold_batch=fold_batch)
    seg = segregate(p.ks, p.s, padding)
    w_ks = _pack_prep_weights(p, seg)

    kw = dict(p.kernel_kwargs(), seg=seg)
    if p.fold_batch:
        kernel = functools.partial(_mm2im_ks_folded_kernel, b=p.b, **kw)
        grid = (p.n_c, p.n_j)
        in_specs = [
            pl.BlockSpec((p.b, p.ihp, p.iw, p.ic), lambda c, j: (0, 0, 0, 0)),
            pl.BlockSpec((p.ic, p.ks * p.ks, p.boc), lambda c, j: (0, 0, c)),
            pl.BlockSpec((1, p.boc), lambda c, j: (0, c)),
            pl.BlockSpec((1, p.boc), lambda c, j: (0, c)),
        ]
        out_specs = pl.BlockSpec((p.b, p.block_oh, p.ow_p, p.boc),
                                 lambda c, j: (0, j, 0, c))
        n_parallel = 1
    else:
        kernel = functools.partial(_mm2im_ks_kernel, **kw)
        if p.grid_order == "bcj":
            grid = (p.b, p.n_c, p.n_j)
            ix = lambda b_, c, j: (b_, 0, 0, 0)
            iw_ = lambda b_, c, j: (0, 0, c)
            ib = lambda b_, c, j: (0, c)
            io = lambda b_, c, j: (b_, j, 0, c)
        else:  # "cbj"
            grid = (p.n_c, p.b, p.n_j)
            ix = lambda c, b_, j: (b_, 0, 0, 0)
            iw_ = lambda c, b_, j: (0, 0, c)
            ib = lambda c, b_, j: (0, c)
            io = lambda c, b_, j: (b_, j, 0, c)
        in_specs = [
            pl.BlockSpec((1, p.ihp, p.iw, p.ic), ix),
            pl.BlockSpec((p.ic, p.ks * p.ks, p.boc), iw_),
            pl.BlockSpec((1, p.boc), ib),
            pl.BlockSpec((1, p.boc), ib),
        ]
        out_specs = pl.BlockSpec((1, p.block_oh, p.ow_p, p.boc), io)
        n_parallel = 2

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct(
            (p.b, p.n_j * p.block_oh, p.ow_p, p.oc_p), p.out_dtype),
        compiler_params=grid_semantics(n_parallel),
        interpret=p.interpret,
    )(p.x_p, w_ks, p.bias_p, p.scales_p)

    return out[:, :p.oh, :p.ow, :p.oc]
