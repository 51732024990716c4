"""MM2IM-OG — output-gathered implicit-GEMM TCONV as a Pallas TPU kernel.

Fourth kernel family of the registry (after ``mm2im`` / ``mm2im_db`` /
``mm2im_ks``), implementing the *output-gathered* dataflow (the
AttentionEngine ``conv_transpose_example`` exemplar in SNIPPETS.md;
EcoFlow's dataflow taxonomy in PAPERS.md names this the gather-style
TCONV).  Where MM2IM computes a dense input-stationary product and
*scatters* it through col2im, and MM2IM-KS computes per-sub-kernel
products and folds taps with post-MatMul shifted adds, MM2IM-OG inverts
the direction entirely: each output tile *gathers* the strided input
contributions that feed it and reduces over the taps **inside the MXU
K-dimension**.

For output pixel ``(oh, ow)`` the contributing input taps are the
``(kh, kw)`` with ``(oh + ct - kh) % S == 0`` — exactly the tap groups of
``core/segregate.py``'s residue decomposition, so the host-side sub-kernel
bookkeeping is shared.  Per residue class ``(a', b')`` the kernel builds a
gathered operand by stacking the ``Jh·Jw`` statically-shifted input
windows along a new tap axis,

    G : (B_fold · bi · Iw', Jh·Jw·Ic)      (VMEM-staged, static slices)

and issues **one dense MXU product** against the tap-major weight slice,

    G @ W[a', b'] : (Jh·Jw·Ic, boc)  ->  plane (B_fold · bi · Iw', boc).

The plane *is* the output restricted to its residue class — written once
by an interleaved view, like MM2IM-KS.  Compared to the other families:

* **no col2im scatter and no inter-block accumulation**: every output
  element is produced by exactly one MatMul row — residue classes
  partition the output and the tap reduction happens inside the
  contraction, so nothing is ever read back and re-added (MM2IM
  accumulates ``Ks²`` shifted contributions in VMEM; KS still folds each
  sub-kernel's taps with ``Jh·Jw`` post-MatMul shifted adds);
* **no ineffectual MACs**: like KS, empty residue classes of a gapped
  stride > kernel TCONV issue nothing and no inserted zero is multiplied;
* **exact-size output tiles**: M = ``bi·Iw'`` output pixels, not the
  ``(bi + Jh - 1)·Iw`` halo-extended input window KS runs — the win
  grows with the image (large-image / stride-4 decoder shapes, the
  FSRCNN/pix2pix regime), which is exactly where slab residency caps
  MM2IM.  The cost is gather-read amplification: each input element is
  re-read once per tap that uses it while the gathered operand is staged
  in VMEM (``core/perf_model.mm2im_og_estimate`` models the trade).

Host staging is shared with the MM2IM family (``prepare_mm2im`` — same
padding, slab geometry, grid orders, folded-batch rule).  The weight
layout is the KS packed permutation transposed to tap-major
``(Ks², Ic, Oc_p)`` so each sub-kernel's ``(Jh·Jw·Ic, boc)`` slice is one
contiguous static block whose K ordering matches the gathered operand.
Epilogue (bias + requant + activation, f32/bf16 and the paper's int8
mode) and the custom_vjp training path ride the same shared pieces as the
other kernels; the family registers through the ordinary ``KernelSpec``
entry point with full plan/int8/fold support.  docs/DESIGN.md §2.7 walks
through the gather index math.
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


def _og_gather(slab, sk, *, bi: int, iw: int, iw_p: int, delta: int):
    """Stage one residue class's gathered operand: (b_fold, bi, Iw', taps, ic).

    Plane cell ``(r, p)`` of residue ``(a', b')`` gathers input element
    ``x[r + row_shift - jh, p + col_shift - jw]`` for each tap — in slab
    coordinates (the input is top-padded by ``delta`` rows) tap ``jh``
    reads the static ``bi``-row slice starting at
    ``delta + row_shift - jh``, and tap ``jw`` reads the static column
    window shifted by ``col_shift - jw`` (out-of-image columns are zero
    contributions, padded back to ``Iw'``).  A tap whose column window
    never intersects the image still contributes a zero block: the
    gathered K extent must match the sub-kernel's contiguous weight slice.
    All bounds are static — the Mapper-as-affine-arithmetic idea of the
    MM2IM kernel, pointed at the gather direction.
    """
    taps = []
    for jh in range(sk.jh):
        r0 = delta + sk.row_shift - jh
        rows = slab[:, r0:r0 + bi]  # (b_fold, bi, iw, ic)
        for jw in range(sk.jw):
            c_ofs = sk.col_shift - jw
            p0, p1 = max(0, -c_ofs), min(iw_p, iw - c_ofs)
            if p1 <= p0:
                cols = jnp.zeros(rows.shape[:2] + (iw_p,) + rows.shape[3:],
                                 rows.dtype)
            else:
                part = rows[:, :, p0 + c_ofs:p1 + c_ofs, :]
                cols = jnp.pad(part, ((0, 0), (0, 0), (p0, iw_p - p1),
                                      (0, 0)))
            taps.append(cols)
    return jnp.stack(taps, axis=3)  # (b_fold, bi, iw_p, taps, ic)


def _og_plane(slab, w_ref, sk, *, b_fold: int, bi: int, iw: int, iw_p: int,
              boc: int, delta: int, acc_dtype):
    """One residue class: gather + ONE dense MXU product -> its plane.

    ``(b_fold·bi·Iw', Jh·Jw·Ic) @ (Jh·Jw·Ic, boc)`` — the tap reduction
    lives inside the contraction, so each plane element is written exactly
    once with no post-MatMul adds.  The weight slice is the sub-kernel's
    contiguous tap range of the tap-major packed layout, whose
    ``(tap, ic)`` K order matches the gathered operand's by construction.
    """
    g = _og_gather(slab, sk, bi=bi, iw=iw, iw_p=iw_p, delta=delta)
    ic = g.shape[-1]
    wsub = w_ref[sk.offset:sk.offset + sk.taps]  # (taps, ic, boc)
    mm = jax.lax.dot_general(
        g.reshape(b_fold * bi * iw_p, sk.taps * ic),
        wsub.reshape(sk.taps * ic, boc),
        (((1,), (0,)), ((), ())),
        precision=mxu_precision(g.dtype),
        preferred_element_type=acc_dtype,
    )
    return mm.reshape(b_fold, bi, iw_p, boc)


def _og_accumulate(slab, seg: Segregation, w_ref, *, b_fold: int, s: int,
                   bi: int, iw: int, ow_p: int, boc: int, delta: int,
                   acc_dtype):
    """All S² residue planes for one row-block -> (b_fold, block_oh, ow_p, boc).

    ``slab`` is ``(b_fold, n_slab, iw, ic)``.  Planes are assembled by the
    same interleave-by-construction stack as MM2IM-KS — each ``(a', b')``
    lane is exactly one plane, no scatter — but here each plane arrives
    from a single MatMul with the taps already reduced.  Empty residue
    classes (stride > kernel) stay zero: the genuine gaps of the gapped
    TCONV output.
    """
    iw_p = ow_p // s
    zero = jnp.zeros((bi, iw_p, boc), acc_dtype)
    planes = {}
    for sk in seg.subkernels:
        if sk.taps == 0:
            continue
        planes[sk.row_phase, sk.col_phase] = _og_plane(
            slab, w_ref, sk, b_fold=b_fold, bi=bi, iw=iw, iw_p=iw_p,
            boc=boc, delta=delta, acc_dtype=acc_dtype)
    outs = []
    for e in range(b_fold):
        acc = jnp.stack(
            [jnp.stack([planes[a, b][e] if (a, b) in planes else zero
                        for b in range(s)], axis=2)
             for a in range(s)], axis=1)
        outs.append(acc.reshape(s * bi, ow_p, boc))
    return outs


def _mm2im_og_kernel(
    x_ref, w_ref, b_ref, s_ref, o_ref, *, seg: Segregation,
    s: int, ks: int, ct: int, cl: int,
    bi: int, n_slab: int, iw: int, ow: int, ow_p: int, boc: int,
    delta: int, acc_dtype, out_dtype, activation: str, out_scale,
    per_channel: bool,
):
    """One grid cell of the unfolded grid (same loop nest as mm2im)."""
    j = pl.program_id(2)
    slab = x_ref[:, pl.dslice(j * bi, n_slab)]  # (1, n_slab, iw, ic)
    (out,) = _og_accumulate(slab, seg, w_ref, b_fold=1, s=s, bi=bi, iw=iw,
                            ow_p=ow_p, boc=boc, delta=delta,
                            acc_dtype=acc_dtype)
    o_ref[0] = ppu_epilogue(
        out, b_ref[...], s_ref[...], acc_dtype=acc_dtype,
        activation=activation, out_scale=out_scale, per_channel=per_channel,
        out_dtype=out_dtype)


def _mm2im_og_folded_kernel(
    x_ref, w_ref, b_ref, s_ref, o_ref, *, seg: Segregation, b: int,
    s: int, ks: int, ct: int, cl: int,
    bi: int, n_slab: int, iw: int, ow: int, ow_p: int, boc: int,
    delta: int, acc_dtype, out_dtype, activation: str, out_scale,
    per_channel: bool,
):
    """Batch-folded cell: every gathered product's M carries all B elements.

    Folding only grows the M-dimension of each residue MatMul; every
    output element's K-reduction vector is unchanged, so folded and
    unfolded execution are bit-identical by construction (plan v2
    contract).
    """
    j = pl.program_id(1)
    slab = x_ref[:, pl.dslice(j * bi, n_slab)]  # (B, n_slab, iw, ic)
    outs = _og_accumulate(slab, seg, w_ref, b_fold=b, s=s, bi=bi, iw=iw,
                          ow_p=ow_p, boc=boc, delta=delta,
                          acc_dtype=acc_dtype)
    for e in range(b):
        o_ref[e] = ppu_epilogue(
            outs[e], b_ref[...], s_ref[...], acc_dtype=acc_dtype,
            activation=activation, out_scale=out_scale,
            per_channel=per_channel, out_dtype=out_dtype)


def _pack_og_weights(p: MM2IMPrep, seg: Segregation) -> jax.Array:
    """Tap-major packed weights: (Ic, Ks², Oc_p) -> (Ks², Ic, Oc_p).

    The KS permutation groups each sub-kernel's taps contiguously; the
    transpose makes the tap axis leading so the kernel's static slice
    ``w[offset : offset + taps]`` reshapes to a ``(taps·Ic, boc)`` operand
    whose K order (tap-major, ic-minor) matches the gathered input's.
    """
    w_ks = jnp.take(p.w3, jnp.asarray(seg.permutation()), axis=1)
    return jnp.transpose(w_ks, (1, 0, 2))


def mm2im_og_tconv(
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
    """Output-gathered transposed convolution (same contract as
    ``mm2im_tconv`` — drop-in fourth family behind the registry).

    Args match ``mm2im_pallas.mm2im_tconv``; see the module docstring for
    the dataflow difference.  ``fold_batch=True`` folds the batch into
    every gathered product's M-dimension (plan schema v2).
    """
    p = prepare_mm2im(
        x, w, bias, stride=stride, padding=padding, block_oh=block_oh,
        block_oc=block_oc, activation=activation, out_scale=out_scale,
        out_dtype=out_dtype, grid_order=grid_order, interpret=interpret,
        fold_batch=fold_batch)
    seg = segregate(p.ks, p.s, padding)
    w_og = _pack_og_weights(p, seg)

    kw = dict(p.kernel_kwargs(), seg=seg)
    if p.fold_batch:
        kernel = functools.partial(_mm2im_og_folded_kernel, b=p.b, **kw)
        grid = (p.n_c, p.n_j)
        in_specs = [
            pl.BlockSpec((p.b, p.ihp, p.iw, p.ic), lambda c, j: (0, 0, 0, 0)),
            pl.BlockSpec((p.ks * p.ks, p.ic, p.boc), lambda c, j: (0, 0, c)),
            pl.BlockSpec((1, p.boc), lambda c, j: (0, c)),
            pl.BlockSpec((1, p.boc), lambda c, j: (0, c)),
        ]
        out_specs = pl.BlockSpec((p.b, p.block_oh, p.ow_p, p.boc),
                                 lambda c, j: (0, j, 0, c))
        n_parallel = 1
    else:
        kernel = functools.partial(_mm2im_og_kernel, **kw)
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
            pl.BlockSpec((p.ks * p.ks, p.ic, p.boc), iw_),
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
    )(p.x_p, w_og, p.bias_p, p.scales_p)

    return out[:, :p.oh, :p.ow, :p.oc]
