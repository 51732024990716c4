"""Double-buffered MM2IM — the pipelined-DMA variant of the fused kernel.

The single-buffered kernel (``mm2im_pallas.py``) keeps the *whole* padded
input resident in VMEM and lets Pallas's automatic pipelining stage the
weight/output blocks.  That leaves two things on the table (docs/DESIGN.md
§2.4):

* the initial whole-input copy is serial — compute cannot start until the
  full ``(Ihp, Iw, Ic)`` slab landed in VMEM (the paper's SECDA profiling
  shows exactly this data-in stall, and its MM2IM engine pipelines
  ``SendInputRows`` against the MACs to hide it);
* VMEM must hold the whole input, which caps the legal block space for
  large images.

This variant restores the paper's pipeline on TPU: the input stays in HBM
(``ANY`` memory space) and the per-row-block input slab is DMA'd into a
**two-slot VMEM scratch** while the MatMul + col2im of the *previous* block
runs — classic double buffering (``pltpu.make_async_copy`` + DMA
semaphores).  Output row-blocks leave through a mirrored two-slot scratch,
so the HBM write of block ``j-1`` overlaps the compute of block ``j`` too.
The row-block loop that the single-buffered kernel expresses as the inner
grid dimension becomes an in-kernel ``fori_loop``.

Numerics: host staging, the MXU MatMul, the col2im residue adds and the
PPU epilogue are *shared code* with the single-buffered kernel
(``prepare_mm2im`` / ``stage_slabs`` / ``mm2im_block`` /
``residue_outputs``), so both variants are **bit-identical** — the autotuner
(``core/autotune.py``) is free to pick per problem on speed alone.

Interpret-mode note: the async-copy/semaphore path itself runs under
``interpret=True`` (Pallas simulates the DMAs), and a plain synchronous
copy fallback is kept behind ``pipeline='sync'`` (or
``REPRO_MM2IM_DB_SYNC=1``) for environments whose interpreter lacks
semaphore support.  Both paths execute the same shared block math.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mm2im_pallas import (
    Col2im,
    grid_semantics,
    mm2im_block,
    prepare_mm2im,
    residue_outputs,
    slab_out_shape,
    stage_slabs,
    unstage_output,
)

_N_SLOTS = 2  # two-slot scratch: fill slot A while computing from slot B


def _mm2im_db_kernel(
    x_hbm_ref, w_ref, b_ref, s_ref, o_hbm_ref,   # operands (x/o in ANY/HBM)
    slab_ref, outb_ref, *sems,                   # two-slot scratch (+ sems)
    batch_axis: int, n_j: int, async_copies: bool, sched: Col2im, boc: int,
    **epilogue,
):
    """One grid cell: ALL row blocks of one (batch, oc-block) pair.

    Pipeline (async path), steady state at block ``j``:

        in-DMA  slab[j+1]  ──start──┐                 (hides SendInputRows)
        in-DMA  slab[j]    ──wait───┤
        out-DMA out[j-2]   ──wait───┤  (slot j%2 free)
        MXU+VPU block j    ─────────┤  MatMul + col2im + PPU epilogue
        out-DMA out[j]     ──start──┘                 (hides the HBM write)

    The sync fallback replaces the four DMA arrows with direct VMEM
    reads/writes of the same slices — identical block math either way.
    Folded, the batch axis has one element and every slab carries the
    whole batch (``stage_slabs``).
    """
    bsel = pl.program_id(batch_axis)
    csel = pl.program_id(1 - batch_axis)
    rows = sched.n_rows * sched.pitch
    lanes = sched.s * boc
    if async_copies:
        in_sem, out_sem = sems

    def in_dma(slot, j):
        return pltpu.make_async_copy(
            x_hbm_ref.at[bsel, pl.ds(j * sched.block_rows, rows)],
            slab_ref.at[slot], in_sem.at[slot])

    def out_dma(slot, j):
        return pltpu.make_async_copy(
            outb_ref.at[slot],
            o_hbm_ref.at[bsel, pl.ds(j * sched.bi, sched.bi), :, :,
                         pl.ds(csel * lanes, lanes)],
            out_sem.at[slot])

    if async_copies:
        in_dma(0, 0).start()  # pipeline warm-up: first slab in flight

    def body(j, _):
        slot = jax.lax.rem(j, _N_SLOTS)
        if async_copies:
            @pl.when(j + 1 < n_j)
            def _prefetch():
                in_dma(jax.lax.rem(j + 1, _N_SLOTS), j + 1).start()
            in_dma(slot, j).wait()
            # Slot j%2 last carried block j-2; its out-DMA must land before
            # the epilogue below overwrites the scratch.
            @pl.when(j >= _N_SLOTS)
            def _retire():
                out_dma(slot, j - _N_SLOTS).wait()
        else:
            slab_ref[slot] = x_hbm_ref[bsel,
                                       pl.ds(j * sched.block_rows, rows)]

        acc = mm2im_block(slab_ref.at[slot], 0, w_ref, sched,
                          acc_dtype=epilogue["acc_dtype"])
        for a, b, out in residue_outputs(acc, sched, b_ref[...], s_ref[...],
                                         boc=boc, **epilogue):
            if async_copies:
                outb_ref[slot, :, a, :, pl.ds(b * boc, boc)] = out
            else:
                o_hbm_ref[bsel, pl.ds(j * sched.bi, sched.bi), a, :,
                          pl.ds(csel * lanes + b * boc, boc)] = out
        if async_copies:
            out_dma(slot, j).start()
        return 0

    jax.lax.fori_loop(0, n_j, body, 0)

    if async_copies:
        # Drain: the last one or two output DMAs are still in flight.
        if n_j >= _N_SLOTS:
            out_dma((n_j - 2) % _N_SLOTS, n_j - 2).wait()
        out_dma((n_j - 1) % _N_SLOTS, n_j - 1).wait()


def mm2im_db_tconv(
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
    pipeline: str = "auto",
    fold_batch: bool = False,
) -> jax.Array:
    """Double-buffered MM2IM transposed convolution.

    Same contract as ``mm2im_pallas.mm2im_tconv`` (same dtypes, epilogue
    fusions and plan knobs incl. ``fold_batch``), bit-identical outputs.
    ``pipeline`` selects the slab-copy mechanism: ``'async'`` (pltpu async
    copy + semaphores), ``'sync'`` (direct VMEM copies — the
    interpret-safe fallback), or ``'auto'`` (async unless
    ``REPRO_MM2IM_DB_SYNC=1``).  With ``fold_batch=True`` the two-slot
    pipeline fetches batch-folded slabs and the batch grid axis is 1.
    """
    p = prepare_mm2im(
        x, w, bias, stride=stride, padding=padding, block_oh=block_oh,
        block_oc=block_oc, activation=activation, out_scale=out_scale,
        out_dtype=out_dtype, grid_order=grid_order, interpret=interpret,
        fold_batch=fold_batch)

    if pipeline == "auto":
        pipeline = ("sync" if os.environ.get("REPRO_MM2IM_DB_SYNC", "")
                    .lower() in ("1", "true", "yes", "on") else "async")
    if pipeline not in ("async", "sync"):
        raise ValueError(
            f"pipeline must be 'auto'|'async'|'sync', got {pipeline!r}")
    async_copies = pipeline == "async"
    x_flat, w_taps, sched = stage_slabs(p)

    # j (the row-block sweep) is pipelined inside the kernel, so the grid is
    # only the outer pair of the Alg. 1 loop nest (bcj/cbj collapse when
    # the batch is folded into the slab).
    n_b = x_flat.shape[0]
    if p.grid_order == "bcj" or p.fold_batch:
        grid, batch_axis = (n_b, p.n_c), 0
    else:  # "cbj"
        grid, batch_axis = (p.n_c, n_b), 1
    iw_ = lambda *ids: (0, 0, ids[1 - batch_axis])
    ib = lambda *ids: (0, ids[1 - batch_axis])
    kernel = functools.partial(
        _mm2im_db_kernel, batch_axis=batch_axis, n_j=p.n_j,
        async_copies=async_copies, sched=sched, boc=p.boc,
        **p.epilogue_kwargs())
    scratch = [
        pltpu.VMEM((_N_SLOTS, sched.n_rows * sched.pitch, x_flat.shape[2]),
                   x_flat.dtype),
        pltpu.VMEM((_N_SLOTS, p.bi, p.s, sched.pitch, p.s * p.boc),
                   p.out_dtype),
    ]
    if async_copies:
        scratch += [pltpu.SemaphoreType.DMA((_N_SLOTS,)),
                    pltpu.SemaphoreType.DMA((_N_SLOTS,))]

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            any_space,  # x stays in HBM; slabs are DMA'd per row-block
            pl.BlockSpec(w_taps.shape[:2] + (p.boc,), iw_),
            pl.BlockSpec((1, p.boc), ib),
            pl.BlockSpec((1, p.boc), ib),
        ],
        out_specs=any_space,  # o written per row-block via the out pipeline
        out_shape=slab_out_shape(p, sched),
        scratch_shapes=scratch,
        compiler_params=grid_semantics(len(grid), inner_arbitrary=False),
        interpret=p.interpret,
    )(x_flat, w_taps, p.bias_p, p.scales_p)
    return unstage_output(out, p, sched)
