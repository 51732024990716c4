"""Perf-model validation (§V-F analogue): model vs XLA cost_analysis."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import perf_model
from repro.core.maps import TConvProblem, drop_stats
from repro.kernels import ref
from repro.kernels.baselines import zero_insertion_macs
from repro.kernels.mm2im_pallas import lane_block

PROBLEMS = [TConvProblem(8, 8, 64, 5, 32, 2), TConvProblem(16, 16, 32, 3, 16, 1)]


def _xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0))


@pytest.mark.parametrize("p", PROBLEMS, ids=str)
def test_unfused_iom_flops_within_10pct(p):
    x = jnp.zeros((1, p.ih, p.iw, p.ic), jnp.float32)
    w = jnp.zeros((p.ks, p.ks, p.oc, p.ic), jnp.float32)
    got = _xla_flops(lambda a, b: ref.iom_reference(a, b, stride=p.stride), x, w)
    assert abs(got - 2 * p.macs) / (2 * p.macs) < 0.10


@pytest.mark.parametrize("p", PROBLEMS, ids=str)
def test_zero_insertion_flops_within_tolerance(p):
    """XLA's conv cost model excludes border padding taps; our model uses
    the dense Oh*Ow*Ks^2 count (the paper's convention).  For small images
    the border fraction ~ 2*(Ks-1)/Oh — allow for it explicitly."""
    x = jnp.zeros((1, p.ih, p.iw, p.ic), jnp.float32)
    w = jnp.zeros((p.ks, p.ks, p.oc, p.ic), jnp.float32)
    got = _xla_flops(lambda a, b: ref.tconv_direct(a, b, stride=p.stride), x, w)
    want = 2 * zero_insertion_macs(p.ih, p.iw, p.ic, p.ks, p.oc, p.stride)
    border = 2.0 * (p.ks - 1) / (p.stride * p.ih)
    assert abs(got - want) / want < 0.10 + border


def test_estimates_ordering_sane():
    """Fused MM2IM must never be slower than the unfused IOM baseline."""
    for p in PROBLEMS + [TConvProblem(4, 4, 1024, 5, 512, 2)]:
        t_m = perf_model.mm2im_estimate(p, bits=8).t_overlapped
        t_u = perf_model.iom_unfused_estimate(p, bits=8).t_overlapped
        assert t_m <= t_u * 1.05


def test_mxu_utilization_bounds():
    for p in PROBLEMS:
        e = perf_model.mm2im_estimate(p, bits=8)
        assert 0.0 < e.mxu_utilization <= 1.0
        assert e.effectual_macs == drop_stats(p)["effectual_macs"]


def test_modeled_speedup_positive():
    for p in PROBLEMS:
        assert perf_model.modeled_speedup(p) > 0.5


def test_bottleneck_attributes_fill():
    """Regression: a fill-dominated estimate reports 'fill', not 'memory'.

    t_fill is the non-overlappable slice of t_memory, so the memory term
    competes with its overlappable remainder only — previously the whole
    t_memory won and pipeline-fill problems were misdiagnosed as traffic
    problems."""
    fill_dom = perf_model.Estimate("x", t_compute=1.0, t_memory=3.0,
                                   t_fill=2.5)
    assert fill_dom.bottleneck == "fill"
    mem_dom = perf_model.Estimate("x", t_compute=1.0, t_memory=3.0,
                                  t_fill=0.5)
    assert mem_dom.bottleneck == "memory"
    comp_dom = perf_model.Estimate("x", t_compute=9.0, t_memory=3.0,
                                   t_fill=2.5)
    assert comp_dom.bottleneck == "compute"


def test_int8_without_requant_stores_int32():
    """Regression: int8 WITHOUT a requant epilogue stores the int32
    accumulator (4 bytes/elem), not 1 byte — only the paper's requantizing
    mode narrows the store."""
    p = PROBLEMS[0]
    e_req = perf_model.mm2im_estimate(p, bits=8, requant=True)
    e_raw = perf_model.mm2im_estimate(p, bits=8, requant=False)
    # Padded ow, and O_c padded to the 128-lane block the kernel stores.
    out_elems = (p.oh * (-(-p.ow // p.stride) * p.stride)
                 * lane_block(p.oc, p.oc))
    # Same traffic everywhere except the store width: 3 extra bytes/elem.
    assert e_raw.hbm_bytes - e_req.hbm_bytes == 3 * out_elems
    # Default models the paper's precision (requantizing int8).
    assert perf_model.mm2im_estimate(p, bits=8).hbm_bytes == e_req.hbm_bytes
    # f32 ignores the knob (always a 4-byte store).
    assert (perf_model.mm2im_estimate(p, bits=32, requant=False).hbm_bytes
            == perf_model.mm2im_estimate(p, bits=32).hbm_bytes)


def test_t_compute_is_tile_quantized():
    """t_compute counts whole 128^3 MXU tiles, not raw MACs."""
    p = PROBLEMS[0]
    e = perf_model.mm2im_estimate(p, bits=8)
    mxu = perf_model.V5E.mxu_dim
    assert e.issued_macs % mxu**3 == 0
    # A starved M-dimension issues more tile-MACs than the dense count.
    raw = p.macs
    assert e.issued_macs > raw
    assert 0.0 < e.mxu_utilization <= 1.0


def test_fold_batch_raises_mxu_utilization():
    """Folding a small-spatial batch into M must cut issued tiles (and so
    raise utilization) on the paper's GAN layers; memory traffic does not
    grow."""
    dcgan1 = TConvProblem(4, 4, 1024, 5, 512, 2)
    grid = perf_model.mm2im_estimate(dcgan1, 8, bits=8)
    fold = perf_model.mm2im_estimate(dcgan1, 8, bits=8, fold_batch=True)
    assert fold.issued_macs < grid.issued_macs
    assert fold.mxu_utilization > grid.mxu_utilization
    assert fold.effectual_macs == grid.effectual_macs
    assert fold.t_compute < grid.t_compute
    assert fold.hbm_bytes <= grid.hbm_bytes
    # Same holds for the double-buffered pipeline's estimate.
    gdb = perf_model.mm2im_db_estimate(dcgan1, 8, bits=8)
    fdb = perf_model.mm2im_db_estimate(dcgan1, 8, bits=8, fold_batch=True)
    assert fdb.t_compute < gdb.t_compute


def test_modeled_speedup_threads_the_winning_plan():
    """Regression: modeled_speedup hardcoded heuristic single-buffered
    mm2im vs the baseline, silently ignoring the plan that actually won —
    fold_batch / method / explicit blocks must thread through both sides
    of the ratio."""
    from repro.kernels.registry import Plan

    dcgan1 = TConvProblem(4, 4, 1024, 5, 512, 2)
    base = perf_model.modeled_speedup(dcgan1, 8, bits=8)
    folded = Plan(8, 512, "bcj", "mm2im", fold_batch=True)
    threaded = perf_model.modeled_speedup(dcgan1, 8, bits=8, plan=folded)
    # Folding cuts issued tiles on this shape (test above), so the modeled
    # speedup over the unfused baseline must grow when the plan is folded.
    assert threaded > base
    # The ratio is exactly baseline / plan-threaded estimate.
    t_b = perf_model.iom_unfused_estimate(dcgan1, 8, bits=8).t_overlapped
    t_m = perf_model.mm2im_estimate(
        dcgan1, 8, bits=8, block_oh=8, block_oc=512, grid_order="bcj",
        fold_batch=True).t_overlapped
    assert threaded == pytest.approx(t_b / t_m)
    # method= on the plan selects the double-buffered estimator.
    db = Plan(4, 512, "bcj", "mm2im_db")
    t_db = perf_model.mm2im_db_estimate(
        dcgan1, 8, bits=8, block_oh=4, block_oc=512,
        grid_order="bcj").t_overlapped
    assert perf_model.modeled_speedup(dcgan1, 8, bits=8, plan=db) \
        == pytest.approx(t_b / t_db)
    # baseline_plan threads the other side of the ratio too.
    self_vs_self = perf_model.modeled_speedup(
        dcgan1, 8, bits=8, baseline="mm2im", plan=folded,
        baseline_plan=folded)
    assert self_vs_self == pytest.approx(1.0)


def test_estimate_for_plan_populates_fit_terms():
    """The raw cost terms core/model_fit regresses against must be
    populated and geometry-sensitive for every estimator."""
    from repro.kernels.registry import Plan

    p = PROBLEMS[0]
    e = perf_model.estimate_for_plan(p, 4, plan=Plan(8, 32, "bcj", "mm2im"))
    assert e.n_launches > 0 and e.issued_tiles > 0
    assert e.issued_macs == e.issued_tiles * perf_model.V5E.mxu_dim ** 3
    folded = perf_model.estimate_for_plan(
        p, 4, plan=Plan(8, 32, "bcj", "mm2im", fold_batch=True))
    assert folded.n_launches == e.n_launches // 4
    assert folded.fill_bytes >= e.fill_bytes
    # Baseline estimators fill the terms too (the fit's '*' regime).
    for m in ("iom_unfused", "zero_insertion", "tdc"):
        b = perf_model.estimate_for_plan(p, 2, method=m)
        assert b.n_launches > 0
    # Unknown methods degrade to the single-buffered estimate.
    unk = perf_model.estimate_for_plan(p, 1, method="exotic")
    assert unk.t_overlapped == pytest.approx(
        perf_model.mm2im_estimate(p, 1, bits=8).t_overlapped)


def test_mxu_tiles_quantization():
    mxu = perf_model.V5E.mxu_dim
    assert perf_model.mxu_tiles(1, 1, 1, mxu) == 1
    assert perf_model.mxu_tiles(mxu, mxu, mxu, mxu) == 1
    assert perf_model.mxu_tiles(mxu + 1, mxu, mxu, mxu) == 2
    assert perf_model.mxu_tiles(24, 800, 64, 128) == 1 * 7 * 1
