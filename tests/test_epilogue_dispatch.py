"""Epilogue-typed unified dispatch: the ISSUE-4 acceptance surface.

Covers the one-pipeline contract of ``kernels/ops.py``:

* the gold itself — 'lax' f32 equals the hand-applied oracle epilogue,
  and the int8 'lax' fallback equals the hand-written PPU reference (the
  cross-method parity matrix that used to live here moved to
  ``tests/test_parity_matrix.py`` / ``tests/parity.py``, which enrolls
  every registered method automatically);
* the dequant -> compute -> requant fallback that makes every method
  (including unregistered-yesterday baselines and third-party plugins)
  quantization-capable with zero wiring;
* ``tconv_int8`` bit-identity with the direct Pallas kernel invocation
  (the pre-refactor implementation) for the committed ``cpu.json`` plan
  keys;
* the shared jit'd dispatcher's static-argname discipline (repeated
  ``tconv_int8`` calls on one shape compile exactly once — the op used to
  retrace the Pallas kernel from Python on every call);
* the :class:`~repro.core.epilogue.Epilogue` value type itself: stage
  split (prefix rule, requant tail rule), the promoted activation table
  and the single leaky-relu slope constant;
* ``autotune.KERNEL_RUNNERS`` is gone — int8 measurement and variant
  upgrade go through the registry only.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import epilogue as epi
from repro.core.epilogue import Epilogue
from repro.kernels import ref, registry
from repro.kernels.ops import (dispatch_trace_count, run_registered, tconv,
                               tconv_int8)
from repro.kernels.registry import Plan

RNG = np.random.default_rng(21)

ACTS = ("none", "relu", "tanh", "leaky_relu")

# One small problem for the whole matrix: Ic*Ks^2 * 127^2 ~ 0.6M << 2^24,
# so the f32 fallback accumulation of int8 products is exact and the int8
# column can assert bitwise equality across methods.
IH, IW, IC, KS, OC, S = 5, 5, 4, 3, 4, 2


def _f32_operands():
    x = RNG.standard_normal((1, IH, IW, IC)).astype(np.float32)
    w = (RNG.standard_normal((KS, KS, OC, IC)) * 0.1).astype(np.float32)
    b = RNG.standard_normal(OC).astype(np.float32)
    return x, w, b


def _int8_operands():
    x = RNG.integers(-128, 128, (1, IH, IW, IC)).astype(np.int8)
    w = RNG.integers(-128, 128, (KS, KS, OC, IC)).astype(np.int8)
    b = RNG.integers(-500, 500, OC).astype(np.int32)
    return x, w, b


# ---------------------------------------------------------------------------
# The gold itself (cross-method parity lives in test_parity_matrix.py)
# ---------------------------------------------------------------------------


def test_f32_gold_is_really_lax():
    """The 'lax' column itself equals the hand-applied oracle epilogue."""
    x, w, b = _f32_operands()
    for act in ACTS:
        got = np.asarray(tconv(x, w, b, stride=S, method="lax",
                               activation=act))
        want = np.asarray(epi.ACTIVATIONS[act](
            jnp.asarray(ref.tconv_lax(x, w, stride=S)) + b))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=act)


def test_int8_gold_matches_manual_ppu():
    """The int8 'lax' fallback equals the hand-written PPU reference:
    int32 accum -> bias -> requant round/clip -> activation -> int8."""
    xq, wq, bq = _int8_operands()
    scale = 0.004
    acc = np.asarray(ref.iom_reference_int8(xq, wq, bq, stride=S))
    for act in ACTS:
        want = np.clip(np.round(acc.astype(np.float32) * scale), -128, 127)
        want = np.asarray(epi.ACTIVATIONS[act](want))
        want = np.round(want).astype(np.int8)
        got = np.asarray(tconv_int8(xq, wq, bq, scale, stride=S, method="lax",
                                    activation=act))
        assert (got == want).all(), act


def test_int8_fallback_per_channel():
    """Per-channel requant also rides the fallback (traced scales)."""
    xq, wq, bq = _int8_operands()
    scales = RNG.uniform(1e-3, 6e-3, OC).astype(np.float32)
    got = np.asarray(tconv_int8(xq, wq, bq, scales, stride=S,
                                method="zero_insertion"))
    want = np.asarray(tconv_int8(xq, wq, bq, scales, stride=S,
                                 method="mm2im"))
    assert got.dtype == np.int8
    assert (got == want).all()


def test_third_party_variant_is_int8_capable_with_zero_wiring():
    """A plugin registered without supports_int8 serves tconv_int8 via the
    fallback, and measure_plan times it through the registry — no runner
    table, no extra wiring anywhere."""
    from repro.core.autotune import measure_plan
    from repro.core.maps import TConvProblem

    @registry.register("direct_plugin", supports_plan=True,
                       description="ref.tconv_direct as a plugin")
    def _direct(x, w, *, stride, padding, epilogue, plan):
        return ref.tconv_direct(x, w, stride=stride, padding=padding)

    try:
        xq, wq, bq = _int8_operands()
        got = np.asarray(tconv_int8(xq, wq, bq, 0.004, stride=S,
                                    method="direct_plugin"))
        want = np.asarray(tconv_int8(xq, wq, bq, 0.004, stride=S))
        assert got.dtype == np.int8 and (got == want).all()
        # Autotunable in both precisions straight off the registry.
        p = TConvProblem(IH, IW, IC, KS, OC, S)
        for dtype in (jnp.float32, jnp.int8):
            us = measure_plan(p, Plan(S, OC, "bcj", "direct_plugin"),
                              dtype=dtype, repeats=1, warmup=1)
            assert us > 0
    finally:
        assert registry.unregister("direct_plugin") is not None


# ---------------------------------------------------------------------------
# tconv_int8 bit-identity with the direct kernel (pre-refactor path)
# ---------------------------------------------------------------------------

_KEY_RE = re.compile(
    r"tconv:ih(\d+):iw(\d+):ic(\d+):ks(\d+):oc(\d+):s(\d+):(\w+)\|int8\|")


def test_tconv_int8_bit_identical_for_shipped_plan_keys():
    """For committed cpu.json int8 plan keys, the unified dispatcher's
    output is bit-identical to invoking the Pallas kernel directly with
    the plan's geometry — the pre-refactor ``tconv_int8`` implementation.
    """
    from repro.core import plan_table
    from repro.kernels.mm2im_db_pallas import mm2im_db_tconv
    from repro.kernels.mm2im_ks_pallas import mm2im_ks_tconv
    from repro.kernels.mm2im_pallas import mm2im_tconv

    table = plan_table.load_table("cpu", strict=True)
    keys = [k for k in table.keys() if "|int8|" in k and "|b1" in k]
    assert keys, "committed cpu.json lost its int8 coverage"
    checked = 0
    for key in keys:
        m = _KEY_RE.match(key)
        assert m, key
        ih, iw, ic, ks, oc, s = (int(g) for g in m.groups()[:6])
        padding = m.group(7)
        if ih * iw * ic > 7 * 9 * 64 or checked >= 3:
            continue  # keep the interpret-mode cost bounded
        plan = table.get(key)
        import zlib
        rng = np.random.default_rng(zlib.crc32(key.encode()))
        xq = rng.integers(-128, 128, (1, ih, iw, ic)).astype(np.int8)
        wq = rng.integers(-128, 128, (ks, ks, oc, ic)).astype(np.int8)
        bq = rng.integers(-500, 500, oc).astype(np.int32)
        got = np.asarray(tconv_int8(xq, wq, bq, 0.003, stride=s,
                                    padding=padding, plan=plan))
        kernel = {"mm2im": mm2im_tconv,
                  "mm2im_db": mm2im_db_tconv,
                  "mm2im_ks": mm2im_ks_tconv}[plan.method or "mm2im"]
        want = np.asarray(kernel(
            jnp.asarray(xq), jnp.asarray(wq), jnp.asarray(bq), stride=s,
            padding=padding, out_scale=0.003, block_oh=plan.block_oh,
            block_oc=plan.block_oc, grid_order=plan.grid_order))
        assert (got == want).all() and got.dtype == want.dtype, key
        checked += 1
    assert checked >= 2, "shipped table had no small int8 keys to check"


# ---------------------------------------------------------------------------
# jit / retrace discipline
# ---------------------------------------------------------------------------


def test_tconv_int8_compiles_once_per_shape():
    """Repeated tconv_int8 calls on one (shape, scale, static-args) key
    must not retrace the Pallas kernel (regression: the old entry point
    was plain Python and re-staged every call)."""
    # Unique shapes so earlier tests' jit entries cannot mask a retrace.
    xq = RNG.integers(-128, 128, (1, 3, 7, 2)).astype(np.int8)
    wq = RNG.integers(-128, 128, (3, 3, 5, 2)).astype(np.int8)
    bq = RNG.integers(-100, 100, 5).astype(np.int32)
    c0 = dispatch_trace_count()
    first = np.asarray(tconv_int8(xq, wq, bq, 0.02, stride=2))
    c1 = dispatch_trace_count()
    assert c1 == c0 + 1, "first call must trace exactly once"
    for _ in range(3):
        again = np.asarray(tconv_int8(xq, wq, bq, 0.02, stride=2))
        assert (again == first).all()
    assert dispatch_trace_count() == c1, "steady-state calls retraced"
    # A different per-tensor scale is a *static* epilogue knob -> retrace.
    tconv_int8(xq, wq, bq, 0.03, stride=2)
    assert dispatch_trace_count() == c1 + 1
    # tconv shares the same dispatcher and the same discipline.
    x = RNG.standard_normal((1, 3, 7, 2)).astype(np.float32)
    w = (RNG.standard_normal((3, 3, 5, 2)) * 0.1).astype(np.float32)
    c2 = dispatch_trace_count()
    tconv(x, w, stride=2)
    tconv(x, w, stride=2)
    assert dispatch_trace_count() == c2 + 1


# ---------------------------------------------------------------------------
# Epilogue value type
# ---------------------------------------------------------------------------


def test_epilogue_split_prefix_rule():
    b = np.ones(4, np.float32)
    ep = Epilogue(bias=b, activation="relu")
    # Fusing only the activation may not reorder it before the bias add.
    k, r = ep.split(frozenset({"activation"}))
    assert k.is_noop and r.activation == "relu" and r.bias is not None
    # Fusing the bias keeps it in-kernel, activation goes to the remainder.
    k, r = ep.split(frozenset({"bias"}))
    assert k.bias is not None and k.activation == "none"
    assert r.bias is None and r.activation == "relu"
    # Full fusion: nothing remains.
    k, r = ep.split(frozenset({"bias", "activation"}))
    assert (k.bias is not None and k.activation == "relu" and r.is_noop)


def test_epilogue_split_requant_tail_rule():
    """Requant only fuses when the whole remaining tail does: an in-kernel
    int8 cast ahead of a dispatcher-side activation would quantize too
    early."""
    ep = Epilogue(bias=np.ones(4, np.int32), activation="relu",
                  out_scale=0.05, out_dtype=jnp.int8)
    k, r = ep.split(frozenset({"bias", "requant"}))  # activation unfused
    assert k.out_scale is None, "requant fused ahead of an unfused stage"
    assert r.out_scale == 0.05 and r.activation == "relu"
    assert r.out_dtype == jnp.dtype(jnp.int8) and k.out_dtype is None
    k, r = ep.split(frozenset({"bias", "requant", "activation"}))
    assert k.out_scale == 0.05 and r.is_noop
    assert k.out_dtype == jnp.dtype(jnp.int8)


def test_epilogue_resolved_out_dtype():
    assert Epilogue().resolved_out_dtype(integer=False) is None
    assert Epilogue().resolved_out_dtype(integer=True) == jnp.int32
    assert Epilogue(out_scale=0.1).resolved_out_dtype(integer=True) == jnp.int8
    assert (Epilogue(out_dtype=jnp.bfloat16).resolved_out_dtype(True)
            == jnp.bfloat16)


def test_epilogue_is_jit_static_aware_pytree():
    """Arrays are traced leaves; activation/scalar scale/dtype are treedef."""
    b = jnp.ones(4)
    leaves, treedef = jax.tree_util.tree_flatten(
        Epilogue(bias=b, activation="relu", out_scale=0.5))
    assert len(leaves) == 1 and leaves[0] is b
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.activation == "relu" and rebuilt.out_scale == 0.5
    # Per-channel scales are leaves (traced), not treedef (static).
    scales = jnp.ones(4)
    leaves, _ = jax.tree_util.tree_flatten(Epilogue(out_scale=scales))
    assert any(leaf is scales for leaf in leaves)
    with pytest.raises(ValueError, match="activation"):
        Epilogue(activation="sigmoid?")


def test_leaky_relu_slope_single_constant():
    """Forward table and custom_vjp backward share the one slope constant
    (it used to be hardcoded 0.2 in two places)."""
    x = jnp.asarray([-2.0, 3.0])
    np.testing.assert_allclose(
        np.asarray(epi.ACTIVATIONS["leaky_relu"](x)),
        [-2.0 * epi.LEAKY_RELU_SLOPE, 3.0])
    g = np.asarray(epi.activation_grad_from_output(
        "leaky_relu", x, jnp.ones_like(x)))
    np.testing.assert_allclose(g, [epi.LEAKY_RELU_SLOPE, 1.0])
    # The kernel module's table *is* the shared one (promotion, not copy).
    from repro.kernels import mm2im_pallas
    assert mm2im_pallas._ACTIVATIONS is epi.ACTIVATIONS
    # And the end-to-end gradient uses the same slope.
    x1 = RNG.standard_normal((1, 4, 4, 2)).astype(np.float32)
    w1 = (RNG.standard_normal((3, 3, 2, 2)) * 0.1).astype(np.float32)
    dx = jax.grad(lambda xx: jnp.sum(
        tconv(xx, w1, stride=2, activation="leaky_relu")))(x1)
    out = tconv(x1, w1, stride=2)
    want = np.asarray(jax.grad(lambda xx: jnp.sum(
        ref.tconv_direct(xx, w1, stride=2)
        * jnp.where(ref.tconv_direct(x1, w1, stride=2) >= 0, 1.0,
                    epi.LEAKY_RELU_SLOPE)))(x1))
    np.testing.assert_allclose(np.asarray(dx), want, rtol=1e-3, atol=1e-3)
    del out


# ---------------------------------------------------------------------------
# KERNEL_RUNNERS is gone; run_registered is the measurement surface
# ---------------------------------------------------------------------------


def test_kernel_runners_table_removed():
    from repro.core import autotune

    assert not hasattr(autotune, "KERNEL_RUNNERS")


def test_run_registered_matches_dispatch():
    """run_registered (the autotuner's measurement entry) computes the
    same function dispatch serves, in both precisions."""
    x, w, b = _f32_operands()
    ep = Epilogue(bias=jnp.asarray(b), activation="relu")
    got = np.asarray(run_registered("mm2im", x, w, stride=S, padding="SAME",
                                    epilogue=ep, plan=Plan(S, OC)))
    want = np.asarray(tconv(x, w, b, stride=S, activation="relu",
                            plan=Plan(S, OC)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    xq, wq, bq = _int8_operands()
    ep8 = Epilogue(bias=jnp.asarray(bq), out_scale=0.004)
    got = np.asarray(run_registered("tdc", xq, wq, stride=S, padding="SAME",
                                    epilogue=ep8))
    want = np.asarray(tconv_int8(xq, wq, bq, 0.004, stride=S, method="tdc"))
    assert (got == want).all()


@pytest.mark.parametrize("i", [4, 8])
def test_valid_3x3_stride2_dispatch_matches_lax_at_odd_outputs(i):
    """StyleGAN2's up-convolution: VALID, 3x3, stride 2, through the
    default dispatch (the MM2IM kernel) gives ``2i+1`` rows and columns,
    equal to 'lax'."""
    x = RNG.standard_normal((2, i, i, 16)).astype(np.float32)
    w = RNG.standard_normal((3, 3, 8, 16)).astype(np.float32)
    got = np.asarray(tconv(x, w, stride=2, padding="VALID"))
    want = np.asarray(tconv(x, w, stride=2, padding="VALID", method="lax"))
    assert got.shape == (2, 2 * i + 1, 2 * i + 1, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
