"""GeneratorRunner contract — the ISSUE-8 refactor surface.

All four generator families serve through one runner contract
(``models/runner.py``): policy-driven forwards that match the legacy
entry points, ``tconv_problems()`` that agree with what the forward
actually traces, input geometry helpers, plan resolution precedence, the
int8 policy's closeness to f32, and the generic step builder that
replaced the per-model sample-step plumbing in ``runtime/steps.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.maps import TConvProblem
from repro.kernels import ops
from repro.kernels.registry import Plan
from repro.models import gan
from repro.models.runner import (DEFAULT_METHOD, GeneratorRunner, make_runner,
                                 get_spec, runner_names)

MODELS = ("dcgan", "pix2pix", "fsrcnn", "styletransfer")

# CPU-sized geometry per family (same knobs the serve smoke CLI uses).
TINY = {
    "dcgan": dict(init_kw={"scale_down": 16}),
    "pix2pix": dict(init_kw={"depth": 4, "scale_down": 16}),
    "fsrcnn": dict(init_kw={"d": 8, "s": 4, "m": 1}, input_hw=8),
    "styletransfer": dict(init_kw={"base": 8, "n_res": 1}, input_hw=16),
}


@pytest.fixture(scope="module")
def runners():
    return {name: make_runner(name, key=jax.random.PRNGKey(i), **TINY[name])
            for i, name in enumerate(MODELS)}


def test_registry_covers_all_four_families():
    assert set(runner_names()) >= set(MODELS)
    with pytest.raises(ValueError, match="unknown runner"):
        get_spec("nope")


def test_unknown_option_rejected():
    with pytest.raises(TypeError, match="accepts options"):
        make_runner("fsrcnn", key=jax.random.PRNGKey(0),
                    init_kw=TINY["fsrcnn"]["init_kw"], not_an_option=1)
    with pytest.raises(TypeError, match="accepts options"):
        # dcgan declares no options at all
        make_runner("dcgan", key=jax.random.PRNGKey(0),
                    init_kw=TINY["dcgan"]["init_kw"], input_hw=8)


# ---------------------------------------------------------------------------
# Forward parity: the runner IS the legacy entry point.
# ---------------------------------------------------------------------------


def _legacy_forward(name, params, x):
    if name == "dcgan":
        return gan.dcgan_generator(params, x, method=DEFAULT_METHOD)
    if name == "pix2pix":
        return gan.pix2pix_generator(params, x,
                                     depth=gan.pix2pix_depth(params),
                                     method=DEFAULT_METHOD)
    if name == "fsrcnn":
        return gan.fsrcnn(params, x, method=DEFAULT_METHOD)
    return gan.styletransfer(params, x, method=DEFAULT_METHOD)


@pytest.mark.parametrize("name", MODELS)
def test_runner_matches_legacy_forward(runners, name):
    r = runners[name]
    x = r.example_inputs(batch=1, seed=3)
    got = np.asarray(r.apply(x))
    want = np.asarray(_legacy_forward(name, r.params, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.isfinite(got).all()


# ---------------------------------------------------------------------------
# tconv_problems() agrees with what the forward actually traces.
# ---------------------------------------------------------------------------


class _RecordingPolicy:
    """Logs every named TCONV the forward issues (shape ground truth)."""

    def __init__(self):
        self.layers = {}

    def tconv(self, x, w, bias=None, *, name, stride, padding="SAME",
              activation="none"):
        self.layers[name] = TConvProblem(x.shape[1], x.shape[2], x.shape[3],
                                         w.shape[0], w.shape[2], stride)
        return ops.tconv(x, w, bias, stride=stride, padding=padding,
                         method="lax", activation=activation)


@pytest.mark.parametrize("name", MODELS)
def test_tconv_problems_match_traced_layers(runners, name):
    r = runners[name]
    rec = _RecordingPolicy()
    r.spec.forward(r.params, r.example_inputs(batch=1), r.options, policy=rec)
    assert rec.layers, "forward issued no TCONVs through the policy"
    assert rec.layers == r.tconv_problems()


# ---------------------------------------------------------------------------
# Input geometry.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", MODELS)
def test_input_spec_and_example_inputs(runners, name):
    r = runners[name]
    spec = r.input_spec(batch=3)
    x = r.example_inputs(batch=3, seed=1)
    assert spec.shape == x.shape == (3,) + r.input_shape()
    assert spec.dtype == x.dtype == jnp.float32


# ---------------------------------------------------------------------------
# Plan resolution precedence.
# ---------------------------------------------------------------------------


def test_resolve_plans_explicit_beats_cache(monkeypatch, tmp_path, runners):
    from repro.core import autotune, plan_table

    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "cache.json"))
    monkeypatch.setenv(plan_table.TABLE_DIR_ENV, str(tmp_path / "no_plans"))
    autotune.reset_shared_caches()
    plan_table.reset_shipped_tables()

    r = runners["dcgan"]
    problems = r.tconv_problems()
    name, prob = next(iter(problems.items()))
    cached = Plan(2, 4, "bcj")
    autotune.shared_cache().put(
        autotune.cache_key(prob, dtype=jnp.float32, batch=2), cached)

    assert r.resolve_plans(batch=2) == {name: cached}
    override = Plan(1, 4, "cbj")
    assert r.resolve_plans(batch=2, plans={name: override})[name] == override
    # plan-incapable method: only explicit entries pass through
    r_lax = GeneratorRunner(r.spec, r.params, method="lax")
    assert r_lax.resolve_plans(batch=2) == {}
    assert r_lax.resolve_plans(batch=2, plans={name: override}) == {
        name: override}
    autotune.reset_shared_caches()


# ---------------------------------------------------------------------------
# Int8 policy.
# ---------------------------------------------------------------------------


def test_int8_calibration_and_closeness(runners):
    r = runners["dcgan"]
    scales = r.quant_scales()
    assert set(scales) == set(r.tconv_problems())
    assert all(q.x_scale > 0 and q.w_scale > 0 and q.y_scale > 0
               for q in scales.values())
    assert r.quant_scales() is scales  # memoized

    x = r.example_inputs(batch=2, seed=5)
    f32 = np.asarray(r.apply(x))
    i8 = np.asarray(r.apply(x, precision="int8"))
    assert np.isfinite(i8).all()
    # tanh output in [-1, 1]; static PTQ on a 4-layer net stays close.
    assert np.max(np.abs(f32 - i8)) < 0.25
    with pytest.raises(ValueError, match="precision must be one of"):
        r.apply(x, precision="fp4")


def test_int8_policy_runs_requant_epilogue(runners):
    """The int8 policy quantizes operands and defers the activation to
    after dequant (requant runs BEFORE activation in the Epilogue)."""
    r = runners["dcgan"]
    pol = r.policy(precision="int8")
    name, prob = next(iter(r.tconv_problems().items()))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, prob.ih, prob.iw, prob.ic)).astype(np.float32)
    w = (rng.standard_normal((prob.ks, prob.ks, prob.oc, prob.ic)) * 0.1
         ).astype(np.float32)
    y = np.asarray(pol.tconv(x, w, name=name, stride=prob.stride,
                             activation="relu"))
    assert (y >= 0).all()          # activation applied post-dequant
    q = pol.quant[name]
    # outputs live on the y_scale grid (int8 store, dequantized after)
    np.testing.assert_allclose(y / q.y_scale, np.round(y / q.y_scale),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# jitted() memoization + warm tracking.
# ---------------------------------------------------------------------------


def test_jitted_memoized_and_warm_tracking(runners):
    r = runners["fsrcnn"]
    assert not r.has_compiled(batch=2, precision="f32")
    fn = r.jitted(batch=2)
    assert r.jitted(batch=2) is fn
    out = np.asarray(fn(r.example_inputs(batch=2)))
    assert r.has_compiled(batch=2, precision="f32")
    np.testing.assert_allclose(out, np.asarray(r.apply(
        r.example_inputs(batch=2))), rtol=1e-5, atol=1e-5)
    # lower() gives the bucket's program itself (what chip_smoke.py counts
    # Mosaic kernels in): same result on the same inputs.
    compiled = r.lower(batch=2).compile()
    np.testing.assert_array_equal(
        np.asarray(compiled(r.params, r.example_inputs(batch=2))), out)


# ---------------------------------------------------------------------------
# Step builders (runtime/steps.py rides the runner now).
# ---------------------------------------------------------------------------


def test_make_runner_sample_step_generic(runners):
    from repro.runtime import steps

    r = runners["styletransfer"]
    bundle = steps.make_runner_sample_step(r, batch=2)
    assert bundle.kind == "styletransfer_sample"
    assert bundle.meta["precision"] == "f32"
    assert bundle.meta["method"] == r.method
    out = np.asarray(bundle.fn(r.params, r.example_inputs(batch=2)))
    assert out.shape[0] == 2 and np.isfinite(out).all()


def test_make_gan_sample_step_compat(runners):
    from repro.runtime import steps

    r = runners["dcgan"]
    z_dim = r.input_shape()[0]
    bundle = steps.make_gan_sample_step(r.params, batch=2, z_dim=z_dim)
    assert bundle.kind == "gan_sample"
    with pytest.raises(ValueError, match="z_dim"):
        steps.make_gan_sample_step(r.params, batch=2, z_dim=z_dim + 1)
