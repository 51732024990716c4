"""The plain references against the program's GeneratorRunner, on the CPU
at reduced widths, in f32 and int8; a perturbed tap must fail."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, plain

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SMALL = {"dcgan-64": {"base": 64},
         "pix2pix-256": {"base": 4, "depth": 5, "image_size": 32}}
BATCH = 8


class _Cell:
    def __init__(self, config):
        self.cfg = harness.read_json(os.path.join(
            ROOT, "chipbench", "configs", f"{config}.json"))
        self.cfg.update(SMALL[config])
        self.model = harness.load_module(
            os.path.join(ROOT, "chipbench", "reference",
                         f"{self.cfg['reference']}.py"),
            f"ref_test_{config.replace('-', '_')}")


def _setup(config, seed=5):
    cell = _Cell(config)
    key = plain.key_from_seed(seed)
    params = harness.make_params(cell, jax.random.fold_in(key, 0))
    x = cell.model.make_inputs(jax.random.fold_in(key, 1), BATCH, cell.cfg)
    return cell, params, x


def _gaps(cell, params, x, precision, program_params=None):
    from repro.models.runner import make_runner

    runner = make_runner(cell.cfg["runner"],
                         params=params if program_params is None
                         else program_params)
    got = np.asarray(runner.jitted(batch=BATCH, precision=precision)(x))
    prec = plain.Prec()
    if precision == "int8":
        absmax = plain.calibrate(cell.model, params, cell.cfg, prec)
        want = plain.forward_int(cell.model, params, x, cell.cfg, prec,
                                 plain.int_scales(absmax))
        last = [l for l in cell.model.layers(cell.cfg)
                if l["kind"] == "tconv"][-1]["name"]
        lsb = plain.scales_from_absmax(absmax[last])[2]
        if program_params is None:
            # The program's own scales, calibrated by its rule, agree with
            # the reference's.
            q = runner.quant_scales()[last]
            assert q.y_scale == pytest.approx(lsb, rel=1e-6)
    else:
        want = plain.forward_f32(cell.model, params, x, cell.cfg, prec)
        lsb = None
    return float(np.max(np.abs(got - np.asarray(want)))), lsb


@pytest.mark.parametrize("config", ["dcgan-64", "pix2pix-256"])
def test_f32_reference_matches_the_runner(config):
    cell, params, x = _setup(config)
    gap, _ = _gaps(cell, params, x, "f32")
    assert gap < 1e-4
    # One tap of the last-but-one TCONV moved in the program's weights.
    name = [l for l in cell.model.layers(cell.cfg)
            if l["kind"] == "tconv"][-2]["name"]
    bad = dict(params)
    bad[name] = params[name].at[1, 2, 0, 0].add(0.5)
    gap_bad, _ = _gaps(cell, params, x, "f32", program_params=bad)
    assert gap_bad > 100 * max(gap, 1e-6)


@pytest.mark.parametrize("config", ["dcgan-64", "pix2pix-256"])
def test_int8_reference_matches_the_runner(config):
    cell, params, x = _setup(config)
    gap, lsb = _gaps(cell, params, x, "int8")
    # Up to a requant tie broken differently by the float layers between
    # the TCONVs: within one output step.
    assert gap <= lsb
    name = [l for l in cell.model.layers(cell.cfg)
            if l["kind"] == "tconv"][-2]["name"]
    bad = dict(params)
    bad[name] = params[name].at[1, 2, 0, 0].add(0.5)
    gap_bad, _ = _gaps(cell, params, x, "int8", program_params=bad)
    assert gap_bad > 4 * lsb


def test_tconv_raw_is_the_scatter_definition():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 3, 3, 4))
    w = jax.random.normal(jax.random.fold_in(key, 1), (5, 5, 6, 4))
    s, ks = 2, 5
    c = (ks - s) // 2
    full = np.zeros((2, 3 * s + ks, 3 * s + ks, 6))
    xn, wn = np.asarray(x, np.float64), np.asarray(w, np.float64)
    for i in range(3):
        for j in range(3):
            for kh in range(ks):
                for kw in range(ks):
                    full[:, i * s + kh, j * s + kw] += xn[:, i, j] @ wn[kh, kw].T
    want = full[:, c:c + 3 * s, c:c + 3 * s]
    got = np.asarray(plain.tconv_raw(x, w, s))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_int_tconv_requant_rounds_half_to_even():
    # 1x1 input, 1x1 kernel, stride 1: acc = xq * wq exactly.
    x = jnp.full((1, 1, 1, 1), 1.0)
    w = jnp.full((1, 1, 1, 1), 1.0)
    b = jnp.zeros((1,))
    # absmax (1, 1, 2): x_scale = w_scale = 1/127, y_scale = 2/127, so
    # acc = 127 * 127 and the requant multiplier (1/127^2) / (2/127) give
    # 63.5, which rounds half to even to 64, dequantized to 64 * 2/127.
    sc = plain.int_scales({"l": (1.0, 1.0, 2.0)})["l"]
    y = plain.tconv_int(x, w, b, 1, sc, plain.Prec())
    assert float(y[0, 0, 0, 0]) == pytest.approx(64 * 2 / 127)
