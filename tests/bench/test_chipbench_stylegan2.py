"""StyleGAN2-256 against its plain reference, on the CPU at a small size
(widths capped at 32, resolution 32, z and w of 32, 2 mapping layers):
the runner agrees in f32, a perturbed tap and a shared style do not, and
each row of a batch is the request served alone."""

import math
import os
import time

import jax
import numpy as np
import pytest

from chipbench import harness, plain

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
NAME = "stylegan2.f32.closed"
SMALL = {"resolution": 32, "fmap_max": 32, "z_dim": 32, "w_dim": 32,
         "mapping_layers": 2}
BATCH = 8


def _cell(**cfg_update):
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    return harness.Cell(bench, NAME, cfg_update=cfg_update,
                        traffic_update={"pool": 32} if cfg_update else None)


@pytest.fixture(scope="module")
def small():
    cell = _cell(**SMALL)
    key = plain.key_from_seed(5)
    params = harness.make_params(cell, jax.random.fold_in(key, 0))
    z = cell.model.make_inputs(jax.random.fold_in(key, 1), BATCH, cell.cfg)
    want = np.asarray(jax.jit(lambda p, x: plain.forward_f32(
        cell.model, p, x, cell.cfg, harness.reference_prec(cell)))(params, z))
    return cell, params, z, want


def _served(params, z, batch=BATCH):
    from repro.models.runner import make_runner

    return np.asarray(make_runner("stylegan2", params=params).jitted(
        batch=batch)(z))


def test_f32_runner_matches_the_reference_and_a_perturbed_tap_does_not(small):
    cell, params, z, want = small
    gap = float(np.max(np.abs(_served(params, z) - want)))
    assert gap < 1e-4
    # One tap of the second-to-last up-convolution, moved in the program's
    # weights only.
    name = [l for l in cell.layers if l["kind"] == "tconv"][-2]["name"]
    bad = dict(params)
    bad[f"{name}.w"] = params[f"{name}.w"].at[1, 2, 0, 0].add(0.5)
    gap_bad = float(np.max(np.abs(_served(bad, z) - want)))
    assert gap_bad > 100 * max(gap, 1e-6)


def test_each_row_of_a_batch_is_that_request_served_alone(small):
    _, params, z, _ = small
    batched = _served(params, z)
    for j in range(BATCH):
        alone = _served(params, z[j:j + 1], batch=1)
        np.testing.assert_allclose(alone[0], batched[j], rtol=1e-5,
                                   atol=1e-5)


def _share_row0_style(monkeypatch):
    """Fault: every row of a batch gets row 0's style in every layer."""
    from repro.models import stylegan2

    styles = stylegan2.styles

    def shared(params, name, w_lat):
        s = styles(params, name, w_lat)
        return jax.numpy.broadcast_to(s[:1], s.shape)

    def hook(server):
        (runner,) = server.runners.values()
        monkeypatch.setattr(stylegan2, "styles", shared)
        runner._jitted.clear()   # retrace the bucket with the fault

    return hook


def test_sound_run_is_correct_and_a_shared_style_is_not(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(harness, "STATE_DIR", tmp_path)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "p.json"))
    cell = _cell(**SMALL)

    def run(seed, hook=None):
        return harness.run_cell(cell, seed, 2.0, False,
                                t_start=time.perf_counter(),
                                require_device=False, compile_cache=False,
                                server_hook=hook)

    result, _ = run(2 ** 32 + 21)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    bad, _ = run(2 ** 32 + 22, hook=_share_row0_style(monkeypatch))
    assert not bad["correct"]
    check = bad["checks"]["rel_rms_err"]
    assert check["value"] > check["limit"]


def test_published_widths_and_the_programs_layer_names():
    """At config-f's widths the reference's VALID layers are the program's
    ``tconv_problems()``, under the same names; the stored parameters are
    about 30 M, and the 3x3 convolutions 32.2 GMAC an image."""
    from repro.models import stylegan2

    cell = _cell()
    shapes = cell.model.param_shapes(cell.cfg)
    probs = stylegan2.tconv_problems(
        {k: jax.ShapeDtypeStruct(v, np.float32) for k, v in shapes.items()})
    tconvs = [l for l in cell.layers if l["kind"] == "tconv"]
    assert [l["name"] for l in tconvs] == list(probs)
    for l in tconvs:
        p = probs[l["name"]]
        assert (p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding) == (
            l["ih"], l["iw"], l["ic"], l["ks"], l["oc"], l["stride"],
            l["padding"])
        assert p.oh == 2 * p.ih + 1
    assert [(p.ih, p.ic, p.oc) for p in probs.values()] == [
        (4, 512, 512), (8, 512, 512), (16, 512, 512), (32, 512, 512),
        (64, 512, 256), (128, 256, 128)]
    n_params = sum(math.prod(s) for s in shapes.values())
    assert 29e6 < n_params < 31e6
    convs = sum(l["ih"] * l["iw"] * l["ks"] ** 2 * l["ic"] * l["oc"]
                for l in cell.layers if l["kind"] == "conv" and l["ks"] == 3)
    assert convs == pytest.approx(32.2e9, rel=2e-3)
