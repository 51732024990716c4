"""Operation counts of the benchmark against hand-worked layers."""

import json
import os

import numpy as np
import pytest

from chipbench import counts, harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _layers(config):
    cfg = harness.read_json(os.path.join(ROOT, "chipbench", "configs",
                                         f"{config}.json"))
    model = harness.load_module(
        os.path.join(ROOT, "chipbench", "reference", f"{cfg['reference']}.py"),
        f"ref_{config.replace('-', '_')}")
    return model.layers(cfg)


def test_dcgan1_taps_per_axis_by_hand():
    # Ih=4, Ks=5, S=2, SAME crop 1, output rows 0..7: input row 0 reaches
    # full rows 0..4 (row 0 cropped: 4 taps), rows 1 and 2 all 5, row 3
    # full rows 6..10 of which 6..8 survive (3 taps): 4+5+5+3 = 17.
    assert counts.tconv_taps(4, 5, 2) == 17


def test_conv_taps_by_hand():
    # 256 -> 128 with a 4x4 stride-2 SAME conv: pad 1 before and after;
    # the first and the last output each lose one tap: 4*128 - 2.
    assert counts.conv_taps(256, 4, 2) == 510
    # 2 -> 1: the single output reads input rows -1..2, two of them real.
    assert counts.conv_taps(2, 4, 2) == 2


@pytest.mark.parametrize("i,ks,s", [(4, 5, 2), (8, 5, 2), (1, 4, 2),
                                    (16, 4, 2), (5, 3, 1), (7, 9, 3)])
def test_tconv_taps_match_a_brute_force_scatter(i, ks, s):
    c = (ks - s) // 2
    hits = np.zeros(i * s + ks + s, int)
    for a in range(i):
        for k in range(ks):
            hits[a * s + k] += 1
    assert counts.tconv_taps(i, ks, s) == hits[c:c + i * s].sum()


def test_dcgan_totals():
    layers = _layers("dcgan-64")
    tot = counts.model_counts(layers)
    # Input-oriented: every (input, tap) pair, cropped or not.
    io = sum(l["ih"] * l["iw"] * l["ic"] * l["ks"] ** 2 * l["oc"]
             for l in layers if l["kind"] == "tconv")
    assert io == 638_976_000
    assert tot["tconv"] == 534_703_488      # about 0.53 G per image
    assert tot["other"] == 100 * 16 * 1024  # the dense projection


def test_pix2pix_totals():
    layers = _layers("pix2pix-256")
    tot = counts.model_counts(layers)
    assert tot["tconv"] == 3_799_582_208    # about 3.8 G per image
    assert tot["other"] == 1_900_315_392    # the 8 encoder convs
    # About 91 GFLOP per batch of 8.
    assert 2 * 8 * tot["total"] == pytest.approx(91.2e9, rel=1e-3)
    params = harness.load_module(
        os.path.join(ROOT, "chipbench", "reference", "pix2pix-256.py"),
        "ref_p2p_params").param_shapes(json.load(open(os.path.join(
            ROOT, "chipbench", "configs", "pix2pix-256.json"))))
    assert sum(int(np.prod(s)) for s in params.values()) == 54_406_595


def test_tconv_bytes_and_roofline_time():
    layer = {"kind": "tconv", "ih": 4, "iw": 4, "ic": 1024, "ks": 5,
             "oc": 512, "stride": 2}
    # f32: x 8*4*4*1024*4, w 25*1024*512*4, bias 512*4, y 8*8*8*512*4.
    assert counts.tconv_bytes(layer, 8, "f32") == (
        524_288 + 52_428_800 + 2_048 + 1_048_576)
    # int8: one byte a value, the bias int32.
    assert counts.tconv_bytes(layer, 8, "int8") == (
        131_072 + 13_107_200 + 2_048 + 262_144)
    t = counts.tconv_min_seconds([layer], 8, "int8", 393e12, 819e9)
    ops = 2 * 8 * 17 * 17 * 1024 * 512
    assert t == pytest.approx(max(ops / 393e12,
                                  counts.tconv_bytes(layer, 8, "int8")
                                  / 819e9))
