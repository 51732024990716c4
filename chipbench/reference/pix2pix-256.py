"""Plain reference of the pix2pix U-Net-256 generator (Isola et al.,
arXiv:1611.07004), as the program serves it.

    encoder: 8 x [4x4 stride-2 conv, no bias]; BN on all but the first;
             the pre-activation outputs are the skips; LeakyReLU(0.2)
    decoder: ReLU(bottleneck), then 8 x [4x4 stride-2 TCONV + bias]; BN,
             ReLU and concatenation with the mirrored skip after all but
             the last, whose output goes through tanh.

Departures from the paper's generator, in the program and here alike:
no decoder dropout; batch norm on the innermost encoder layer; the skip
half of each decoder input is not passed through the ReLU; every
decoder layer has a bias.  Batch statistics at test time, as the paper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import plain


def _enc_chans(cfg):
    b = cfg["base"]
    return [min(b * 2 ** i, b * 8) for i in range(cfg["depth"])]


def _dec(cfg):
    """(ic, oc) of each decoder layer."""
    enc, d = _enc_chans(cfg), cfg["depth"]
    out = []
    for i in range(d):
        ic = enc[d - 1 - i] * (1 if i == 0 else 2)
        oc = enc[d - 2 - i] if i < d - 1 else cfg["out_ch"]
        out.append((ic, oc))
    return out


def param_shapes(cfg) -> dict:
    """The program's layout: encoder ``e0..`` HWIO, decoder ``d0..`` HWOI,
    decoder biases ``db0..``."""
    ks = cfg["kernel_size"]
    shapes, cin = {}, cfg["in_ch"]
    for i, c in enumerate(_enc_chans(cfg)):
        shapes[f"e{i}"] = (ks, ks, cin, c)
        cin = c
    for i, (ic, oc) in enumerate(_dec(cfg)):
        shapes[f"d{i}"] = (ks, ks, oc, ic)
        shapes[f"db{i}"] = (oc,)
    return shapes


def layers(cfg) -> list:
    ks, s, d = cfg["kernel_size"], cfg["stride"], cfg["depth"]
    hw, cin, out = cfg["image_size"], cfg["in_ch"], []
    for i, c in enumerate(_enc_chans(cfg)):
        out.append({"name": f"e{i}", "kind": "conv", "ih": hw, "iw": hw,
                    "ic": cin, "ks": ks, "oc": c, "stride": s})
        hw //= s
        cin = c
    for i, (ic, oc) in enumerate(_dec(cfg)):
        out.append({"name": f"d{i}", "kind": "tconv", "ih": hw, "iw": hw,
                    "ic": ic, "ks": ks, "oc": oc, "stride": s})
        hw *= s
    return out


def input_shape(cfg) -> tuple:
    return (cfg["image_size"], cfg["image_size"], cfg["in_ch"])


def make_inputs(key, n: int, cfg):
    """Images scaled to [-1, 1], as pix2pix normalizes them."""
    return jax.random.uniform(key, (n,) + input_shape(cfg), jnp.float32,
                              -1.0, 1.0)


def forward(params, img, cfg, prec, tconv):
    d, s = cfg["depth"], cfg["stride"]
    skips, x = [], img
    for i in range(d):
        x = plain.conv(x, params[f"e{i}"], s, prec)
        if i > 0:
            x = plain.batchnorm(x)
        skips.append(x)
        x = plain.leaky_relu(x, 0.2)
    x = jax.nn.relu(skips[-1])
    for i in range(d):
        last = i == d - 1
        x = tconv(f"d{i}", x, params[f"d{i}"], params[f"db{i}"], s,
                  "tanh" if last else "none")
        if not last:
            x = plain.batchnorm(x)
            x = jnp.concatenate([jax.nn.relu(x), skips[d - 2 - i]], -1)
    return x
