"""Plain reference of the DCGAN generator (Radford et al., arXiv:1511.06434).

    z (B, z_dim) -> dense -> (B, 4, 4, base) -> BN -> ReLU
    -> 4 x [5x5 stride-2 TCONV (+ bias)] with BN + ReLU between them
    -> tanh -> (B, 64, 64, out_ch)

Batch norms use the batch's own statistics (the served model has no
running averages), so a row's output depends on its batch-mates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import plain


def _widths(cfg):
    base = cfg["base"]
    chans = [base // 2 ** i for i in range(cfg["tconv_layers"])]
    return chans + [cfg["out_ch"]]


def param_shapes(cfg) -> dict:
    """The program's parameter layout: ``proj`` (z_dim, 16*base), TCONV
    weights ``t1..tN`` HWOI, biases ``b1..bN``."""
    ks = cfg["kernel_size"]
    chans = _widths(cfg)
    shapes = {"proj": (cfg["z_dim"], 16 * cfg["base"])}
    for i in range(cfg["tconv_layers"]):
        shapes[f"t{i + 1}"] = (ks, ks, chans[i + 1], chans[i])
        shapes[f"b{i + 1}"] = (chans[i + 1],)
    return shapes


def layers(cfg) -> list:
    """Every product layer, per image, in forward order."""
    ks, s = cfg["kernel_size"], cfg["stride"]
    chans = _widths(cfg)
    out = [{"name": "proj", "kind": "dense", "ic": cfg["z_dim"],
            "oc": 16 * cfg["base"]}]
    hw = 4
    for i in range(cfg["tconv_layers"]):
        out.append({"name": f"t{i + 1}", "kind": "tconv", "ih": hw, "iw": hw,
                    "ic": chans[i], "ks": ks, "oc": chans[i + 1],
                    "stride": s})
        hw *= s
    return out


def input_shape(cfg) -> tuple:
    return (cfg["z_dim"],)


def make_inputs(key, n: int, cfg):
    return jax.random.normal(key, (n,) + input_shape(cfg), jnp.float32)


def forward(params, z, cfg, prec, tconv):
    b = z.shape[0]
    x = plain.dense(z, params["proj"], prec).reshape(b, 4, 4, cfg["base"])
    x = jax.nn.relu(plain.batchnorm(x))
    n = cfg["tconv_layers"]
    for i in range(1, n + 1):
        last = i == n
        x = tconv(f"t{i}", x, params[f"t{i}"], params[f"b{i}"], cfg["stride"],
                  "tanh" if last else "none")
        if not last:
            x = jax.nn.relu(plain.batchnorm(x))
    return x
