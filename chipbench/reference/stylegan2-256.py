"""Plain reference of the StyleGAN2 generator (Karras et al.,
arXiv:1912.04958), config-f, skip architecture, written from the paper's
equations rather than from the program's factored form.

    mapping: z <- z * rsqrt(mean(z^2) + 1e-8); 8 x lrelu(z @ W/sqrt(fan_in) + b)
    synthesis: const 4x4; per layer, per request (eqs. 1-3):
        s   = w @ A/sqrt(fan_in) + A_b
        w'  = s_i * w / sqrt(kh*kw*Cin)
        w'' = w' * rsqrt(sum_{kh, kw, i} w'^2 + 1e-8)     (demodulation)
        y   = conv(x, w'')   or   FIR(tconv_VALID_s2(x, w''))
        y   = lrelu(y + strength * noise + bias)
    toRGB: conv1x1(x, w') + b (no demodulation); img = upfirdn(img) + toRGB

Each request's weights ``w''`` are built and applied by a ``vmap`` over
the batch, so agreement with the program also checks its factoring.  The
up-convolution is a VALID transposed convolution computed here
(``lhs_dilation`` 2, flipped kernel, padding 2), followed by the 4x4 FIR
``outer([1,3,3,1]) / 64 * 4`` as a depthwise convolution padded by 1.

Precision: ``prec.tconv`` for the up-convolutions, ``prec.xla`` for every
other product; ``bfloat16`` holds operands and activations in bf16.  The
``tconv`` callback of ``plain.forward_f32`` (SAME crop) is not used.

Departures from the paper, in the program and here alike: biases, noise
strengths and affine biases are drawn from the seed (the paper starts
them at 0, 0 and 1); the noise buffers are fixed; psi = 1, no mixing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import plain

_DN = ("NHWC", "HWIO", "NHWC")


def _nf(cfg, res):
    return min(2 * cfg["fmap_base"] // res, cfg["fmap_max"])


def _blocks(cfg):
    """(res, cin, cout) of each block from 8 px on."""
    out, c, res = [], _nf(cfg, 4), 8
    while res <= cfg["resolution"]:
        out.append((res, c, _nf(cfg, res)))
        c, res = _nf(cfg, res), 2 * res
    return out


def param_shapes(cfg) -> dict:
    """The program's layout: HWIO convs, HWOI up-convolutions."""
    zd, wd, rgb, ks = cfg["z_dim"], cfg["w_dim"], cfg["channels"], 3
    shapes = {}
    for k in range(cfg["mapping_layers"]):
        shapes[f"map{k}.w"] = (zd if k == 0 else wd, wd)
        shapes[f"map{k}.b"] = (wd,)

    def layer(name, w_shape, cin, cout, res):
        shapes.update({f"{name}.w": w_shape, f"{name}.b": (cout,),
                       f"{name}.aff_w": (wd, cin), f"{name}.aff_b": (cin,)})
        if res:
            shapes[f"{name}.noise"] = (res, res, 1)
            shapes[f"{name}.strength"] = ()

    c = _nf(cfg, 4)
    shapes["const"] = (4, 4, c)
    layer("b4_conv", (ks, ks, c, c), c, c, 4)
    layer("b4_rgb", (1, 1, c, rgb), c, rgb, 0)
    for res, cin, cout in _blocks(cfg):
        layer(f"b{res}_up", (ks, ks, cout, cin), cin, cout, res)
        layer(f"b{res}_conv", (ks, ks, cout, cout), cout, cout, res)
        layer(f"b{res}_rgb", (1, 1, cout, rgb), cout, rgb, 0)
    return shapes


def layers(cfg) -> list:
    """Every product per image: mapping, affine and demodulation as
    ``dense``, 3x3 and 1x1 as ``conv``, the up-convolutions as VALID
    ``tconv``."""
    wd, rgb = cfg["w_dim"], cfg["channels"]
    out = [{"name": f"map{k}", "kind": "dense",
            "ic": cfg["z_dim"] if k == 0 else wd, "oc": wd}
           for k in range(cfg["mapping_layers"])]

    def modulated(name, kind, ih, cin, cout, ks, demod=True, **extra):
        out.append({"name": f"{name}.affine", "kind": "dense", "ic": wd,
                    "oc": cin})
        if demod:
            out.append({"name": f"{name}.demod", "kind": "dense", "ic": cin,
                        "oc": cout})
        out.append({"name": name, "kind": kind, "ih": ih, "iw": ih,
                    "ic": cin, "ks": ks, "oc": cout, **extra})

    c = _nf(cfg, 4)
    modulated("b4_conv", "conv", 4, c, c, 3, stride=1)
    modulated("b4_rgb", "conv", 4, c, rgb, 1, demod=False, stride=1)
    for res, cin, cout in _blocks(cfg):
        modulated(f"b{res}_up", "tconv", res // 2, cin, cout, 3, stride=2,
                  padding="VALID")
        modulated(f"b{res}_conv", "conv", res, cout, cout, 3, stride=1)
        modulated(f"b{res}_rgb", "conv", res, cout, rgb, 1, demod=False,
                  stride=1)
    return out


def input_shape(cfg) -> tuple:
    return (cfg["z_dim"],)


def make_inputs(key, n: int, cfg):
    return jax.random.normal(key, (n,) + input_shape(cfg), jnp.float32)


def _lrelu(x):
    return jnp.where(x >= 0, x, jnp.asarray(0.2, x.dtype) * x) * \
        jnp.asarray(math.sqrt(2.0), x.dtype)


def _dense(x, w, b, prec):
    return plain.dense(x, w / math.sqrt(w.shape[0]), prec) + \
        b.astype(prec.dtype)


def _fir(x, up, pad0, pad1, cfg, prec):
    """``upfirdn``: insert ``up - 1`` zeros after each sample, pad, and
    convolve with the depthwise 4x4 FIR ``outer(taps) / sum^2 * 4``, the
    gain of a 2x upsampling in both of its uses (the skip, and after the
    stride-2 up-convolution)."""
    taps = np.asarray(cfg["fir_taps"], np.float64)
    k = np.outer(taps, taps) / taps.sum() ** 2 * 4
    c = x.shape[-1]
    k = jnp.asarray(np.tile(k[::-1, ::-1, None, None], (1, 1, 1, c)),
                    prec.dtype)
    return lax.conv_general_dilated(
        x.astype(prec.dtype), k, (1, 1),
        [(pad0, pad1 + up - 1)] * 2, lhs_dilation=(up, up),
        dimension_numbers=_DN, feature_group_count=c,
        precision=prec.xla_precision).astype(prec.dtype)


def _tconv_valid(x, w_hwio, prec):
    """Scatter ``y[2i + k] += x[i] * w[k]``: a convolution of the
    2-dilated input with the flipped kernel, padded by ``ks - 1``."""
    if prec.xla == "bfloat16":
        dt, precision = jnp.bfloat16, lax.Precision.DEFAULT
    else:
        dt, precision = jnp.float32, plain._PRECISIONS[prec.tconv]
    ks = w_hwio.shape[0]
    return lax.conv_general_dilated(
        x.astype(dt), w_hwio[::-1, ::-1].astype(dt), (1, 1),
        [(ks - 1, ks - 1)] * 2, lhs_dilation=(2, 2), dimension_numbers=_DN,
        precision=precision).astype(prec.dtype)


def _modulated(params, name, x, w_lat, cfg, prec, *, up=False, demod=True):
    """Modulated conv with per-request weights, one request at a time."""
    w = params[f"{name}.w"].astype(prec.dtype)
    if up:
        w = jnp.transpose(w, (0, 1, 3, 2))        # HWOI -> HWIO
    kh, kw, cin, _ = w.shape
    s = _dense(w_lat, params[f"{name}.aff_w"], params[f"{name}.aff_b"], prec)

    def one(xb, sb):
        ww = sb[None, None, :, None] * w / math.sqrt(kh * kw * cin)
        if demod:
            ww = ww * lax.rsqrt(jnp.sum(ww * ww, axis=(0, 1, 2)) +
                                jnp.asarray(1e-8, ww.dtype))
        if up:
            y = _tconv_valid(xb[None], ww, prec)
            return _fir(y, 1, 1, 1, cfg, prec)[0]
        return plain.conv(xb[None], ww, 1, prec)[0]

    return jax.vmap(one)(x, s)


def forward(params, z, cfg, prec, tconv):
    del tconv  # the up-convolutions are VALID; computed here
    dt = prec.dtype
    z = z.astype(dt)
    x = z * lax.rsqrt(jnp.mean(z * z, axis=1, keepdims=True) +
                      jnp.asarray(1e-8, dt))
    for k in range(cfg["mapping_layers"]):
        x = _lrelu(_dense(x, params[f"map{k}.w"], params[f"map{k}.b"], prec))
    w_lat = x

    def layer(name, x, up=False):
        y = _modulated(params, name, x, w_lat, cfg, prec, up=up)
        y = y + (params[f"{name}.strength"] * params[f"{name}.noise"]
                 ).astype(dt)
        return _lrelu(y + params[f"{name}.b"].astype(dt))

    def to_rgb(name, x):
        return _modulated(params, name, x, w_lat, cfg, prec, demod=False) + \
            params[f"{name}.b"].astype(dt)

    const = params["const"].astype(dt)
    x = jnp.broadcast_to(const, (z.shape[0],) + const.shape)
    x = layer("b4_conv", x)
    img = to_rgb("b4_rgb", x)
    for res, _, _ in _blocks(cfg):
        x = layer(f"b{res}_up", x, up=True)
        x = layer(f"b{res}_conv", x)
        img = _fir(img, 2, 2, 1, cfg, prec) + to_rgb(f"b{res}_rgb", x)
    return img.astype(jnp.float32)
