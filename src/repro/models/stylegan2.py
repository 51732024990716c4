"""StyleGAN2 generator (Karras et al., arXiv:1912.04958), config-f, skip
architecture, with its up-convolutions on the MM2IM TCONV path.

    z -> normalize -> 8 x [dense + lrelu] -> w          (mapping network)
    const 4x4 -> [modulated 3x3 conv] -> toRGB
    per resolution 8..R: [modulated 3x3 up-conv] -> [modulated 3x3 conv]
                         img = upsample(img) + toRGB(x)

Every product uses its stored weight over ``sqrt(fan_in)`` (equalized
learning rate) and runs at ``Precision.HIGHEST``; the TCONV kernels pin it
for themselves.  ``lrelu(x) = leaky_relu(x, 0.2) * sqrt(2)``.

**The factored modulated convolution** (docs/DESIGN.md §2.8).  The paper
scales each request's weights by its style ``s`` and renormalizes them per
output channel (demodulation, ``d``).  Written as per-request weights, the
batch would need one weight matrix per row, which the MM2IM kernels do not
take.  The same function in its factored form keeps one weight per layer
for the whole batch:

    y = conv(x * s, w / sqrt(fan_in)) * d,
    d_o = rsqrt(sum_{kh, kw, i} (s_i * w[kh, kw, i, o] / sqrt(fan_in))^2 + 1e-8)

so the batch folds into the kernel's M dimension as for any other model
(StyleGAN2-ADA's ``fused_modconv=False``).  An up-convolution is the VALID
stride-2 TCONV (``2i+1`` outputs) through ``policy.tconv`` followed by the
4x4 FIR ``outer([1,3,3,1]) / 64 * 4`` padded by 1, which gives ``2i``;
``d`` commutes with the FIR.  After each conv: ``+ strength * noise``
(fixed per-layer buffers), ``+ bias``, lrelu.  toRGB is a modulated 1x1
conv without demodulation, plus a bias; the image skips between
resolutions through ``upfirdn(up=2, FIR * 4, pad=(2, 1))``.

No batch statistics anywhere: a request's output does not depend on its
batch-mates.  Layout: NHWC, HWIO conv weights, HWOI TCONV weights with the
scatter semantics of ``kernels/ref.py``.

Parameters are one flat dict (the benchmark's reference uses the same
names): ``map{k}.w``/``.b``; ``const``; per layer ``b{r}_conv``,
``b{r}_up`` and ``b{r}_rgb``: ``.w``, ``.b``, ``.aff_w``, ``.aff_b`` and,
for the convolutions, ``.noise`` (r, r, 1) and ``.strength`` ().
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

HIGHEST = lax.Precision.HIGHEST
LRELU_SLOPE = 0.2
LRELU_GAIN = math.sqrt(2.0)
# One axis of the separable FIR, outer([1,3,3,1]) / 64 * 4: each axis
# carries [1,3,3,1] / 8 and half the gain of 4.
FIR_1D = (0.25, 0.75, 0.75, 0.25)
_DN = ("NHWC", "HWIO", "NHWC")


def _nf(res: int, fmap_base: int, fmap_max: int) -> int:
    """Channels at resolution ``res``: ``min(fmap_base / 2^stage,
    fmap_max)`` with ``stage = log2(res) - 1``, as the official code."""
    return min(2 * fmap_base // res, fmap_max)


def init(key, *, resolution: int = 256, z_dim: int = 512, w_dim: int = 512,
         mapping_layers: int = 8, fmap_base: int = 16384,
         fmap_max: int = 512, channels: int = 3):
    """Generator parameters, every one N(0, 1) (stored weights are unit
    normal under the equalized learning rate)."""
    shapes = param_shapes(resolution=resolution, z_dim=z_dim, w_dim=w_dim,
                          mapping_layers=mapping_layers, fmap_base=fmap_base,
                          fmap_max=fmap_max, channels=channels)
    params = {name: jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
              for i, (name, shape) in enumerate(sorted(shapes.items()))}
    return params, {name: P() for name in params}


def param_shapes(*, resolution, z_dim, w_dim, mapping_layers, fmap_base,
                 fmap_max, channels) -> dict:
    shapes = {}
    for k in range(mapping_layers):
        shapes[f"map{k}.w"] = (z_dim if k == 0 else w_dim, w_dim)
        shapes[f"map{k}.b"] = (w_dim,)

    def modulated(name, ks, cin, cout, res, layout):
        shapes[f"{name}.w"] = ((ks, ks, cout, cin) if layout == "HWOI"
                               else (ks, ks, cin, cout))
        shapes[f"{name}.b"] = (cout,)
        shapes[f"{name}.aff_w"] = (w_dim, cin)
        shapes[f"{name}.aff_b"] = (cin,)
        if res is not None:
            shapes[f"{name}.noise"] = (res, res, 1)
            shapes[f"{name}.strength"] = ()

    c = _nf(4, fmap_base, fmap_max)
    shapes["const"] = (4, 4, c)
    modulated("b4_conv", 3, c, c, 4, "HWIO")
    modulated("b4_rgb", 1, c, channels, None, "HWIO")
    res = 8
    while res <= resolution:
        cout = _nf(res, fmap_base, fmap_max)
        modulated(f"b{res}_up", 3, c, cout, res, "HWOI")
        modulated(f"b{res}_conv", 3, cout, cout, res, "HWIO")
        modulated(f"b{res}_rgb", 1, cout, channels, None, "HWIO")
        c, res = cout, 2 * res
    return shapes


def resolutions(params) -> list:
    """Output resolution of each block, 4 first."""
    out = [4]
    while f"b{2 * out[-1]}_up.w" in params:
        out.append(2 * out[-1])
    return out


def tconv_problems(params) -> dict:
    """The VALID stride-2 up-convolution of each block from 8 px on."""
    from repro.core.maps import TConvProblem

    probs = {}
    for res in resolutions(params)[1:]:
        ks, _, oc, ic = params[f"b{res}_up.w"].shape
        probs[f"b{res}_up"] = TConvProblem(res // 2, res // 2, ic, ks, oc, 2,
                                           "VALID")
    return probs


def lrelu(x):
    return jnp.where(x >= 0, x, LRELU_SLOPE * x) * LRELU_GAIN


def _dense(x, w, b):
    return jnp.matmul(x, w * (1.0 / math.sqrt(w.shape[0])),
                      precision=HIGHEST) + b


def mapping(params, z):
    """z (B, z_dim) -> w (B, w_dim); every layer gets this same w."""
    x = z * lax.rsqrt(jnp.mean(z * z, axis=1, keepdims=True) + 1e-8)
    k = 0
    while f"map{k}.w" in params:
        x = lrelu(_dense(x, params[f"map{k}.w"], params[f"map{k}.b"]))
        k += 1
    return x


def styles(params, name, w_lat):
    """Per-request scale of each input channel of layer ``name``."""
    return _dense(w_lat, params[f"{name}.aff_w"], params[f"{name}.aff_b"])


def _fir_axis(x, axis: int, up: int, pad0: int, pad1: int):
    """One axis of ``upfirdn``: insert ``up - 1`` zeros after each sample,
    pad, and correlate with :data:`FIR_1D` (symmetric, so convolve)."""
    if up > 1:
        zeros = jnp.zeros_like(x)
        x = jnp.stack([x] + [zeros] * (up - 1), axis=axis + 1)
        shape = list(x.shape)
        shape[axis:axis + 2] = [shape[axis] * up]
        x = x.reshape(shape)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (pad0, pad1)
    x = jnp.pad(x, pad)
    n = x.shape[axis] - len(FIR_1D) + 1
    return sum(t * lax.slice_in_dim(x, k, k + n, axis=axis)
               for k, t in enumerate(FIR_1D))


def fir_down(y):
    """The FIR after a VALID up-convolution: ``2i+1`` -> ``2i`` rows and
    columns (pad 1 on every side).  Shifted adds in f32, exact to
    rounding."""
    return _fir_axis(_fir_axis(y, 1, 1, 1, 1), 2, 1, 1, 1)


def upsample_skip(img):
    """The RGB skip: ``upfirdn(img, up=2, FIR * 4, pad=(2, 1))``."""
    return _fir_axis(_fir_axis(img, 1, 2, 2, 1), 2, 2, 2, 1)


def demodulation(w, s):
    """``d`` (B, Cout) of a 3x3 layer; ``w`` HWIO, already over
    ``sqrt(fan_in)``: ``rsqrt((s * s) @ sum_{kh, kw} w^2 + 1e-8)``."""
    w2 = jnp.sum(w * w, axis=(0, 1))
    return lax.rsqrt(jnp.matmul(s * s, w2, precision=HIGHEST) + 1e-8)


def _layer(tc, params, name, x, w_lat, *, up: bool):
    """Modulated 3x3 conv (or up-conv), noise, bias, lrelu."""
    w = params[f"{name}.w"]
    s = styles(params, name, w_lat)
    cin = x.shape[-1]
    w = w * (1.0 / math.sqrt(w.shape[0] * w.shape[1] * cin))
    x = x * s[:, None, None, :]
    if up:
        d = demodulation(jnp.swapaxes(w, 2, 3), s)
        y = fir_down(tc.tconv(x, w, None, name=name, stride=2,
                              padding="VALID"))
    else:
        d = demodulation(w, s)
        y = lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                     dimension_numbers=_DN,
                                     precision=HIGHEST)
    y = y * d[:, None, None, :]
    y = y + params[f"{name}.strength"] * params[f"{name}.noise"]
    return lrelu(y + params[f"{name}.b"])


def _to_rgb(params, name, x, w_lat):
    w = params[f"{name}.w"][0, 0]
    s = styles(params, name, w_lat)
    w = w * (1.0 / math.sqrt(w.shape[0]))
    return jnp.einsum("bhwc,co->bhwo", x * s[:, None, None, :], w,
                      precision=HIGHEST) + params[f"{name}.b"]


def generator(params, z, *, method: str = "mm2im", plans=None, policy=None):
    """z (B, z_dim) -> images (B, R, R, 3), linear (no tanh).

    ``method``/``plans``/``policy`` route the up-convolutions as in
    ``models/gan.py`` (:func:`repro.models.gan._tconv_policy`).
    """
    from repro.models.gan import _tconv_policy

    tc = _tconv_policy(method, plans, policy)
    w_lat = mapping(params, z)
    const = params["const"]
    x = jnp.broadcast_to(const, (z.shape[0],) + const.shape)
    x = _layer(tc, params, "b4_conv", x, w_lat, up=False)
    img = _to_rgb(params, "b4_rgb", x, w_lat)
    for res in resolutions(params)[1:]:
        x = _layer(tc, params, f"b{res}_up", x, w_lat, up=True)
        x = _layer(tc, params, f"b{res}_conv", x, w_lat, up=False)
        img = upsample_skip(img) + _to_rgb(params, f"b{res}_rgb", x, w_lat)
    return img
