"""Plain layer arithmetic shared by the references in ``reference/``.

Straightforward ``jax.numpy``/``lax`` with no kernels, plans, policies or
server: the yardstick the served outputs are compared with.  Nothing here
imports the program under test.

Precision is explicit on every product.  ``Prec`` names how one forward
computes:

* ``xla``: the dense and convolution layers around the TCONVs and the
  batch norms.  ``"highest"`` (full f32 products), ``"default"`` (JAX's
  default matmul precision; one bf16 pass on a TPU), or ``"bfloat16"``
  (operands, products and activations held in bf16).
* ``tconv``: the f32 TCONV layers: ``"highest"``, ``"high"`` (three bf16
  passes) or ``"default"``.
* ``int_bits``: the width of the TCONV quantization in the int8 forward
  (8; the control lowers it to 4).

Layouts follow the program's: NHWC activations, HWIO conv weights, HWOI
TCONV weights (``w[kh, kw, oc, ic]``), scatter semantics
``y[i*S + k - c] += x[i] * w[k]`` with the SAME crop ``c = (Ks - S) // 2``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

_PRECISIONS = {"highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH,
               "default": lax.Precision.DEFAULT}


@dataclasses.dataclass(frozen=True)
class Prec:
    xla: str = "highest"
    tconv: str = "highest"
    int_bits: int = 8

    @property
    def dtype(self):
        return jnp.bfloat16 if self.xla == "bfloat16" else jnp.float32

    @property
    def xla_precision(self):
        return _PRECISIONS.get(self.xla, lax.Precision.DEFAULT)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed up to 2**64.

    ``PRNGKey`` alone keeps only the low 32 bits, so seeds that differ
    above them would collide.
    """
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def dense(x, w, prec: Prec):
    return jnp.matmul(x.astype(prec.dtype), w.astype(prec.dtype),
                      precision=prec.xla_precision).astype(prec.dtype)


def conv(x, w, stride: int, prec: Prec):
    """SAME convolution, HWIO weights (the encoder layers)."""
    return lax.conv_general_dilated(
        x.astype(prec.dtype), w.astype(prec.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=prec.xla_precision).astype(prec.dtype)


def batchnorm(x, eps: float = 1e-5):
    """Batch statistics over (batch, height, width), as the models serve."""
    mu = x.mean((0, 1, 2), keepdims=True)
    var = x.var((0, 1, 2), keepdims=True)
    return (x - mu) * lax.rsqrt(var + jnp.asarray(eps, x.dtype))


def leaky_relu(x, slope: float = 0.2):
    return jnp.where(x >= 0, x, jnp.asarray(slope, x.dtype) * x)


def activation(name: str, x):
    if name == "none":
        return x
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "tanh":
        return jnp.tanh(x)
    raise ValueError(f"unknown activation {name!r}")


def _tconv_dims(ks: int, stride: int):
    crop = (ks - stride) // 2
    return [(ks - 1 - crop, stride - 1 + crop)] * 2


def tconv_raw(x, w, stride: int, *, precision=lax.Precision.HIGHEST,
              out_dtype=None):
    """SAME TCONV with HWOI weights: a convolution of the stride-dilated
    input with the spatially flipped kernel, padded so that the output is
    the full scatter result cropped to ``I * S`` rows and columns."""
    ks = w.shape[0]
    w_hwio = jnp.transpose(w, (0, 1, 3, 2))[::-1, ::-1]
    return lax.conv_general_dilated(
        x, w_hwio, (1, 1), _tconv_dims(ks, stride),
        lhs_dilation=(stride, stride),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision,
        preferred_element_type=out_dtype)


def tconv_f32(x, w, b, stride: int, prec: Prec):
    """f32 TCONV plus bias at the forward's TCONV precision."""
    if prec.xla == "bfloat16":
        y = tconv_raw(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), stride,
                      precision=lax.Precision.DEFAULT)
        return y + b.astype(jnp.bfloat16)
    y = tconv_raw(x.astype(jnp.float32), w.astype(jnp.float32), stride,
                  precision=_PRECISIONS[prec.tconv])
    return y + b


# --- int8: the runner's documented one-shot rule, rewritten from its text.

def quantize(t, scale, bits: int = 8):
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(t / scale), -qmax, qmax).astype(jnp.int8)


def scales_from_absmax(absmax, bits: int = 8):
    """Symmetric per-tensor scales: max|t| (floored at 1e-6) over the
    largest code, 127 for int8.  ``absmax`` is ``(x, w, acc)`` of one
    layer from the calibration forward."""
    qmax = 2 ** (bits - 1) - 1
    return tuple(max(float(a), 1e-6) / qmax for a in absmax)


def int_scales(absmax: dict, bits: int = 8) -> dict:
    """Per layer, the float32 numbers the quantized TCONV multiplies or
    divides by: x and w scales, their product (the bias scale), the
    requant multiplier ``x_scale * w_scale / y_scale`` and the y scale,
    each worked out in double precision first.  Passed to the forward as
    arrays, so that one compiled program serves every seed."""
    out = {}
    for name, am in absmax.items():
        sx, sw, sy = scales_from_absmax(am, bits)
        out[name] = jnp.asarray([sx, sw, sx * sw, (sx * sw) / sy, sy],
                                jnp.float32)
    return out


def tconv_int(x, w, b, stride: int, scales, prec: Prec):
    """Quantized TCONV: quantize operands, integer accumulate, add the
    integer bias, requantize to the output scale (round half to even,
    clip to [-2^(n-1), 2^(n-1)-1]), dequantize.  ``scales`` is one row of
    :func:`int_scales`."""
    bits = prec.int_bits
    xq = quantize(x.astype(jnp.float32), scales[0], bits)
    wq = quantize(w, scales[1], bits)
    bq = jnp.round(b / scales[2]).astype(jnp.int32)
    acc = tconv_raw(xq, wq, stride, precision=lax.Precision.DEFAULT,
                    out_dtype=jnp.int32) + bq
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    yq = jnp.clip(jnp.round(acc.astype(jnp.float32) * scales[3]), lo, hi)
    return (yq * scales[4]).astype(prec.dtype)


def forward_f32(model, params, x, cfg, prec: Prec):
    def tconv(name, h, w, b, stride, act):
        return activation(act, tconv_f32(h, w, b, stride, prec))
    return model.forward(params, x, cfg, prec, tconv)


def calibrate(model, params, cfg, prec: Prec):
    """Per-layer (max|x|, max|w|, max|acc|) of the one-shot calibration
    forward: f32, batch 1, on ``normal(PRNGKey(0))`` inputs, every TCONV
    at the XLA layers' precision, ``acc`` taken after the bias and before
    the activation."""
    shape = (1,) + tuple(model.input_shape(cfg))

    def run(params):
        seen = {}

        def tconv(name, h, w, b, stride, act):
            h = h.astype(jnp.float32)
            acc = tconv_raw(h, w, stride, precision=prec.xla_precision) + b
            seen[name] = (jnp.max(jnp.abs(h)), jnp.max(jnp.abs(w)),
                          jnp.max(jnp.abs(acc)))
            return activation(act, acc)

        x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
        model.forward(params, x, cfg, Prec(xla=prec.xla), tconv)
        return seen

    seen = jax.jit(run)(params)
    return {name: tuple(float(v) for v in vals) for name, vals in seen.items()}


def forward_int(model, params, x, cfg, prec: Prec, scales):
    """The quantized forward; ``scales`` from :func:`int_scales`."""
    def tconv(name, h, w, b, stride, act):
        return activation(act, tconv_int(h, w, b, stride, scales[name], prec))
    return model.forward(params, x, cfg, prec, tconv)
