"""Operations and bytes of each product layer, computed from shapes.

A TCONV layer is counted by its effectual taps: input pixel ``i`` and
kernel tap ``k`` contribute to output ``i*S + k - c`` only where that lands
inside the SAME-cropped output (``c = (Ks - S) // 2``, length ``I*S``).
A SAME convolution is counted by the taps that read inside the input.
So the count is the same whatever implements the layer: padding, tiling,
lane rounding and cropped-away taps are work of the implementation, not
of the model.

One MAC is two operations.  Bytes are the least HBM traffic of one call:
its input, weights and bias read once and its output written once.
"""

from __future__ import annotations

BYTES = {"f32": {"x": 4, "w": 4, "b": 4, "y": 4},
         "int8": {"x": 1, "w": 1, "b": 4, "y": 1}}


def tconv_taps(i: int, ks: int, s: int) -> int:
    """Effectual (input, tap) pairs along one axis of a SAME TCONV."""
    c = (ks - s) // 2
    return sum(1 for a in range(i) for k in range(ks)
               if 0 <= a * s + k - c < i * s)


def conv_taps(i: int, ks: int, s: int) -> int:
    """(output, tap) pairs reading inside the input along one axis of a
    SAME convolution (TensorFlow's split: the smaller pad before)."""
    o = -(-i // s)
    pad = max((o - 1) * s + ks - i, 0)
    lo = pad // 2
    return sum(1 for a in range(o) for k in range(ks)
               if 0 <= a * s + k - lo < i)


def layer_macs(layer: dict) -> int:
    """MACs of one layer for one image."""
    kind = layer["kind"]
    if kind == "dense":
        return layer["ic"] * layer["oc"]
    taps = tconv_taps if kind == "tconv" else conv_taps
    th = taps(layer["ih"], layer["ks"], layer["stride"])
    tw = taps(layer["iw"], layer["ks"], layer["stride"])
    return th * tw * layer["ic"] * layer["oc"]


def tconv_bytes(layer: dict, batch: int, precision: str) -> int:
    """Least HBM bytes of one TCONV call at ``batch`` images."""
    b = BYTES[precision]
    s = layer["stride"]
    x = batch * layer["ih"] * layer["iw"] * layer["ic"] * b["x"]
    w = layer["ks"] ** 2 * layer["ic"] * layer["oc"] * b["w"]
    y = batch * layer["ih"] * s * layer["iw"] * s * layer["oc"] * b["y"]
    return x + w + layer["oc"] * b["b"] + y


def model_counts(layers: list) -> dict:
    """Per-image MACs: ``tconv``, ``other`` and ``total``."""
    tconv = sum(layer_macs(l) for l in layers if l["kind"] == "tconv")
    total = sum(layer_macs(l) for l in layers)
    return {"tconv": tconv, "other": total - tconv, "total": total}


def tconv_min_seconds(layers: list, batch: int, precision: str,
                      peak_ops: float, hbm_bytes_per_s: float) -> float:
    """Roofline time of one forward's TCONV calls at ``batch`` images: per
    call the larger of operations over the peak and bytes over HBM
    bandwidth, summed over the calls."""
    t = 0.0
    for l in layers:
        if l["kind"] != "tconv":
            continue
        ops = 2 * batch * layer_macs(l)
        t += max(ops / peak_ops,
                 tconv_bytes(l, batch, precision) / hbm_bytes_per_s)
    return t
