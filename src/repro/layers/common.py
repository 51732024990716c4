"""Common NN layers — functional style.

Every ``init_*`` returns ``(params, specs)`` — two trees with identical
structure, the second holding ``jax.sharding.PartitionSpec`` leaves over the
production mesh axes ``('pod', 'data', 'model')`` (see docs/DESIGN.md §6).
Sharding conventions:

* FSDP ("zero-3") storage axis is ``'data'``; tensor-parallel axis is
  ``'model'``; ``'pod'`` extends the batch axis (pure DP) unless a config
  repurposes it.
* Megatron pattern: column-parallel into the hidden (shard out-dim over
  'model'), row-parallel back out (shard in-dim over 'model').
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Any  # nested dict of arrays
Specs = Any  # nested dict of PartitionSpec


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}, {"scale": P(None)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(dim: int, dtype=jnp.float32):
    return ({"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)},
            {"scale": P(None), "bias": P(None)})


def layernorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (..., L, H, hd); positions: broadcastable to (..., L)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,L,1,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, kind: str = "swiglu", dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        params = {
            "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype),
        }
        specs = {
            "w_gate": P("data", "model"),
            "w_up": P("data", "model"),
            "w_down": P("model", "data"),
        }
    else:  # plain gelu/relu FFN
        params = {
            "w_up": dense_init(ks[0], d_model, d_ff, dtype),
            "b_up": jnp.zeros((d_ff,), dtype),
            "w_down": dense_init(ks[1], d_ff, d_model, dtype),
            "b_down": jnp.zeros((d_model,), dtype),
        }
        specs = {
            "w_up": P("data", "model"), "b_up": P("model"),
            "w_down": P("model", "data"), "b_down": P(None),
        }
    return params, specs


def mlp(params, x, kind: str = "swiglu"):
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
        return h @ params["w_down"]
    if kind == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"]) * (x @ params["w_up"])
        return h @ params["w_down"]
    h = jax.nn.relu(x @ params["w_up"] + params["b_up"])
    return h @ params["w_down"] + params["b_down"]


# ---------------------------------------------------------------------------
# Transposed convolution (MM2IM-backed)
# ---------------------------------------------------------------------------


def init_tconv(key, ks: int, oc: int, ic: int, dtype=jnp.float32,
               scale: float = 0.02):
    """TCONV layer params: HWOI weights (paper layout) + bias.

    Sharding: output channels over 'model' (column-parallel), input channels
    over 'data' (FSDP storage), matching the GAN generators.
    """
    w = (jax.random.normal(key, (ks, ks, oc, ic), jnp.float32) * scale)
    params = {"w": w.astype(dtype), "b": jnp.zeros((oc,), dtype)}
    specs = {"w": P(None, None, "model", "data"), "b": P("model")}
    return params, specs


def tconv_layer(params, x, *, stride: int, padding: str = "SAME",
                method: str = "mm2im", activation: str = "none", plan=None,
                out_scale=None, out_dtype=None):
    """Apply a TCONV layer through the kernel registry.

    ``plan`` is an explicit tile plan (``kernels.registry.Plan`` or a
    ``(block_oh, block_oc[, grid_order])`` tuple), typically produced by
    ``core.autotune.autotune`` — this is how tuned plans reach model code.

    With ``plan=None`` the dispatcher consumes the on-disk autotuner cache
    automatically: if this layer's problem key (shapes, dtype, batch) was
    ever tuned, the tuned plan — including a double-buffered kernel
    preference (``Plan.method``) — applies with no threading here.
    Precedence: explicit ``plan`` > cache hit > heuristic
    (docs/AUTOTUNER.md).

    ``out_scale`` (and optionally ``out_dtype``) attach the PPU requant
    epilogue stage, making a quantized *inference* layer out of the same
    call: int8 params/activations run the paper's int8 datapath on
    kernels that fuse requant, and the dispatcher's dequant -> requant
    fallback on every other registered method — the layer code does not
    change either way.  Requantization is not differentiable (round/clip;
    the paper quantizes frozen models) — keep ``out_scale=None`` on
    training paths.
    """
    from repro.kernels.ops import tconv

    return tconv(x, params["w"], params["b"], stride=stride, padding=padding,
                 method=method, activation=activation, plan=plan,
                 out_scale=out_scale, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32,
                   pad_to: int = 512):
    """Vocab-sharded embedding table, padded so the vocab dim divides the
    'model' axis (production convention — e.g. 50280 -> 50688)."""
    vp = -(-vocab // pad_to) * pad_to
    emb = (jax.random.normal(key, (vp, d_model), jnp.float32) * 0.02).astype(dtype)
    return {"table": emb}, {"table": P("model", "data")}


def embed(params, tokens):
    """Row gather that keeps the tokens' batch sharding.

    Under an explicit-sharding mesh the gather's output sharding is
    ambiguous (vocab-sharded table, data-sharded tokens), so it is spelled
    out: the tokens' spec with the feature dim replicated.
    """
    sharding = jax.typeof(tokens).sharding
    return params["table"].at[tokens].get(
        out_sharding=sharding.update(spec=P(*sharding.spec, None)))


def unembed(params, x, vocab: Optional[int] = None):
    """Logits over the padded table; padded columns masked to -inf so the
    softmax/CE semantics match the unpadded vocab exactly."""
    logits = x @ params["table"].T
    vp = params["table"].shape[0]
    if vocab is not None and vocab != vp:
        mask = jnp.arange(vp) < vocab
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    return logits
