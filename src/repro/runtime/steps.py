"""Step builders: train / prefill / decode, with full sharding annotations.

Each builder returns ``(jitted_fn, abstract_inputs, shardings)`` ready for
``.lower(...).compile()`` (the dry-run path) or direct execution (examples
and smoke tests).  All lowering happens under ``jax.set_mesh`` so
PartitionSpec-level constraints resolve against the production mesh.

The GAN builders (:func:`make_gan_train_step`,
:func:`make_gan_sample_step`) are the training/serve entry points for the
paper's TCONV models; with no explicit ``plans=`` they resolve each
generator layer's tile plan from the autotuner's on-disk cache
(``core/autotune.py``) — tune once with ``autotune_sweep``, and every
later training or serving process runs the tuned plans (and tuned kernel
variant, single- vs double-buffered) with zero plan threading.  Every
TCONV here goes through the single Epilogue-typed dispatch pipeline
(``kernels/ops.py``), so the f32 training steps and the int8 serve path
share one plan-consumption and variant-upgrade implementation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.distributed import sharding as shd
from repro.models import gan, lm
from repro.models import runner as runner_mod
from repro.models.config import ModelConfig
from repro.optim import adamw


@dataclasses.dataclass
class StepBundle:
    fn: Any                 # jitted function
    abstract_args: tuple    # ShapeDtypeStruct pytrees for .lower(*args)
    kind: str
    meta: Optional[dict] = None  # builder diagnostics (e.g. resolved plans)


def usable_batch_axes(batch: int, mesh) -> tuple:
    """DP axes whose product divides the global batch (long_500k has B=1:
    no batch sharding — parallelism comes from the model axes only).
    Greedy: accumulate axes while divisibility holds."""
    axes, prod = [], 1
    for a in shd.batch_axes(mesh):
        if batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)


def _model_inputs(cfg: ModelConfig, batch: int, seq: int, mesh) -> Dict[str, Any]:
    """Abstract model inputs (the batch pytree) for one training/prefill step."""
    bspec = P(usable_batch_axes(batch, mesh))
    sds = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    out = {
        "tokens": sds((batch, seq), jnp.int32, P(*bspec)),
        "targets": sds((batch, seq), jnp.int32, P(*bspec)),
    }
    if cfg.modality == "vision":
        out["prefix_embeds"] = sds((batch, cfg.frontend_len, cfg.d_model),
                                   jnp.bfloat16, P(*bspec, None, None))
    if cfg.enc_layers:
        out["enc_embeds"] = sds((batch, seq, cfg.d_model),
                                jnp.bfloat16, P(*bspec, None, None))
    return out


def abstract_params(cfg: ModelConfig):
    """(ShapeDtypeStruct params tree, PartitionSpec tree) — no allocation.

    Specs are plain python objects built during tracing, so they are
    captured through a side channel while eval_shape abstracts the arrays.
    """
    box = []

    def f(k):
        p, s = lm.init(cfg, k)
        box.append(s)
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, box[0]


def abstract_state(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig, mesh):
    """Abstract TrainState (params + opt) with shardings attached."""
    p_shape, specs = abstract_params(cfg)
    specs = shd.pad_specs_for_mesh(specs, mesh)
    p_shard = shd.tree_shardings(mesh, specs)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        p_shape, p_shard)
    opt_shape = jax.eval_shape(functools.partial(adamw.init, cfg=opt_cfg), params)
    opt_specs = adamw.state_specs(specs)
    opt_shard = shd.tree_shardings(mesh, shd.pad_specs_for_mesh(opt_specs, mesh))
    opt = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        opt_shape, opt_shard)
    return {"params": params, "opt": opt}, specs


def loss_from_batch(cfg: ModelConfig, params, batch):
    kw = {}
    if "prefix_embeds" in batch:
        kw["prefix_embeds"] = batch["prefix_embeds"]
    if "enc_embeds" in batch:
        kw["enc_embeds"] = batch["enc_embeds"]
    return lm.loss_fn(cfg, params, batch["tokens"], batch["targets"], **kw)


def make_train_step(cfg: ModelConfig, mesh, opt_cfg: Optional[adamw.AdamWConfig] = None,
                    *, batch: int, seq: int, donate: bool = True,
                    seq_shard: bool = True,
                    n_micro: Optional[int] = None) -> StepBundle:
    opt_cfg = opt_cfg or adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype)
    n_micro = cfg.microbatches if n_micro is None else n_micro
    # Microbatch count must divide the per-DP-shard batch.
    dp = 1
    for a in usable_batch_axes(batch, mesh):
        dp *= mesh.shape[a]
    while (batch // dp) % n_micro:
        n_micro -= 1
    shd.set_activation_hints(batch_axes=usable_batch_axes(batch, mesh),
                             seq_axis="model" if seq_shard else None)
    _, pspecs = abstract_params(cfg)
    pspecs = shd.pad_specs_for_mesh(pspecs, mesh)

    def lg(p, b_in):
        (loss, metrics), grads = jax.value_and_grad(
            lambda pp: loss_from_batch(cfg, pp, b_in), has_aux=True)(p)
        return (loss, metrics), grads

    lg_acc = adamw.accumulate(lg, n_micro)

    def train_step(state, batch_in):
        (loss, metrics), grads = lg_acc(state["params"], batch_in)
        # Pin gradients to the parameters' (FSDP) sharding: reductions of
        # dW for ZeRO-gathered weights become reduce-scatters instead of
        # all-reduces (halves cross-device dW traffic — EXPERIMENTS §Perf).
        grads = jax.tree.map(
            lambda g, s: shd.constrain(g, s), grads, pspecs)
        new_p, new_opt, om = adamw.apply(grads, state["opt"], state["params"], opt_cfg)
        metrics = dict(metrics, loss=loss, **om)
        return {"params": new_p, "opt": new_opt}, metrics

    astate, _specs = abstract_state(cfg, opt_cfg, mesh)
    abatch = _model_inputs(cfg, batch, seq, mesh)
    fn = jax.jit(train_step, donate_argnums=(0,) if donate else ())
    return StepBundle(fn=fn, abstract_args=(astate, abatch), kind="train")


def make_train_step_compressed(cfg: ModelConfig, mesh,
                               opt_cfg: Optional[adamw.AdamWConfig] = None,
                               *, batch: int, seq: int) -> StepBundle:
    """Train step with int8 error-feedback gradient compression on the
    cross-pod ('pod' axis / DCN) reduction — DESIGN.md §6.

    shard_map is *manual* over 'pod' and automatic over (data, model):
    gradients are pod-local, compressed, all-gathered as int8 + scalar
    scales, and the dequantized mean feeds AdamW.  The error state carries
    a leading pod dim (one residual per pod).
    """
    from repro.distributed import compression

    if "pod" not in mesh.axis_names:
        return make_train_step(cfg, mesh, opt_cfg, batch=batch, seq=seq)
    opt_cfg = opt_cfg or adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype)
    shd.set_activation_hints(batch_axes=("data",), seq_axis="model")
    n_pods = mesh.shape["pod"]

    def body(state, err, batch_in):
        def lf(p):
            loss, metrics = loss_from_batch(cfg, p, batch_in)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(state["params"])
        err_local = jax.tree.map(lambda e: e[0], err)
        gmean, err_new = compression.tree_pod_mean_int8(grads, err_local)
        new_p, new_opt, om = adamw.apply(gmean, state["opt"], state["params"],
                                         opt_cfg)
        metrics = dict(metrics, loss=jax.lax.pmean(loss, "pod"), **om)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
        return ({"params": new_p, "opt": new_opt},
                jax.tree.map(lambda e: e[None], err_new), metrics)

    astate, specs = abstract_state(cfg, opt_cfg, mesh)
    err_specs = jax.tree.map(
        lambda s: P(*(("pod",) + tuple(s))), shd.pad_specs_for_mesh(specs, mesh),
        is_leaf=lambda s: isinstance(s, P))
    err_shape = jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            (n_pods,) + s.shape, jnp.float32,
            sharding=NamedSharding(mesh, sp)),
        astate["params"], err_specs)
    abatch = _model_inputs(cfg, batch, seq, mesh)

    # Partial-manual shard_map: in/out specs may reference ONLY the manual
    # axis ('pod'); the data/model shardings of each leaf are handled by
    # the automatic axes (and are carried by the abstract args' shardings).
    state_pod_specs = jax.tree.map(lambda _: P(), astate)
    err_pod_specs = jax.tree.map(lambda _: P("pod"), astate["params"])
    batch_pod_specs = jax.tree.map(lambda _: P("pod"), abatch)
    fn_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(state_pod_specs, err_pod_specs, batch_pod_specs),
        out_specs=(state_pod_specs, err_pod_specs,
                   {"loss": P(), "ce": P(), "aux": P(), "z": P(),
                    "lr": P(), "grad_norm": P()}),
        axis_names={"pod"}, check_vma=False)
    fn = jax.jit(fn_sm, donate_argnums=(0, 1))
    return StepBundle(fn=fn, abstract_args=(astate, err_shape, abatch),
                      kind="train_compressed")


def make_prefill_step(cfg: ModelConfig, mesh, *, batch: int, seq: int,
                      seq_shard: bool = True) -> StepBundle:
    shd.set_activation_hints(batch_axes=usable_batch_axes(batch, mesh),
                             seq_axis="model" if seq_shard else None)

    def prefill(params, batch_in):
        kw = {}
        if "prefix_embeds" in batch_in:
            kw["prefix_embeds"] = batch_in["prefix_embeds"]
        if "enc_embeds" in batch_in:
            kw["enc_embeds"] = batch_in["enc_embeds"]
        logits, _ = lm.forward(cfg, params, batch_in["tokens"], **kw)
        # Serve-prefill returns only the last-position logits (next token).
        return logits[:, -1]

    opt_cfg = adamw.AdamWConfig()
    astate, _ = abstract_state(cfg, opt_cfg, mesh)
    abatch = _model_inputs(cfg, batch, seq, mesh)
    abatch.pop("targets")
    fn = jax.jit(prefill)
    return StepBundle(fn=fn, abstract_args=(astate["params"], abatch),
                      kind="prefill")


def _weight_stationary_specs(pspecs):
    """Decode-profile param shardings: drop the FSDP ('data') axis so no
    weight is gathered per generated token — weights are read from local
    HBM only (model-sharded), trading replication memory for zero
    weight-collective traffic on the decode path (EXPERIMENTS §Perf D)."""
    def fix(s):
        parts = []
        for part in s:
            if part == "data":
                parts.append(None)
            elif isinstance(part, tuple):
                kept = tuple(a for a in part if a != "data")
                parts.append(kept if kept else None)
            else:
                parts.append(part)
        return P(*parts)
    return jax.tree.map(fix, pspecs, is_leaf=lambda s: isinstance(s, P))


def make_decode_step(cfg: ModelConfig, mesh, *, batch: int, seq: int,
                     kv_seq_shard: bool = True,
                     weight_stationary: bool = False) -> StepBundle:
    """One-token serve_step with the KV cache at fill level seq-1."""
    baxes = usable_batch_axes(batch, mesh)
    shd.set_activation_hints(batch_axes=baxes, seq_axis=None)

    def serve_step(params, cache, tokens):
        logits, new_cache = lm.decode(cfg, params, tokens, cache)
        return logits, new_cache

    opt_cfg = adamw.AdamWConfig()
    if weight_stationary:
        p_shape, pspecs = abstract_params(cfg)
        pspecs = _weight_stationary_specs(shd.pad_specs_for_mesh(pspecs, mesh))
        p_shard = shd.tree_shardings(mesh, pspecs)
        params_abs = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            p_shape, p_shard)
        astate = {"params": params_abs}
    else:
        astate, _ = abstract_state(cfg, opt_cfg, mesh)
    cache_shape = jax.eval_shape(
        lambda: lm.init_cache(cfg, batch, seq, length=seq - 1))
    seq_axis = "model" if kv_seq_shard else None
    cspecs = lm.cache_specs(cfg, seq_axis=seq_axis,
                            batch_axis=baxes if baxes else None)
    cspecs = shd.pad_specs_for_mesh(cspecs, mesh)
    cshard = shd.tree_shardings(mesh, cspecs)
    cache = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        cache_shape, cshard)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32,
                                  sharding=NamedSharding(mesh, P(baxes)))
    fn = jax.jit(serve_step, donate_argnums=(1,))
    return StepBundle(fn=fn, abstract_args=(astate["params"], cache, tokens),
                      kind="decode")


# ---------------------------------------------------------------------------
# GAN steps (the paper's TCONV models) — plan-cache-aware.
# ---------------------------------------------------------------------------


def resolve_gan_plans(g_params, *, batch: int, dtype=jnp.float32,
                      plans: Optional[dict] = None,
                      method: str = "mm2im") -> dict:
    """Per-layer tile plans for a DCGAN generator, cache-backed.

    Compat wrapper over the generic
    :meth:`repro.models.runner.GeneratorRunner.resolve_plans` (which any
    registered model family gets for free).  Precedence per layer:
    explicit ``plans`` entry > autotuner cache hit > nothing (trace-time
    tier lookup / heuristic); plan-incapable methods skip the cache and
    pass only the caller's explicit entries through.
    """
    r = runner_mod.GeneratorRunner(runner_mod.get_spec("dcgan"), g_params,
                                   method=method)
    return r.resolve_plans(batch=batch, dtype=dtype, plans=plans)


def make_gan_train_step(
    g_params, d_params,
    opt_cfg: Optional[adamw.AdamWConfig] = None,
    *,
    batch: int,
    z_dim: int = 100,
    method: str = "mm2im",
    plans: Optional[dict] = None,
) -> StepBundle:
    """Alternating D/G DCGAN update with every generator TCONV on MM2IM.

    State is ``(g_params, g_opt, d_params, d_opt)``; the returned fn maps
    ``(state, z, real) -> (state, (d_loss, g_loss))``.  With ``plans=None``
    the generator layers consume cached autotuner plans automatically
    (see :func:`resolve_gan_plans`).
    """
    opt_cfg = opt_cfg or adamw.AdamWConfig(
        lr=2e-4, b1=0.5, b2=0.999, weight_decay=0.0, clip_norm=None,
        warmup_steps=0, total_steps=1, schedule="constant")
    plans = resolve_gan_plans(g_params, batch=batch, plans=plans,
                              method=method)
    policy = runner_mod.TconvPolicy(method=method, plans=plans)
    img_size, out_ch = gan.dcgan_output_geometry(g_params)

    def bce(logits, is_real: bool):
        sign = 1.0 if is_real else -1.0
        return jnp.mean(jax.nn.softplus(-sign * logits))

    def train_step(state, z, real):
        gp, g_opt, dp, d_opt = state

        def d_loss(dpp):
            fake = gan.dcgan_generator(gp, z, policy=policy)
            return bce(gan.dcgan_discriminator(dpp, real), True) + \
                bce(gan.dcgan_discriminator(dpp, fake), False)

        dl, dg = jax.value_and_grad(d_loss)(dp)
        dp, d_opt, _ = adamw.apply(dg, d_opt, dp, opt_cfg)

        def g_loss(gpp):
            fake = gan.dcgan_generator(gpp, z, policy=policy)
            return bce(gan.dcgan_discriminator(dp, fake), True)

        gl, gg = jax.value_and_grad(g_loss)(gp)
        gp, g_opt, _ = adamw.apply(gg, g_opt, gp, opt_cfg)
        return (gp, g_opt, dp, d_opt), (dl, gl)

    astate = jax.eval_shape(
        lambda: ((g_params, adamw.init(g_params, opt_cfg),
                  d_params, adamw.init(d_params, opt_cfg))))
    az = jax.ShapeDtypeStruct((batch, z_dim), jnp.float32)
    areal = jax.ShapeDtypeStruct((batch, img_size, img_size, out_ch),
                                 jnp.float32)
    fn = jax.jit(train_step, donate_argnums=(0,))
    return StepBundle(fn=fn, abstract_args=(astate, az, areal),
                      kind="gan_train",
                      meta={"plans": plans, "method": method})


def make_runner_sample_step(
    runner: "runner_mod.GeneratorRunner",
    *,
    batch: int,
    precision: str = "f32",
    plans: Optional[dict] = None,
    kind: Optional[str] = None,
) -> StepBundle:
    """Serve step for ANY registered generator family: inputs -> outputs.

    The generic successor of the DCGAN-only sample step: plans resolve
    through the runner's problem enumeration (so pix2pix/FSRCNN/style-
    transfer get cache-backed plans too), and ``precision='int8'`` routes
    every TCONV through the calibrated requant-Epilogue policy.
    """
    dtype = jnp.int8 if precision == "int8" else jnp.float32
    plans = runner.resolve_plans(batch=batch, dtype=dtype, plans=plans)
    policy = runner.policy(precision=precision, plans=plans)

    def sample(params, x):
        return runner.spec.forward(params, x, runner.options, policy=policy)

    fn = jax.jit(sample)
    return StepBundle(
        fn=fn,
        abstract_args=(jax.eval_shape(lambda: runner.params),
                       runner.input_spec(batch)),
        kind=kind or f"{runner.name}_sample",
        meta={"plans": plans, "method": runner.method,
              "precision": precision})


def make_gan_sample_step(
    g_params,
    *,
    batch: int,
    z_dim: int = 100,
    method: str = "mm2im",
    plans: Optional[dict] = None,
) -> StepBundle:
    """Generator-only serve step: ``z -> images``, cached plans consumed.

    DCGAN compat wrapper over :func:`make_runner_sample_step` (``z_dim``
    is recovered from the params; the kwarg is kept for callers that
    passed it explicitly and must agree with the projection weight).
    """
    r = runner_mod.GeneratorRunner(runner_mod.get_spec("dcgan"), g_params,
                                   method=method)
    if z_dim != r.input_shape()[0]:
        raise ValueError(f"z_dim={z_dim} disagrees with params "
                         f"(proj expects {r.input_shape()[0]})")
    return make_runner_sample_step(r, batch=batch, plans=plans,
                                   kind="gan_sample")


def make_step_for_cell(arch: str, shape: str, mesh) -> StepBundle:
    """The (architecture x input-shape) cell entry point used by dryrun.py."""
    spec = registry.get(arch)
    cfg = spec.model
    seq, gbatch, kind = registry.SHAPES[shape]
    if shape not in spec.supported_shapes():
        raise ValueError(f"cell ({arch}, {shape}) is skipped per DESIGN.md §5")
    if kind == "train":
        return make_train_step(cfg, mesh, batch=gbatch, seq=seq)
    if kind == "prefill":
        return make_prefill_step(cfg, mesh, batch=gbatch, seq=seq)
    return make_decode_step(cfg, mesh, batch=gbatch, seq=seq)
