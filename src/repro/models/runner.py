"""GeneratorRunner — the one serving contract for every TCONV model.

Before this layer each generator (`dcgan_generator`, `pix2pix_generator`,
`fsrcnn`, `styletransfer`) carried its own copy of the `method=`/`plans=`
dispatch plumbing, only DCGAN could enumerate its TCONV problem shapes,
and every caller (step builders, benchmarks, a would-be server) had to
special-case each model's geometry.  The runner layer collapses that into
one uniform contract:

    runner = make_runner("dcgan", key=jax.random.PRNGKey(0))
    runner.apply(z)                       # f32; tuned plans consumed per tier
    runner.apply(z, precision="int8")     # every TCONV through the requant PPU
    runner.tconv_problems()               # {layer: TConvProblem} for warmup/sweep
    runner.input_spec(batch=8)            # what the server batches to
    runner.jitted(batch=8)                # memoized jit per (batch, precision)

Two pieces make it work:

* **Policies** (:class:`TconvPolicy`, :class:`Int8TconvPolicy`): a policy
  is the object a model forward delegates every named TCONV layer to
  (``models/gan.py::_tconv_policy``).  The f32 policy reproduces the
  legacy behavior (explicit plan > trace-time tier lookup); the int8
  policy statically quantizes operands with calibrated per-layer scales
  and runs the genuine ``tconv_int8`` requant-Epilogue path, dequantizing
  only for the activation (the Epilogue applies requant *before* the
  activation — see ``core/epilogue.py::STAGES`` — so a tanh in the int8
  domain would saturate; serving keeps the kernel store int8 and applies
  the nonlinearity on the dequantized output instead).
* **Specs** (:class:`RunnerSpec`): per-model closures for init / forward /
  problem enumeration / input geometry, registered below for all four
  models.  Geometry a model cannot recover from its params (FSRCNN and
  style-transfer input resolution, FSRCNN upscale) lives in runner
  *options* with per-spec defaults.

Int8 calibration is one-shot static post-training quantization (the
paper deploys quantized frozen models): a single eager f32 forward on a
synthetic sample records per-layer symmetric absmax scales for the
input, weight, and pre-activation accumulator; scales are python floats,
so they are static under jit and the requant epilogue never retraces.

Serving caveat: the paper's four models compute batch statistics inline
(BN folding is a deployment-time transform the repo doesn't model), so a
request's output depends on its co-batched neighbors.  The serving layer
(`repro/serve/`) documents and tests against the batched forward.
StyleGAN2 (``models/stylegan2.py``) has no batch statistics: each row is
the request served alone.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import epilogue as epi
from repro.kernels import ops
from repro.models import gan, stylegan2

DEFAULT_METHOD = ops.DEFAULT_METHOD
PRECISIONS: Tuple[str, ...] = ("f32", "int8")


def _check_precision(precision: str) -> None:
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}")


# ---------------------------------------------------------------------------
# Per-layer TCONV policies.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TconvPolicy:
    """f32 execution policy: one kernel method + optional per-layer plans.

    A ``None`` plan for a layer is not "no plan": ``ops.tconv`` resolves
    the problem key through the four plan tiers (explicit > user cache >
    shipped table > heuristic) at trace time.
    """

    method: str = DEFAULT_METHOD
    plans: Optional[Mapping[str, Any]] = None

    def plan_for(self, name: str):
        return None if self.plans is None else self.plans.get(name)

    def tconv(self, x, w, bias=None, *, name: str, stride: int,
              padding: str = "SAME", activation: str = "none"):
        return ops.tconv(x, w, bias, stride=stride, padding=padding,
                         method=self.method, activation=activation,
                         plan=self.plan_for(name))


@dataclasses.dataclass(frozen=True)
class LayerQuant:
    """Calibrated symmetric absmax scales for one TCONV layer.

    ``out_scale`` is the requant multiplier the PPU epilogue applies to
    the int32 accumulator: acc is in units of ``x_scale * w_scale``, and
    the int8 output should be in units of ``y_scale``.
    """

    x_scale: float
    w_scale: float
    y_scale: float

    @property
    def out_scale(self) -> float:
        return (self.x_scale * self.w_scale) / self.y_scale


def quantize_int8(t, scale: float):
    """Symmetric per-tensor quantization to int8 (saturating at ±127)."""
    return jnp.clip(jnp.round(t / scale), -127, 127).astype(jnp.int8)


@dataclasses.dataclass(frozen=True)
class Int8TconvPolicy:
    """Int8 execution policy: every TCONV through the requant Epilogue.

    Operands are quantized with static calibrated scales, the kernel runs
    ``tconv_int8`` (int8 in, int8 out, bias+requant fused in the PPU
    epilogue), and only the activation runs on the dequantized output.
    """

    quant: Mapping[str, LayerQuant]
    method: str = DEFAULT_METHOD
    plans: Optional[Mapping[str, Any]] = None

    def plan_for(self, name: str):
        return None if self.plans is None else self.plans.get(name)

    def tconv(self, x, w, bias=None, *, name: str, stride: int,
              padding: str = "SAME", activation: str = "none"):
        q = self.quant[name]
        x_q = quantize_int8(x, q.x_scale)
        w_q = quantize_int8(w, q.w_scale)
        bias_q = None if bias is None else jnp.round(
            bias / (q.x_scale * q.w_scale)).astype(jnp.int32)
        y_q = ops.tconv_int8(x_q, w_q, bias_q, q.out_scale, stride=stride,
                             padding=padding, method=self.method,
                             activation="none", plan=self.plan_for(name))
        return epi.apply_activation(activation,
                                    y_q.astype(jnp.float32) * q.y_scale)


class _CalibrationPolicy:
    """Records per-layer quant scales from one eager f32 forward.

    Uses the 'lax' reference method (XLA-native, fast on CPU) — the scales
    depend only on value ranges, which every registered method agrees on.
    """

    def __init__(self):
        self.quant: Dict[str, LayerQuant] = {}

    @staticmethod
    def _scale(t) -> float:
        return max(float(jnp.max(jnp.abs(t))), 1e-6) / 127.0

    def tconv(self, x, w, bias=None, *, name: str, stride: int,
              padding: str = "SAME", activation: str = "none"):
        acc = ops.tconv(x, w, bias, stride=stride, padding=padding,
                        method="lax")
        self.quant[name] = LayerQuant(self._scale(x), self._scale(w),
                                      self._scale(acc))
        return epi.apply_activation(activation, acc)


# ---------------------------------------------------------------------------
# Model specs + registry.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RunnerSpec:
    """Everything the runner layer needs to know about one model family.

    ``init(key, **kw) -> (params, specs)`` (the models' existing inits);
    ``forward(params, inputs, options, *, policy)``;
    ``problems(params, options) -> {layer: TConvProblem}``;
    ``input_shape(params, options) -> per-request input shape`` (no batch
    dim).  ``defaults`` declares the legal runner options and their
    values — geometry that is not recoverable from the params.
    """

    name: str
    init: Callable
    forward: Callable
    problems: Callable
    input_shape: Callable
    defaults: Mapping[str, Any] = dataclasses.field(default_factory=dict)


_SPECS: Dict[str, RunnerSpec] = {}


def register_spec(spec: RunnerSpec) -> None:
    _SPECS[spec.name] = spec


def get_spec(name: str) -> RunnerSpec:
    try:
        return _SPECS[name]
    except KeyError:
        raise ValueError(f"unknown runner {name!r}; registered: "
                         f"{sorted(_SPECS)}") from None


def runner_names() -> tuple:
    return tuple(sorted(_SPECS))


# ---------------------------------------------------------------------------
# The runner.
# ---------------------------------------------------------------------------


class GeneratorRunner:
    """One model + params behind the uniform serving contract."""

    def __init__(self, spec: RunnerSpec, params, *,
                 method: str = DEFAULT_METHOD, **options):
        unknown = set(options) - set(spec.defaults)
        if unknown:
            raise TypeError(f"runner {spec.name!r} accepts options "
                            f"{sorted(spec.defaults)}, got {sorted(unknown)}")
        self.spec = spec
        self.params = params
        self.method = method
        self.options = dict(spec.defaults)
        self.options.update(options)
        self._quant: Optional[Dict[str, LayerQuant]] = None
        self._jitted: Dict[tuple, Callable] = {}
        self._jit_fns: Dict[tuple, Callable] = {}
        self._warm: set = set()

    @property
    def name(self) -> str:
        return self.spec.name

    # -- geometry -----------------------------------------------------------

    def tconv_problems(self) -> dict:
        """{layer_name: TConvProblem} — warmup, sweep, and bucketing input."""
        return self.spec.problems(self.params, self.options)

    def input_shape(self) -> tuple:
        """Per-request input shape (no batch dim)."""
        return tuple(self.spec.input_shape(self.params, self.options))

    def input_spec(self, batch: int = 1) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((batch,) + self.input_shape(),
                                    jnp.float32)

    def example_inputs(self, batch: int = 1, seed: int = 0):
        """Synthetic inputs of the right geometry (warmup / calibration)."""
        return jax.random.normal(jax.random.PRNGKey(seed),
                                 (batch,) + self.input_shape(), jnp.float32)

    # -- plans ----------------------------------------------------------------

    def resolve_plans(self, *, batch: int, dtype=jnp.float32,
                      plans: Optional[dict] = None) -> dict:
        """Per-layer tile plans, cache-backed (the generic form of the old
        DCGAN-only ``runtime/steps.resolve_gan_plans``).

        Precedence per layer: explicit ``plans`` entry > autotuner cache
        hit > nothing (trace-time tier lookup / heuristic).  Plan-incapable
        methods skip the cache — only the caller's explicit entries pass
        through (their mistake to make).
        """
        from repro.kernels import registry as kernel_registry

        if not kernel_registry.get(self.method).supports_plan:
            return dict(plans) if plans else {}
        resolved = gan.auto_plans(self.tconv_problems(), batch=batch,
                                  dtype=dtype)
        if plans:
            resolved.update(plans)
        return resolved

    # -- precision ----------------------------------------------------------

    def quant_scales(self) -> Dict[str, LayerQuant]:
        """Calibrated per-layer int8 scales (memoized one-shot PTQ)."""
        if self._quant is None:
            cal = _CalibrationPolicy()
            self.spec.forward(self.params, self.example_inputs(batch=1),
                              self.options, policy=cal)
            self._quant = dict(cal.quant)
        return self._quant

    def policy(self, *, precision: str = "f32",
               plans: Optional[dict] = None):
        _check_precision(precision)
        if precision == "int8":
            return Int8TconvPolicy(quant=self.quant_scales(),
                                   method=self.method, plans=plans)
        return TconvPolicy(method=self.method, plans=plans)

    # -- execution ----------------------------------------------------------

    def apply(self, inputs, *, precision: str = "f32",
              plans: Optional[dict] = None):
        """Eager forward: inputs (B, *input_shape) -> outputs."""
        return self.spec.forward(self.params, inputs, self.options,
                                 policy=self.policy(precision=precision,
                                                    plans=plans))

    def jitted(self, *, batch: int, precision: str = "f32") -> Callable:
        """Memoized jit'd forward for one (batch, precision) bucket.

        Plans are left to the trace-time tier lookup (``ops._auto_plan``)
        so the compile records (key, plan, tier) in
        ``ops.consumed_plans()`` — the attribution the warmup layer and
        its tests read.
        """
        _check_precision(precision)
        key = (int(batch), precision)
        fn = self._jitted.get(key)
        if fn is None:
            policy = self.policy(precision=precision)
            jfn = jax.jit(functools.partial(self.spec.forward,
                                            options=self.options,
                                            policy=policy))

            def fn(x, _jfn=jfn, _key=key):
                try:
                    return _jfn(self.params, x)
                finally:
                    self._warm.add(_key)

            self._jitted[key] = fn
            self._jit_fns[key] = jfn
        return fn

    def lower(self, *, batch: int, precision: str = "f32"):
        """Lower the bucket's jitted forward — the program :meth:`jitted`
        runs — e.g. to count the kernels in ``.compile().as_text()``."""
        self.jitted(batch=batch, precision=precision)
        return self._jit_fns[(int(batch), precision)].lower(
            self.params, self.input_spec(batch))

    def has_compiled(self, *, batch: int, precision: str = "f32") -> bool:
        """Whether the (batch, precision) bucket has executed at least once
        (i.e. a further call is a jit-cache hit) — compile-hit counters."""
        return (int(batch), precision) in self._warm


def make_runner(name: str, *, params=None, key=None, init_kw=None,
                method: str = DEFAULT_METHOD, **options) -> GeneratorRunner:
    """Build a runner by registry name, initializing params if not given."""
    spec = get_spec(name)
    if params is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        params, _ = spec.init(key, **(init_kw or {}))
    return GeneratorRunner(spec, params, method=method, **options)


# ---------------------------------------------------------------------------
# Registrations — the four generator families of the paper's evaluation,
# and StyleGAN2 (modulated convolutions, VALID up-convolutions).
# ---------------------------------------------------------------------------


def _dcgan_forward(params, z, options, *, policy):
    return gan.dcgan_generator(params, z, policy=policy)


def _pix2pix_forward(params, img, options, *, policy):
    return gan.pix2pix_generator(params, img, depth=gan.pix2pix_depth(params),
                                 policy=policy)


def _fsrcnn_forward(params, img, options, *, policy):
    return gan.fsrcnn(params, img, upscale=options["upscale"], policy=policy)


def _styletransfer_forward(params, img, options, *, policy):
    return gan.styletransfer(params, img, policy=policy)


def _stylegan2_forward(params, z, options, *, policy):
    return stylegan2.generator(params, z, policy=policy)


register_spec(RunnerSpec(
    name="dcgan",
    init=gan.init_dcgan_g,
    forward=_dcgan_forward,
    problems=lambda p, opt: gan.dcgan_tconv_problems(p),
    input_shape=lambda p, opt: (p["proj"].shape[0],),
))

register_spec(RunnerSpec(
    name="pix2pix",
    init=gan.init_pix2pix_g,
    forward=_pix2pix_forward,
    problems=lambda p, opt: gan.pix2pix_tconv_problems(p),
    input_shape=lambda p, opt: ((2 ** gan.pix2pix_depth(p),) * 2
                                + (p["e0"].shape[2],)),
))

register_spec(RunnerSpec(
    name="fsrcnn",
    init=gan.init_fsrcnn,
    forward=_fsrcnn_forward,
    problems=lambda p, opt: gan.fsrcnn_tconv_problems(
        p, input_hw=opt["input_hw"], upscale=opt["upscale"]),
    input_shape=lambda p, opt: (opt["input_hw"], opt["input_hw"],
                                p["feat"].shape[2]),
    defaults={"upscale": 3, "input_hw": 16},
))

register_spec(RunnerSpec(
    name="styletransfer",
    init=gan.init_styletransfer,
    forward=_styletransfer_forward,
    problems=lambda p, opt: gan.styletransfer_tconv_problems(
        p, input_hw=opt["input_hw"]),
    input_shape=lambda p, opt: (opt["input_hw"], opt["input_hw"],
                                p["c1"].shape[2]),
    defaults={"input_hw": 32},
))

register_spec(RunnerSpec(
    name="stylegan2",
    init=stylegan2.init,
    forward=_stylegan2_forward,
    problems=lambda p, opt: stylegan2.tconv_problems(p),
    input_shape=lambda p, opt: (p["map0.w"].shape[0],),
))
