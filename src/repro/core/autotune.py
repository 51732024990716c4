"""Empirical block-size + kernel-variant autotuner for the MM2IM kernels.

The paper picks its tile geometry per TCONV configuration with Alg. 1 and
validates the choice over 261 problem configs; the seed port instead ran
one ``plan_blocks`` heuristic everywhere.  This module closes that gap
with a measure-don't-guess loop:

  1. **enumerate** — every legal ``(method, block_oh, block_oc,
     grid_order)`` under the VMEM budget
     (``core/tiling.candidate_plans``) — ``method`` picks between the
     single-buffered kernel and the double-buffered DMA pipeline
     (``kernels/mm2im_db_pallas``), which are bit-identical, so the choice
     is purely empirical;
  2. **prune** — rank candidates by the cost model and keep the top few,
     always including the heuristic default.  When a shipped calibration
     exists for this backend (``core/model_fit.py`` — coefficients fit
     from persisted sweep measurements), ranking uses the fitted
     microsecond predictions and fewer survivors are timed; otherwise
     the datasheet roofline (``core/perf_model.mm2im_estimate`` /
     ``mm2im_db_estimate``, including the overlapped-copy term) orders
     the field;
  3. **measure** — wall-time the survivors **through the kernel registry**
     (``kernels.ops.run_registered`` — Pallas TPU kernels on TPU,
     interpret mode elsewhere), with the same epilogue-splitting contract
     dispatch uses, so the timed program is the program inference runs;
  4. **persist** — store the winner in an on-disk JSON cache keyed by
     ``(TConvProblem, dtype, hw, batch)`` so later processes skip straight
     to the tuned plan.

The returned :class:`~repro.kernels.registry.Plan` is accepted verbatim by
``ops.tconv(..., plan=...)``, ``layers.common.tconv_layer`` and the GAN
models' ``plans=`` mapping — and, because ``ops.tconv`` consults this
cache automatically at trace time (:func:`cached_plan`), a tuned problem
needs **no** explicit ``plans=`` threading at all: tune once, every later
process with the same cache hits the tuned plan.  See docs/AUTOTUNER.md
for the file format, the key schema and the consumption precedence.

Tuning a third-party registry variant needs **no wiring here**: register
the kernel with ``supports_plan=True`` (``kernels/registry.register`` —
see that module's docstring) and ``core/tiling.candidate_plans``
enumerates it, this module measures it through the registry (both f32 and
int8 — specs without native int8 are timed through the dispatcher's
dequant->requant fallback, the program they would actually serve), and
tuned plans carry ``Plan.method`` naming the variant so both ``ops.tconv``
and ``ops.tconv_int8`` dispatch back to it.  Variants with a bespoke
roofline can extend :data:`METHOD_ESTIMATORS`; unknown methods rank with
the single-buffered estimate.

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune_cache.json``.  Below the user cache sits the
read-only **shipped plan table** tier (``core/plan_table.py`` — tables
committed under ``src/repro/data/plans/`` and produced by
``tools/tune_sweep.py``), so a fresh checkout starts from the full-sweep
tuning shipped with the package; full precedence is ``plan=`` > user
cache > shipped table > heuristic.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import time
import warnings
from pathlib import Path
from typing import Iterable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model_fit, tiling
from repro.core.epilogue import Epilogue
from repro.core.maps import TConvProblem
from repro.core.perf_model import (HW, V5E, mm2im_db_estimate,
                                   mm2im_estimate, mm2im_ks_estimate,
                                   mm2im_og_estimate)
from repro.kernels import ops as kernel_ops
from repro.kernels.registry import Plan

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE_PATH = "~/.cache/repro/autotune_cache.json"
# Deliberately still 1 although plans now serialize the v2 fold_batch
# field: bumping would make every existing user cache read as empty
# (_read_disk discards version mismatches).  A pre-fold reader sharing a
# new cache ignores the unknown field and runs the tuned geometry
# unfolded — bit-identical results, possibly suboptimal speed — which is
# the cheaper failure than discarding all prior tuning.  The *shipped
# tables* (immutable release artifacts) do gate the field via their own
# version bump (core/plan_table.py).
_CACHE_VERSION = 1

# method name -> roofline estimator used by the pruning stage.  Methods
# without an entry (third-party variants) rank with the single-buffered
# estimate — measurement, not the model, decides the winner anyway.
# Estimators take (p, batch, *, block_oh, block_oc, bits, grid_order, hw,
# fold_batch) — the plan-v2 ``fold_batch`` kwarg is part of the contract
# since candidates are ranked folded vs grid-batch a priori.
METHOD_ESTIMATORS = {
    "mm2im": mm2im_estimate,
    "mm2im_db": mm2im_db_estimate,
    "mm2im_ks": mm2im_ks_estimate,
    "mm2im_og": mm2im_og_estimate,
}


def default_cache_path() -> Path:
    return Path(os.environ.get(CACHE_ENV, DEFAULT_CACHE_PATH)).expanduser()


def cache_key(p: TConvProblem, *, dtype=jnp.float32, hw: HW = V5E,
              batch: int = 1) -> str:
    """Stable, human-readable cache key for one tuning instance."""
    dt = jnp.dtype(dtype).name
    return (f"tconv:ih{p.ih}:iw{p.iw}:ic{p.ic}:ks{p.ks}:oc{p.oc}"
            f":s{p.stride}:{p.padding}|{dt}|{hw.name}|b{batch}")


@contextlib.contextmanager
def _file_lock(path: Path):
    """Advisory lock serializing a read-merge-replace window on ``path``.

    Best effort: POSIX ``flock`` on a ``.lock`` sidecar; a no-op where
    ``fcntl`` is unavailable (non-POSIX), falling back to atomic-replace
    semantics only.
    """
    try:
        import fcntl
    except ImportError:  # non-POSIX: atomic os.replace is all we have
        yield
        return
    with open(path.with_name(path.name + ".lock"), "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


# Paths already warned about as corrupt — one UserWarning per file per
# process, not one per lookup.  Cleared by reset_shared_caches() (tests).
_WARNED_CORRUPT: set = set()


class PlanCache:
    """On-disk JSON store of tuned plans; safe to share across processes.

    The file holds ``{"version": 1, "entries": {key: {"plan": {...},
    "us": ..., ...}}}``.  Writes are atomic (tmp file + ``os.replace``)
    and merge with the current on-disk entries under an advisory file
    lock, so concurrent tuners sharing one cache lose no keys.  A bad
    cache can never break inference: a missing or version-mismatched file
    reads as empty, and a file that does not parse at all — truncated
    write, disk corruption, stray hand-edit — is **quarantined** to
    ``<path>.corrupt`` with a one-shot ``UserWarning`` naming the file,
    rather than being silently treated as empty forever (the old
    behavior, which hid that all tuned plans had quietly vanished).
    """

    def __init__(self, path: Union[str, Path, None] = None):
        self.path = Path(path).expanduser() if path else default_cache_path()
        self._entries: Optional[dict] = None
        self._loaded_mtime: Optional[float] = None

    # -- storage ------------------------------------------------------------

    def _mtime(self) -> Optional[float]:
        try:
            return self.path.stat().st_mtime_ns
        except OSError:
            return None

    def _read_disk(self) -> dict:
        """Fresh parse of the on-disk entries — no memo, no mtime check.

        Three distinct empty-read cases, deliberately told apart:

        * missing file (or unreadable: permissions) — the normal first-run
          state, silently empty;
        * parses but ``version`` mismatches — a cache written by a
          different schema generation; silently empty by design (see the
          ``_CACHE_VERSION`` note above — the file is *valid*, just not
          ours to consume);
        * does not parse as a JSON object with object ``entries`` —
          corruption.  Quarantined via :meth:`_quarantine` so the bad
          bytes stop shadowing the cache path (the next ``_save`` starts
          a fresh cache) and the operator is warned once instead of
          every tuned plan silently disappearing.
        """
        try:
            text = self.path.read_text()
        except OSError:  # missing (first run) or unreadable: empty cache
            return {}
        try:
            raw = json.loads(text)
            if not isinstance(raw, dict):
                raise ValueError(
                    f"top-level JSON is {type(raw).__name__}, not an object")
        except ValueError as err:
            self._quarantine(err)
            return {}
        if raw.get("version") != _CACHE_VERSION:
            return {}
        entries = raw.get("entries", {})
        if not isinstance(entries, dict):
            self._quarantine(ValueError(
                f"'entries' is {type(entries).__name__}, not an object"))
            return {}
        return dict(entries)

    def _quarantine(self, err: Exception) -> None:
        """Move a corrupt cache aside to ``<path>.corrupt`` and warn once.

        ``os.replace`` keeps the bad bytes for post-mortem (restore the
        file after fixing it, or re-run ``tools/tune_sweep.py``) while
        clearing the cache path for fresh writes.  The warning is one-shot
        per path per process so a hot lookup path does not spam.
        """
        dest = self.path.with_name(self.path.name + ".corrupt")
        try:
            os.replace(self.path, dest)
            action = f"quarantined to {dest}"
        except OSError as mv_err:  # read-only fs etc.: warn anyway
            action = f"could not quarantine to {dest} ({mv_err})"
        key = str(self.path)
        if key not in _WARNED_CORRUPT:
            _WARNED_CORRUPT.add(key)
            warnings.warn(
                f"plan cache {self.path} is corrupt ({err}); {action}. "
                "Tuned plans from it are unavailable — restore the file "
                "or re-run tools/tune_sweep.py to regenerate.",
                UserWarning, stacklevel=3)

    def _load(self) -> dict:
        # Re-read when the file changed on disk (another PlanCache instance
        # or another process tuned since) — one stat() per lookup, so the
        # long-lived shared_cache() instance behind automatic consumption
        # sees same-process tune-then-train writes too.
        mtime = self._mtime()
        if self._entries is None or mtime != self._loaded_mtime:
            self._loaded_mtime = mtime
            self._entries = self._read_disk()
        return self._entries

    def _save(self, dirty: dict) -> None:
        # Merge only the keys *this write actually changed* over the
        # current on-disk entries: another process may have tuned other
        # keys (or re-tuned ones we merely hold memoized) between our last
        # _load() and now, and replaying our whole memo would clobber
        # them.  Last writer wins per key, not per file.  The advisory
        # lock serializes the read-merge-replace window itself (two
        # unserialized merges could each miss the other's key); os.replace
        # additionally keeps the swap atomic for lock-less readers and
        # non-POSIX writers.
        self.path.parent.mkdir(parents=True, exist_ok=True)
        with _file_lock(self.path):
            merged = self._read_disk()
            merged.update(dirty)
            self._entries = merged
            tmp = self.path.with_name(self.path.name + f".tmp{os.getpid()}")
            tmp.write_text(json.dumps(
                {"version": _CACHE_VERSION, "entries": merged}, indent=1,
                sort_keys=True))
            # Record the *tmp* file's mtime (os.replace preserves it as
            # the destination's): statting self.path after the replace
            # would race a concurrent writer landing in between,
            # permanently memoizing our entries under *their* mtime and
            # hiding their keys.
            tmp_mtime = tmp.stat().st_mtime_ns
            os.replace(tmp, self.path)
            self._loaded_mtime = tmp_mtime

    # -- API ----------------------------------------------------------------

    def get(self, key: str) -> Optional[Plan]:
        e = self._load().get(key)
        return Plan.from_json(e["plan"]) if e else None

    def get_entry(self, key: str) -> Optional[dict]:
        e = self._load().get(key)
        return dict(e) if e else None

    def put(self, key: str, plan: Plan, meta: Optional[dict] = None) -> None:
        entry = {"plan": plan.to_json(), "created": time.time()}
        if meta:
            entry.update(meta)
        self._save({key: entry})

    def keys(self) -> Sequence[str]:
        return tuple(self._load())

    def __len__(self) -> int:
        return len(self._load())


@dataclasses.dataclass(frozen=True)
class TuningResult:
    """What :func:`autotune_result` learned about one problem."""

    key: str
    plan: Plan
    us: float                 # measured time of the winning plan
    default_plan: Plan
    default_us: float         # measured time of the heuristic default
    n_candidates: int         # legal plans enumerated
    n_measured: int           # survivors actually timed
    from_cache: bool

    @property
    def speedup_vs_default(self) -> float:
        """Tuned-vs-heuristic ratio; NaN when either time is unknown.

        Cache-hit results replayed from an entry that never recorded
        timings (e.g. imported from a shipped table) have ``us`` /
        ``default_us`` of NaN — reporting 0.0 here would read as a 0x
        slowdown, so "unknown" stays unknown.
        """
        if math.isnan(self.us) or math.isnan(self.default_us):
            return float("nan")
        return self.default_us / max(self.us, 1e-9)


def _rand_inputs(p: TConvProblem, batch: int, dtype):
    rng = np.random.default_rng(0)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        x = rng.integers(-128, 128, (batch, p.ih, p.iw, p.ic)).astype(dtype)
        w = rng.integers(-128, 128, (p.ks, p.ks, p.oc, p.ic)).astype(dtype)
    else:
        x = rng.standard_normal((batch, p.ih, p.iw, p.ic)).astype(dtype)
        w = (rng.standard_normal((p.ks, p.ks, p.oc, p.ic)) * 0.1).astype(dtype)
    return jnp.asarray(x), jnp.asarray(w)


def measure_epilogue(p: TConvProblem, dtype) -> tuple:
    """Representative ``(bias, out_scale)`` for timing one candidate.

    Integer dtypes get a per-tensor requant scale and an int32 bias so the
    measured program includes the PPU epilogue (int32 accum -> requant ->
    int8 store) that ``ops.tconv_int8`` will actually run; without them
    the tuner would rank int8 plans on an int32-output kernel — a
    different program with different store traffic.  Float dtypes keep
    the plain no-epilogue forward (bias/activation fusion costs are
    epilogue-invariant across plans there).
    """
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        rng = np.random.default_rng(1)
        bias = jnp.asarray(rng.integers(-8, 8, (p.oc,)), jnp.int32)
        return bias, 0.05
    return None, None


def measure_plan(p: TConvProblem, plan: Plan, *, batch: int = 1,
                 dtype=jnp.float32, repeats: int = 3,
                 warmup: int = 1) -> float:
    """Median wall-time (us) of the plan's kernel variant under the plan.

    ``plan.method`` names the registered method to time (``None`` means
    the single-buffered default); the candidate runs through the registry
    itself (``kernels.ops.run_registered``) with the dispatcher's
    epilogue-splitting contract, so any registered variant is measurable
    with zero wiring and the timed program matches what dispatch executes
    — including the dequant->requant fallback for variants without native
    int8.  Integer dtypes are timed with the requant epilogue attached
    (:func:`measure_epilogue`).
    """
    x, w = _rand_inputs(p, batch, dtype)
    method = plan.method or "mm2im"
    bias, out_scale = measure_epilogue(p, dtype)
    ep = Epilogue(bias=bias, out_scale=out_scale)
    # Strip the method (it is dispatched explicitly above) but keep the
    # fold_batch knob — a folded candidate must be timed folded.
    geom = Plan(plan.block_oh, plan.block_oc, plan.grid_order,
                fold_batch=plan.fold_batch)

    fn = jax.jit(lambda xx, ww: kernel_ops.run_registered(
        method, xx, ww, stride=p.stride, padding=p.padding, epilogue=ep,
        plan=geom))
    for _ in range(warmup):
        jax.block_until_ready(fn(x, w))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x, w))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def _bits(dtype) -> int:
    return jnp.dtype(dtype).itemsize * 8


def default_plan(p: TConvProblem, *, batch: int = 1, dtype=jnp.float32,
                 hw: HW = V5E) -> Plan:
    """The seed heuristic's choice, as an explicit Plan."""
    tp = tiling.plan(p, batch=batch, bits=_bits(dtype), hw=hw)
    return Plan(tp.block_oh, tp.block_oc, tp.grid_order, tp.method,
                tp.fold_batch)


def autotune_result(
    p: TConvProblem,
    *,
    batch: int = 1,
    dtype=jnp.float32,
    hw: HW = V5E,
    cache: Union[PlanCache, str, Path, None] = None,
    max_measure: Optional[int] = None,
    repeats: int = 3,
    force: bool = False,
    fit="auto",
) -> TuningResult:
    """Enumerate -> prune -> measure -> persist; full diagnostics returned.

    ``cache`` may be a :class:`PlanCache`, a path, or None (default
    location).  ``force=True`` re-measures even on a cache hit.

    ``fit`` selects the pruning model: ``"auto"`` (default) uses the
    shipped per-backend calibration (``core/model_fit.shipped_fit``) when
    one exists, an explicit :class:`~repro.core.model_fit.FittedHW` uses
    that, and None forces the uncalibrated datasheet roofline.
    ``max_measure=None`` adapts to the model's trustworthiness: 4 timed
    survivors under a calibration, 6 under the bare roofline — the whole
    point of fitting coefficients from sweep measurements is that the
    a-priori ranking stops discarding true winners (the recorded sb/db
    and fold/grid misranks), so fewer candidates need wall-timing.
    """
    if not isinstance(cache, PlanCache):
        cache = PlanCache(cache)
    key = cache_key(p, dtype=dtype, hw=hw, batch=batch)
    dflt = default_plan(p, batch=batch, dtype=dtype, hw=hw)

    if not force:
        hit = cache.get_entry(key)
        if hit is not None:
            # Entries without timings (imported/hand-written) report NaN,
            # not 0.0 — speedup_vs_default then stays NaN instead of
            # masquerading as a 0x slowdown.
            return TuningResult(
                key=key, plan=Plan.from_json(hit["plan"]),
                us=float(hit.get("us", float("nan"))), default_plan=dflt,
                default_us=float(hit.get("default_us", float("nan"))),
                n_candidates=int(hit.get("n_candidates", 0)),
                n_measured=0, from_cache=True)

    bits = _bits(dtype)
    cands = tiling.candidate_plans(p, batch=batch, bits=bits, hw=hw)
    plans = [Plan(c.block_oh, c.block_oc, c.grid_order, c.method,
                  c.fold_batch)
             for c in cands]
    if dflt not in plans:
        plans.append(dflt)

    # Prune by the model — the measurement-calibrated one when available
    # (core/model_fit.py), the datasheet roofline otherwise (overlapped-
    # copy term + MXU tile quantization included, so single- vs
    # double-buffered and folded vs grid-batch candidates all rank
    # against each other a priori); keep the default in the field so the
    # measurement is always at least a default-vs-challenger comparison.
    if fit == "auto":
        fit = model_fit.shipped_fit()
    if max_measure is None:
        max_measure = 4 if fit is not None else 6

    def score(pl: Plan) -> float:
        if fit is not None:
            return fit.predict_us(p, pl, batch=batch, bits=bits, hw=hw)
        est = METHOD_ESTIMATORS.get(pl.method or "mm2im", mm2im_estimate)
        return est(p, batch, block_oh=pl.block_oh, block_oc=pl.block_oc,
                   bits=bits, grid_order=pl.grid_order, hw=hw,
                   fold_batch=pl.fold_batch).t_overlapped

    # Up to max_measure survivors, always including the default: when the
    # model already ranks the default on top, the remaining slots go to
    # challengers instead of shrinking the field to a self-comparison.
    # On a chip, a plan its compiler refuses is skipped, never timed as a
    # loser (tiling.plan_compiles; checked in rank order, so only plans
    # that would be timed are compiled).
    survivors = []
    for pl in sorted(plans, key=score):
        if len(survivors) >= max(max_measure, 1):
            break
        if pl == dflt or tiling.plan_compiles(p, pl, batch=batch, bits=bits):
            survivors.append(pl)
    if dflt not in survivors:
        survivors = survivors[:max(max_measure - 1, 1)] + [dflt]

    timed = {pl: measure_plan(p, pl, batch=batch, dtype=dtype,
                              repeats=repeats) for pl in survivors}
    winner = min(timed, key=timed.get)
    result = TuningResult(
        key=key, plan=winner, us=timed[winner], default_plan=dflt,
        default_us=timed[dflt], n_candidates=len(plans),
        n_measured=len(survivors), from_cache=False)
    cache.put(key, winner, meta={
        "us": result.us, "default_us": result.default_us,
        "default_plan": dflt.to_json(), "n_candidates": result.n_candidates,
        # Measurement conditions, per entry — tools/tune_sweep.py --export
        # derives a table's provenance from these rather than trusting
        # whatever flags the (possibly later, possibly different) export
        # invocation happened to use.
        "backend": jax.default_backend(), "repeats": repeats,
        "jax": jax.__version__,
        # Whether a fitted calibration pruned the field (model_fit) —
        # distinguishes "ranked by measured coefficients" entries from
        # datasheet-roofline ones when auditing a cache.
        "calibrated": fit is not None,
    })
    return result


def autotune(p: TConvProblem, **kw) -> Plan:
    """Tuned :class:`Plan` for ``p`` (cache-backed). See autotune_result."""
    return autotune_result(p, **kw).plan


# ---------------------------------------------------------------------------
# Automatic consumption — the read-only fast path used by ops.tconv.
# ---------------------------------------------------------------------------

_SHARED_CACHES: dict = {}  # resolved path -> PlanCache (per-process memo)


def shared_cache(path: Union[str, Path, None] = None) -> PlanCache:
    """Process-wide :class:`PlanCache` for ``path`` (default location).

    ``ops.tconv`` consults the cache once per jit trace; sharing one
    instance per path means the JSON file is parsed once per process, not
    once per trace.
    """
    resolved = str(Path(path).expanduser() if path else default_cache_path())
    c = _SHARED_CACHES.get(resolved)
    if c is None:
        c = _SHARED_CACHES[resolved] = PlanCache(resolved)
    return c


def reset_shared_caches() -> None:
    """Drop the per-process cache memo (tests; after external cache edits)."""
    _SHARED_CACHES.clear()
    _WARNED_CORRUPT.clear()


# Tier names recorded by kernels.ops.consumed_plans() — who served a hit.
TIER_USER_CACHE = "user-cache"
TIER_SHIPPED = "shipped-table"


def lookup_plan(p: TConvProblem, *, dtype=jnp.float32, batch: int = 1,
                hw: HW = V5E,
                cache: Union[PlanCache, str, Path, None] = None
                ) -> Optional[Tuple[Plan, str]]:
    """Tuned ``(plan, tier)`` for ``p``, or None; never measures.

    This is the lookup behind automatic plan consumption (``ops.tconv``
    with no ``plan=``).  Precedence within the read path: the user's
    on-disk cache (:data:`TIER_USER_CACHE`) beats the shipped per-backend
    table (:data:`TIER_SHIPPED`, ``core/plan_table.py``); a miss in both
    returns None and the caller falls back to the ``plan_blocks``
    heuristic.  A pure read either way.

    Forward compatibility: an entry whose ``Plan.method`` names a kernel
    that is *not* in this checkout's registry (e.g. a table exported by a
    newer release with an extra family) is skipped with a warning and the
    lookup falls through to the next tier — a stale plan must degrade to
    the heuristic, never fail dispatch.
    """
    if not isinstance(cache, PlanCache):
        cache = shared_cache(cache)
    key = cache_key(p, dtype=dtype, hw=hw, batch=batch)
    plan = cache.get(key)
    if plan is not None:
        if _method_registered(plan):
            return plan, TIER_USER_CACHE
        warnings.warn(
            f"autotune cache entry {key!r} selects unregistered kernel "
            f"method {plan.method!r}; ignoring it (re-tune or upgrade to "
            f"a build that provides the method)", stacklevel=2)
    from repro.core.plan_table import shipped_table

    table = shipped_table()
    if table is not None:
        plan = table.get(key)
        if plan is not None:
            if _method_registered(plan):
                return plan, TIER_SHIPPED
            warnings.warn(
                f"shipped plan table entry {key!r} selects unregistered "
                f"kernel method {plan.method!r}; ignoring it (table "
                f"exported by a newer build?)", stacklevel=2)
    return None


def _method_registered(plan: Plan) -> bool:
    """True when the plan's kernel variant exists in this checkout."""
    from repro.kernels import registry as kernel_registry

    return not plan.method or plan.method in kernel_registry.names()


def cached_plan(p: TConvProblem, *, dtype=jnp.float32, batch: int = 1,
                hw: HW = V5E,
                cache: Union[PlanCache, str, Path, None] = None
                ) -> Optional[Plan]:
    """Tuned plan for ``p`` from any read tier (:func:`lookup_plan`)."""
    hit = lookup_plan(p, dtype=dtype, batch=batch, hw=hw, cache=cache)
    return hit[0] if hit else None


def autotune_sweep(
    problems: Iterable[TConvProblem],
    *,
    dtypes: Sequence = (jnp.float32, jnp.int8),
    batches: Sequence[int] = (1,),
    hw: HW = V5E,
    cache: Union[PlanCache, str, Path, None] = None,
    **kw,
) -> list:
    """Tune the cross product problems x dtypes x batches; return results.

    This is how the cache gets its int8 (the paper's precision) and
    batch>1 coverage so the GAN training/serve paths hit tuned plans out
    of the box — e.g.::

        autotune_sweep(gan.dcgan_tconv_problems(params).values(),
                       dtypes=(jnp.float32, jnp.int8), batches=(1, 8))

    Extra kwargs flow to :func:`autotune_result` (``max_measure``,
    ``repeats``, ``force``, ...).
    """
    if not isinstance(cache, PlanCache):
        cache = PlanCache(cache) if cache is not None else shared_cache()
    results = []
    for p in problems:
        for dtype in dtypes:
            for batch in batches:
                results.append(autotune_result(
                    p, batch=batch, dtype=dtype, hw=hw, cache=cache, **kw))
    return results
