"""Resilient serving: deadlines, shedding, a degradation ladder, breakers.

The serve stack (DESIGN.md §9) was built for the sunny day: every batch
forward succeeds, every queue drains.  This module is the rainy-day half
— the paper's whole premise is *resource-constrained edge devices*, where
overload, stragglers and partial failure are the norm — and it follows
the GANAX split (PAPERS.md): all irregular control work (retry, rung
selection, breaker state) lives here, outside the dense kernel hot path,
which stays exactly as fast as before when nothing is failing.

Four pieces, threaded through ``serve/server.py``:

* **Deadlines + bounded queues.**  ``submit(deadline_s=...)`` attaches an
  absolute deadline; expired requests fail fast with
  :class:`DeadlineExceeded` *before* batches form instead of occupying a
  tuned batch slot (``batcher.Batcher.pop_expired``).  Per-bucket queues
  are capped by ``max_queue_depth``; the overflow is shed at admission
  with :class:`~repro.serve.bucketing.QueueFullError` and counted in the
  bucket's ``shed`` stat.
* **Degradation ladder.**  A failing batch is retried once with jittered
  backoff when the fault looks transient
  (``runtime/fault_tolerance.jittered_backoff``), then re-dispatched down
  the rungs: tuned plans -> explicit *heuristic* plans (the
  ``plan_blocks`` default — bypasses whatever tuned state may be the
  culprit) -> [int8 buckets only: the tuned **f32** forward — the
  precision rung] -> the ``'lax'`` reference
  (``kernels.ops.tconv_reference``: no Pallas, no plans).  The rung that
  served each batch lands in the bucket's ``rungs`` stat, so degraded
  traffic is visible, not silent.
* **Circuit breaker.**  K consecutive *fully-failed* batches (every rung
  exhausted) trip the bucket's breaker: open buckets shed at admission
  (:class:`~repro.serve.bucketing.CircuitOpenError`) instead of queueing
  work that will fail, and after ``cooldown_s`` one half-open probe is
  admitted — success closes the breaker, failure re-opens it.
* **Fault injection.**  :class:`FaultInjector` is the seeded,
  deterministic chaos hook the server accepts (``fault_injector=``):
  fail-every-Nth-batch (transient, exercises retry + ladder),
  raise-in-dispatch (non-transient, from inside the jitted call),
  per-batch latency spikes, poison-one-bucket (all rungs fail — drives
  the breaker), drain-loop crash (outside the batch guard — drives the
  supervisor), plus composition with the training-side
  ``runtime.fault_tolerance.StragglerSimulator``.  Everything keys off
  the global batch index, so a replayed request sequence injects the
  same faults.

Drain-loop *supervision* itself lives in ``serve/server.py`` (the
supervisor restarts a crashed drain thread and fails the crashed
iteration's in-flight requests); this module supplies the typed crash it
is tested with.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.fault_tolerance import StragglerSimulator, jittered_backoff
from repro.serve.bucketing import (AdmissionError, CircuitOpenError,
                                   QueueFullError, ShedError)
from repro.serve.telemetry import phase

__all__ = [
    "AdmissionError", "CircuitBreaker", "CircuitOpenError", "DeadlineExceeded",
    "DegradationLadder", "DispatchFault", "DrainLoopCrash", "FaultInjector",
    "InjectedFault", "LadderExhausted", "PoisonedBucket", "QueueFullError",
    "ResilienceConfig", "RUNG_F32", "RUNG_HEURISTIC", "RUNG_LAX",
    "RUNG_TUNED", "ShedError", "TransientFault", "is_transient",
    "ladder_rungs",
]


# ---------------------------------------------------------------------------
# Typed failures.
# ---------------------------------------------------------------------------


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before a batch executed it."""


class TransientFault(RuntimeError):
    """A fault worth retrying once in place (backoff + same rung)."""


class InjectedFault(TransientFault):
    """Raised by :class:`FaultInjector` (fail-Nth-batch): transient, so it
    exercises the retry-then-descend path."""


class DispatchFault(RuntimeError):
    """Raised by :class:`FaultInjector` from *inside* the dispatch call
    (raise-in-dispatch): non-transient, so the ladder descends without a
    retry — the shape of a real kernel/lowering failure."""


class PoisonedBucket(RuntimeError):
    """Raised by :class:`FaultInjector` on every rung of a poisoned
    bucket: the persistent-failure shape that trips the breaker."""


class DrainLoopCrash(RuntimeError):
    """Raised by :class:`FaultInjector` *outside* the per-batch guard:
    kills the drain thread, which is the supervisor's job to survive."""


class LadderExhausted(RuntimeError):
    """Every rung (and the transient retry) failed for this batch.  The
    ``__cause__`` chain carries the last rung's error."""


def is_transient(err: BaseException) -> bool:
    """Whether a batch-execution fault deserves one in-place retry.

    :class:`TransientFault` (and subclasses — injected faults included)
    plus the OS-level hiccups a busy edge box actually throws
    (``OSError``: DMA timeouts, interconnect resets surfaced as errno).
    Everything else — shape errors, lowering failures, NaN guards — is
    assumed deterministic: retrying the identical program wastes the
    deadline budget, so the ladder descends immediately.
    """
    return isinstance(err, (TransientFault, OSError))


# ---------------------------------------------------------------------------
# Configuration.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Knobs for the resilient serve path (``TconvServer(resilience=...)``).

    ``max_queue_depth`` / ``default_deadline_s`` default to None —
    unbounded queues and no deadline, the pre-ISSUE-10 behavior — so
    existing callers see identical semantics until they opt in.
    """

    max_queue_depth: Optional[int] = None   # per-bucket queue cap
    default_deadline_s: Optional[float] = None  # applied when submit() has none
    breaker_threshold: int = 3              # K consecutive failures -> open
    breaker_cooldown_s: float = 1.0         # open -> half-open probe delay
    retry_transient: bool = True            # one in-place retry per rung
    backoff_base_s: float = 0.01
    backoff_jitter: float = 0.5
    seed: int = 0                           # backoff jitter rng


# ---------------------------------------------------------------------------
# Circuit breaker (one per bucket; mutated under the server lock).
# ---------------------------------------------------------------------------

BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half-open"


class CircuitBreaker:
    """Closed -> open after K consecutive batch failures -> half-open probe.

    * **closed**: traffic flows; each fully-failed batch increments the
      consecutive-failure count, any success resets it.
    * **open**: admission sheds (``CircuitOpenError``) until
      ``cooldown_s`` has passed.
    * **half-open**: the first ``allow()`` after the cooldown admits one
      probe; further admissions shed until the probe's batch resolves.
      Probe success closes the breaker, failure re-opens it (and restarts
      the cooldown).

    Time is injected for determinism; the server passes
    ``time.monotonic()``.
    """

    def __init__(self, *, threshold: int = 3, cooldown_s: float = 1.0):
        self.threshold = max(int(threshold), 1)
        self.cooldown_s = float(cooldown_s)
        self.state = BREAKER_CLOSED
        self.consecutive_failures = 0
        self.trips = 0                      # closed/half-open -> open edges
        self._cooldown_until = 0.0
        self._probe_in_flight = False

    def allow(self, now: float) -> bool:
        """Admission check; transitions open -> half-open on first call
        past the cooldown (and claims the single probe slot)."""
        if self.state == BREAKER_CLOSED:
            return True
        if self.state == BREAKER_OPEN:
            if now < self._cooldown_until:
                return False
            self.state = BREAKER_HALF_OPEN
            self._probe_in_flight = True
            return True
        # half-open: one probe at a time
        if self._probe_in_flight:
            return False
        self._probe_in_flight = True
        return True

    def record_success(self) -> None:
        self.consecutive_failures = 0
        self._probe_in_flight = False
        self.state = BREAKER_CLOSED

    def record_failure(self, now: float) -> bool:
        """Count one fully-failed batch; returns True when this failure
        trips (or re-trips) the breaker open."""
        self.consecutive_failures += 1
        tripping = (self.state == BREAKER_HALF_OPEN
                    or (self.state == BREAKER_CLOSED
                        and self.consecutive_failures >= self.threshold))
        if tripping:
            self.state = BREAKER_OPEN
            self._cooldown_until = now + self.cooldown_s
            self._probe_in_flight = False
            self.trips += 1
        return tripping

    def snapshot(self) -> dict:
        return {"state": self.state, "trips": self.trips,
                "consecutive_failures": self.consecutive_failures}


# ---------------------------------------------------------------------------
# Degradation ladder.
# ---------------------------------------------------------------------------

RUNG_TUNED = "tuned"          # the normal path: tuned plans, asked precision
RUNG_HEURISTIC = "heuristic"  # explicit plan_blocks plans: no tuned state
RUNG_F32 = "f32"              # precision rung (int8 buckets): tuned f32 path
RUNG_LAX = "lax"              # ops.tconv_reference: no Pallas, no plans


def ladder_rungs(precision: str) -> Tuple[str, ...]:
    """Rung order for one bucket precision, top (fastest) first."""
    if precision == "int8":
        return (RUNG_TUNED, RUNG_HEURISTIC, RUNG_F32, RUNG_LAX)
    return (RUNG_TUNED, RUNG_HEURISTIC, RUNG_LAX)


def heuristic_plans(runner, *, batch: int, precision: str) -> dict:
    """Explicit ``plan_blocks`` defaults for every runner layer.

    The heuristic rung cannot just "disable the plan cache": the shared
    dispatcher's inner jit is keyed by shapes + static plan, so a
    ``plan=None`` trace of a problem another forward already compiled
    replays the *tuned* program without re-consulting the tiers.  Passing
    the heuristic geometry as explicit per-layer plans makes the rung a
    genuinely different static key — guaranteed to re-trace without the
    tuned state.
    """
    from repro.core.autotune import default_plan

    dtype = jnp.int8 if precision == "int8" else jnp.float32
    return {name: default_plan(prob, batch=batch, dtype=dtype)
            for name, prob in runner.tconv_problems().items()}


class _ReferencePolicy:
    """Ladder bottom: every TCONV through ``ops.tconv_reference`` (f32)."""

    def tconv(self, x, w, bias=None, *, name: str, stride: int,
              padding: str = "SAME", activation: str = "none"):
        from repro.kernels import ops

        return ops.tconv_reference(x, w, bias, stride=stride,
                                   padding=padding, activation=activation)


class DegradationLadder:
    """Per-runner memo of compiled rung forwards.

    Rung forwards are built lazily (a healthy server never compiles the
    lax rung) and memoized per ``(rung, batch, precision)`` — a rung that
    rescued one batch serves the next failure from the jit cache.
    """

    def __init__(self, runner):
        self.runner = runner
        self._fns: Dict[tuple, Callable] = {}
        self._warm: set = set()  # built-here rungs' keys that have run
        self._lock = threading.Lock()

    def rungs(self, precision: str) -> Tuple[str, ...]:
        return ladder_rungs(precision)

    def fn(self, rung: str, *, batch: int, precision: str) -> Callable:
        key = (rung, int(batch), precision)
        with self._lock:
            f = self._fns.get(key)
        if f is None:
            f = self._build(rung, batch=batch, precision=precision)
            with self._lock:
                f = self._fns.setdefault(key, f)
        return f

    def compiled(self, rung: str, *, batch: int, precision: str) -> bool:
        """Whether the rung has run at this batch, so that a call of it
        is a jit-cache hit."""
        if rung == RUNG_TUNED:
            return self.runner.has_compiled(batch=batch, precision=precision)
        if rung == RUNG_F32:
            return self.runner.has_compiled(batch=batch, precision="f32")
        return (rung, int(batch), precision) in self._warm

    def _build(self, rung: str, *, batch: int, precision: str) -> Callable:
        r = self.runner
        if rung == RUNG_TUNED:
            return r.jitted(batch=batch, precision=precision)
        if rung == RUNG_F32:
            # Precision rung: serve the int8 bucket's requests through the
            # tuned f32 forward.  Both policies produce outputs in the
            # same (dequantized) domain, so a row is a valid — merely
            # higher-precision — response.
            return r.jitted(batch=batch, precision="f32")
        if rung == RUNG_HEURISTIC:
            policy = r.policy(precision=precision,
                              plans=heuristic_plans(r, batch=batch,
                                                    precision=precision))
        elif rung == RUNG_LAX:
            policy = _ReferencePolicy()
        else:
            raise ValueError(f"unknown ladder rung {rung!r}")
        jfn = jax.jit(functools.partial(r.spec.forward, options=r.options,
                                        policy=policy))
        key = (rung, int(batch), precision)

        def fn(x):
            out = jfn(r.params, x)
            self._warm.add(key)
            return out

        return fn


# ---------------------------------------------------------------------------
# Fault injection.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FaultInjector:
    """Seeded, deterministic chaos hook for ``TconvServer``.

    All triggers key off the server's global batch index (1-based,
    assigned in execution order), so a replayed request sequence injects
    the same faults; the only randomness (straggler stalls) is seeded.
    Targeting: ``fail_nth_batch`` and ``raise_in_dispatch_nth`` fire only
    on the *tuned* rung (lower rungs are the recovery under test);
    ``poison_bucket`` fires on every rung of matching buckets (the
    persistent failure that must trip the breaker).

    Injection counts are kept in ``injected`` (a plain dict) and surfaced
    by ``server.stats()['fault_injection']``.
    """

    fail_nth_batch: Optional[int] = None      # every Nth: InjectedFault
    raise_in_dispatch_nth: Optional[int] = None  # every Nth: DispatchFault
    spike_every: Optional[int] = None         # every Nth: sleep(spike_s)
    spike_s: float = 0.05
    poison_bucket: Optional[str] = None       # substring of str(BucketKey)
    crash_drain_at_batch: Optional[int] = None  # once, outside the guard
    straggler: Optional[StragglerSimulator] = None
    seed: int = 0
    injected: Dict[str, int] = dataclasses.field(default_factory=dict)
    _crashed: bool = dataclasses.field(default=False, repr=False)

    def _count(self, what: str) -> None:
        self.injected[what] = self.injected.get(what, 0) + 1

    def maybe_crash(self, batch_index: int) -> None:
        """Called by ``serve_once`` outside the per-batch guard — a raise
        here escapes the drain loop (exactly once)."""
        if (self.crash_drain_at_batch is not None and not self._crashed
                and batch_index >= self.crash_drain_at_batch):
            self._crashed = True
            self._count("drain_crash")
            raise DrainLoopCrash(
                f"injected drain-loop crash at batch {batch_index}")

    def before_batch(self, bucket: str, batch_index: int, *, rung: str,
                     attempt: int) -> None:
        """Called before each execution attempt; may sleep or raise."""
        if rung == RUNG_TUNED and attempt == 0:
            if self.straggler is not None and \
                    self.straggler.maybe_stall(batch_index):
                self._count("stall")
            if (self.spike_every is not None
                    and batch_index % self.spike_every == 0):
                self._count("spike")
                time.sleep(self.spike_s)
        if self.poison_bucket is not None and self.poison_bucket in bucket:
            self._count("poison")
            raise PoisonedBucket(
                f"injected poison in bucket {bucket} "
                f"(batch {batch_index}, rung {rung})")
        if (self.fail_nth_batch is not None and rung == RUNG_TUNED
                and batch_index % self.fail_nth_batch == 0):
            self._count("fail")
            raise InjectedFault(
                f"injected transient fault at batch {batch_index} "
                f"(attempt {attempt})")

    def wrap(self, fn: Callable, bucket: str, batch_index: int, *,
             rung: str, attempt: int) -> Callable:
        """Wrap one execution attempt: raise-in-dispatch surfaces the
        fault from *inside* the call, where a real kernel failure would."""
        if (self.raise_in_dispatch_nth is not None and rung == RUNG_TUNED
                and batch_index % self.raise_in_dispatch_nth == 0):
            def raising(x, _n=batch_index):
                self._count("dispatch_raise")
                raise DispatchFault(
                    f"injected dispatch failure at batch {_n}")
            return raising
        return fn

    def stats(self) -> dict:
        out = dict(self.injected)
        if self.straggler is not None:
            out["straggler_stalls"] = self.straggler.stalls
        return out


# ---------------------------------------------------------------------------
# Ladder execution (called by the server with the batch already padded).
# ---------------------------------------------------------------------------


def run_ladder(ladder: DegradationLadder, xs, *, bucket: str, batch: int,
               precision: str, batch_index: int,
               config: ResilienceConfig,
               injector: Optional[FaultInjector] = None,
               rng: Optional[np.random.Generator] = None,
               sleep: Callable[[float], None] = time.sleep,
               stats=None) -> Tuple[np.ndarray, str, int]:
    """Execute one batch down the ladder; ``(output, rung, retries)``.

    Per rung: one attempt, plus one backoff-jittered retry when the fault
    is transient (``is_transient``) and retries are enabled.  Exhausting
    every rung raises :class:`LadderExhausted` chained onto the last
    rung's error — the server fails the batch's requests with it and
    feeds the breaker.  The copy in, each call and the copy out are the
    ``serve.put``, ``serve.dispatch`` and ``serve.fetch`` phases
    (``serve/telemetry.py``), counted in ``stats`` when it is given.
    """
    retries = 0
    last: Optional[BaseException] = None
    with phase("serve.put", stats, batch=batch_index):
        x_dev = jnp.asarray(xs)
    for rung in ladder.rungs(precision):
        try:
            fn = ladder.fn(rung, batch=batch, precision=precision)
        except Exception as err:  # building/compiling the rung itself failed
            last = err
            continue
        for attempt in (0, 1):
            try:
                with phase("serve.dispatch", stats, batch=batch_index,
                           rung=rung, attempt=attempt,
                           compiled=ladder.compiled(rung, batch=batch,
                                                    precision=precision)):
                    if injector is not None:
                        injector.before_batch(bucket, batch_index, rung=rung,
                                              attempt=attempt)
                        call = injector.wrap(fn, bucket, batch_index,
                                             rung=rung, attempt=attempt)
                    else:
                        call = fn
                    out = call(x_dev)
                with phase("serve.fetch", stats, batch=batch_index):
                    return np.asarray(out), rung, retries
            except Exception as err:  # noqa: BLE001 — every rung may fail
                last = err
                if (attempt == 0 and config.retry_transient
                        and is_transient(err)):
                    retries += 1
                    sleep(jittered_backoff(attempt,
                                           base_s=config.backoff_base_s,
                                           jitter=config.backoff_jitter,
                                           rng=rng))
                    continue
                break  # next rung
    raise LadderExhausted(
        f"bucket {bucket}: every ladder rung failed for batch "
        f"{batch_index} (last rung error: {last!r})") from last
