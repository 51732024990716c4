"""The in-process TCONV model server: admission -> batcher -> jit cache.

``TconvServer`` owns a set of named :class:`GeneratorRunner`s and serves
single-sample requests against them:

    server = TconvServer({"dcgan": make_runner("dcgan", ...)})
    server.warmup()                       # plan-table-warmed compiles
    with server:                          # background drain thread
        req = server.submit("dcgan", z, precision="int8", deadline_s=0.5)
        img = req.result(timeout=5)

Dataflow per request: :func:`bucketing.snap` validates the input and
picks the tuned-batch bucket (memoized per ``(model, shape, precision)``
so admission does not re-stat the plan cache per request); the
:class:`batcher.Batcher` queues it under the wait-or-flush policy; the
drain loop pops due batches, pads partials with zeros up to the bucket's
target batch (the tuned jit shape is reused — no recompiles), executes
the runner's memoized jit'd forward, and fulfills each request with its
row of the output.

Failure semantics (``serve/resilience.py``, DESIGN.md §9.4): admission
sheds when the bucket's queue is full or its circuit breaker is open;
requests past their deadline fail fast with ``DeadlineExceeded`` before
batches form; a failing batch retries once (transient faults, jittered
backoff) then descends the degradation ladder
(tuned -> heuristic plans [-> f32] -> lax reference); the drain thread is
supervised — a crash fails that iteration's in-flight requests and the
thread restarts.  The invariant, enforced by the chaos suite: **no
submitted request is ever left unfulfilled** — each completes (possibly
on a lower rung), or fails with a typed error.

Execution is synchronous under the hood (``serve_once``) so tests can
drive the server deterministically with an injected clock; ``start()``
wraps the same drain in a daemon thread for real traffic.  Admission and
each step of the drain loop are ``serve.*`` phases
(``serve/telemetry.py``, DESIGN.md §9.3): profiler spans, and the same
seconds as cumulative counters in ``stats()``.

Numerics caveat: the models compute batch statistics inline (see
``models/gan.py``), so outputs depend on batch composition — a padded
partial batch is the *defined* behavior, matching the batched forward at
the bucket shape, not a per-request isolated forward.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter
from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from repro.serve import bucketing, resilience, warmup as warmup_mod
from repro.serve.batcher import Batcher, FLUSH_FULL, Request
from repro.serve.bucketing import (AdmissionError, BucketKey, BucketSpec,
                                   CircuitOpenError, ShedError)
from repro.serve.resilience import (CircuitBreaker, DeadlineExceeded,
                                    FaultInjector, ResilienceConfig,
                                    RUNG_TUNED)
from repro.serve.telemetry import phase


class ServerClosed(RuntimeError):
    """The server stopped before this request could be served."""


class _BucketStats:
    """Mutable per-bucket counters (one lock-guarded instance each)."""

    __slots__ = ("requests", "completed", "failed", "batches", "flush_full",
                 "flush_deadline", "fill_sum", "wait_sum", "wait_max",
                 "pad_s", "put_s", "dispatch_s", "fetch_s", "fulfil_s",
                 "due_wait_s", "slab_in", "compile_hits", "shed",
                 "deadline_expired", "retries", "degraded", "rungs")

    def __init__(self):
        self.requests = 0       # successfully enqueued (excludes sheds)
        self.completed = 0
        self.failed = 0         # includes deadline_expired
        self.batches = 0
        self.flush_full = 0
        self.flush_deadline = 0
        self.fill_sum = 0.0
        self.wait_sum = 0.0
        self.wait_max = 0.0
        # Seconds of the drain thread's steps of this bucket's batches
        # (``telemetry.COUNTERS``), and of its due batches waiting for the
        # drain thread: from when each became due (``Batcher.due``) to
        # the start of its ``serve.pad``.
        self.pad_s = 0.0
        self.put_s = 0.0
        self.dispatch_s = 0.0
        self.fetch_s = 0.0
        self.fulfil_s = 0.0
        self.due_wait_s = 0.0
        # Batches whose input crossed to the device as a lane-dense slab
        # (``resilience.lane_dense``).
        self.slab_in = 0
        self.compile_hits = 0
        self.shed = 0           # rejected at admission for load (not queued)
        self.deadline_expired = 0
        self.retries = 0        # in-place transient retries across batches
        self.degraded = 0       # batches served below the tuned rung
        self.rungs: Counter = Counter()  # rung -> batches served by it

    def snapshot(self, spec: BucketSpec,
                 breaker: Optional[CircuitBreaker] = None) -> dict:
        return {
            "target_batch": spec.target_batch,
            "tuned_layers": spec.tuned_layers,
            "total_layers": spec.total_layers,
            "tiers": dict(spec.tiers),
            "requests": self.requests,
            "completed": self.completed,
            "failed": self.failed,
            "batches": self.batches,
            "flush_full": self.flush_full,
            "flush_deadline": self.flush_deadline,
            "batch_fill_ratio": (self.fill_sum / self.batches
                                 if self.batches else 0.0),
            "queue_wait_mean_s": (self.wait_sum / self.completed
                                  if self.completed else 0.0),
            "queue_wait_max_s": self.wait_max,
            "pad_s": self.pad_s,
            "put_s": self.put_s,
            "dispatch_s": self.dispatch_s,
            "fetch_s": self.fetch_s,
            "fulfil_s": self.fulfil_s,
            "due_wait_s": self.due_wait_s,
            "slab_in": self.slab_in,
            "compile_hits": self.compile_hits,
            "shed": self.shed,
            "deadline_expired": self.deadline_expired,
            "retries": self.retries,
            "degraded": self.degraded,
            "rungs": dict(self.rungs),
            "breaker": breaker.snapshot() if breaker is not None else None,
        }


class _LoopStats:
    """Server-wide counters of the drain loop, written by its thread."""

    __slots__ = ("form_s", "drain_wait_s", "ticks", "empty_ticks")

    def __init__(self):
        self.form_s = 0.0        # ``serve.form`` seconds
        self.drain_wait_s = 0.0  # ``serve.wait`` seconds
        self.ticks = 0           # ``serve_once`` calls ...
        self.empty_ticks = 0     # ... that served nothing


class TconvServer:
    """Shape-bucketed continuous batching over GeneratorRunners."""

    def __init__(self, runners: Mapping[str, object], *,
                 max_wait_s: float = 0.05,
                 candidate_batches: Tuple[int, ...] = (8, 4, 2, 1),
                 default_batch: int = 1,
                 resilience_config: Optional[ResilienceConfig] = None,
                 fault_injector: Optional[FaultInjector] = None):
        self.runners: Dict[str, object] = dict(runners)
        self.max_wait_s = float(max_wait_s)
        self.candidate_batches = tuple(candidate_batches)
        self.default_batch = int(default_batch)
        self.config = resilience_config or ResilienceConfig()
        self.injector = fault_injector
        self._batcher = Batcher(max_wait_s=max_wait_s,
                                max_queue_depth=self.config.max_queue_depth)
        self._rid = itertools.count()
        self._lock = threading.Lock()
        self._buckets: Dict[tuple, BucketSpec] = {}
        self._stats: Dict[BucketKey, _BucketStats] = {}
        self._breakers: Dict[BucketKey, CircuitBreaker] = {}
        self._ladders: Dict[str, resilience.DegradationLadder] = {}
        self._rejected = 0
        self._thread: Optional[threading.Thread] = None
        self._supervisor: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._running = False
        self._batch_seq = itertools.count(1)   # global batch index (1-based)
        self._inflight: List[Tuple[BucketSpec, Request]] = []
        self._drain_crashes = 0
        self._drain_restarts = 0
        self._loop_stats = _LoopStats()
        self._rng = np.random.default_rng(self.config.seed)  # backoff jitter

    # -- admission ----------------------------------------------------------

    def bucket_for(self, model: str, shape, precision: str) -> BucketSpec:
        """Snap (model, shape, precision) to its bucket, memoized."""
        if model not in self.runners:
            raise AdmissionError(f"unknown model {model!r}; serving "
                                 f"{sorted(self.runners)}")
        memo_key = (model, tuple(shape), precision)
        with self._lock:
            spec = self._buckets.get(memo_key)
        if spec is None:
            spec = bucketing.snap(self.runners[model], shape, precision,
                                  candidate_batches=self.candidate_batches,
                                  default_batch=self.default_batch,
                                  name=model)
            with self._lock:
                self._buckets[memo_key] = spec
                self._stats.setdefault(spec.key, _BucketStats())
                self._breakers.setdefault(spec.key, CircuitBreaker(
                    threshold=self.config.breaker_threshold,
                    cooldown_s=self.config.breaker_cooldown_s))
        return spec

    def submit(self, model: str, inputs, precision: str = "f32", *,
               deadline_s: Optional[float] = None) -> Request:
        """Enqueue one single-sample request; returns its result handle.

        ``deadline_s`` (relative, seconds; falls back to the config's
        ``default_deadline_s``) bounds how long the request may wait —
        past it the server fails it with :class:`DeadlineExceeded` rather
        than executing stale work.  Raises a :class:`ShedError` subclass
        without enqueueing when the bucket's queue is full or its circuit
        breaker is open; ``requests``/``shed`` counters stay consistent
        (``requests == completed + failed + pending``).
        """
        with phase("serve.submit") as span:
            arr = np.asarray(inputs, np.float32)
            try:
                spec = self.bucket_for(model, arr.shape, precision)
            except AdmissionError:
                with self._lock:
                    self._rejected += 1
                raise
            now = time.monotonic()
            with self._lock:
                stats = self._stats[spec.key]
                breaker = self._breakers[spec.key]
                if not breaker.allow(now):
                    stats.shed += 1
                    raise CircuitOpenError(
                        f"bucket {spec.key} breaker is {breaker.state} "
                        f"(after {breaker.consecutive_failures} consecutive "
                        f"batch failures); shedding")
            if deadline_s is None:
                deadline_s = self.config.default_deadline_s
            deadline = None if deadline_s is None else now + float(deadline_s)
            req = Request(next(self._rid), model, arr, precision, now,
                          deadline=deadline)
            span.set_metadata(rid=req.rid)
            try:
                self._batcher.put(spec, req)
            except ShedError:
                with self._lock:
                    stats.shed += 1
                raise
            with self._lock:
                stats.requests += 1
        self._wake.set()
        return req

    # -- execution ----------------------------------------------------------

    def _ladder_for(self, model: str) -> resilience.DegradationLadder:
        with self._lock:
            ladder = self._ladders.get(model)
            if ladder is None:
                ladder = self._ladders[model] = \
                    resilience.DegradationLadder(self.runners[model])
        return ladder

    def _fail_requests(self, spec: BucketSpec, reqs,
                       err: BaseException) -> None:
        t = time.monotonic()
        n = 0
        for r in reqs:
            if not r.done():
                r.set_error(err, t)
                n += 1
        with self._lock:
            self._stats[spec.key].failed += n

    def _run_batch(self, spec: BucketSpec, reqs, reason: str, now: float,
                   batch_index: int, due_wait: float) -> None:
        runner = self.runners[spec.key.model]
        target = spec.target_batch
        precision = spec.key.precision
        stats = self._stats[spec.key]
        breaker = self._breakers[spec.key]
        hit = runner.has_compiled(batch=target, precision=precision)
        with phase("serve.pad", stats, batch=batch_index, n=len(reqs)):
            xs = np.zeros((target,) + spec.key.shape, np.float32)
            for i, r in enumerate(reqs):
                xs[i] = r.inputs
        try:
            out, rung, retries = resilience.run_ladder(
                self._ladder_for(spec.key.model), xs,
                bucket=str(spec.key), batch=target, precision=precision,
                batch_index=batch_index, config=self.config,
                injector=self.injector, rng=self._rng, stats=stats)
        except Exception as err:  # noqa: BLE001 — fulfil, don't wedge
            with phase("serve.fulfil", stats, batch=batch_index):
                self._fail_requests(spec, reqs, err)
                with self._lock:
                    stats.batches += 1
                    stats.due_wait_s += due_wait
                    breaker.record_failure(time.monotonic())
            return
        with phase("serve.fulfil", stats, batch=batch_index):
            t_done = time.monotonic()
            for i, r in enumerate(reqs):
                r.set_result(out[i], t_done)
            waits = [max(now - r.t_enqueue, 0.0) for r in reqs]
            with self._lock:
                stats.completed += len(reqs)
                stats.batches += 1
                stats.due_wait_s += due_wait
                stats.compile_hits += int(hit)
                stats.fill_sum += len(reqs) / target
                stats.wait_sum += sum(waits)
                stats.wait_max = max(stats.wait_max, max(waits))
                stats.retries += retries
                stats.rungs[rung] += 1
                if rung != RUNG_TUNED:
                    stats.degraded += 1
                if reason == FLUSH_FULL:
                    stats.flush_full += 1
                else:
                    stats.flush_deadline += 1
                breaker.record_success()

    def _expire(self, now: float) -> int:
        """Fail every queued request whose deadline has passed."""
        expired = 0
        for spec, dead in self._batcher.pop_expired(now):
            t = time.monotonic()
            for r in dead:
                r.set_error(DeadlineExceeded(
                    f"request {r.rid} deadline passed before execution "
                    f"(bucket {spec.key})"), t)
            with self._lock:
                st = self._stats[spec.key]
                st.failed += len(dead)
                st.deadline_expired += len(dead)
            expired += len(dead)
        return expired

    def serve_once(self, now: Optional[float] = None, *,
                   force: bool = False) -> int:
        """Run every batch due at ``now`` (injected for tests); returns the
        number of requests served (completed or failed, expiries included).

        Popped batches are tracked as in-flight until resolved: anything
        that escapes the per-batch handling (e.g. an injected drain-loop
        crash) leaves requests registered for :meth:`_fail_inflight`, so a
        crashed drain iteration never wedges its callers.
        """
        t_tick = time.monotonic()
        now = t_tick if now is None else now
        loop = self._loop_stats
        with phase("serve.form", loop):
            served = self._expire(now)
            batches = self._batcher.ready(now, force=force)
        for spec, reqs, reason in batches:
            batch_index = next(self._batch_seq)
            for r in reqs:
                r.batch = batch_index
            with self._lock:
                self._inflight.extend((spec, r) for r in reqs)
            if self.injector is not None:
                self.injector.maybe_crash(batch_index)
            # On the tick's clock (``now`` may be injected), the batch
            # starts ``now`` plus the time this tick has taken so far.
            started = now + time.monotonic() - t_tick
            due_wait = max(started - self._batcher.due(reqs, reason, now),
                           0.0)
            self._run_batch(spec, reqs, reason, now, batch_index, due_wait)
            with self._lock:
                self._inflight.clear()
            served += len(reqs)
        loop.ticks += 1
        if not served:
            loop.empty_ticks += 1
        return served

    def _fail_inflight(self, err: BaseException) -> None:
        with self._lock:
            inflight, self._inflight = self._inflight, []
        t = time.monotonic()
        for spec, r in inflight:
            if not r.done():
                r.set_error(err, t)
                with self._lock:
                    self._stats[spec.key].failed += 1

    def drain(self, timeout: float = 30.0) -> None:
        """Serve until the queue is empty (flushing partials immediately)."""
        deadline = time.monotonic() + timeout
        while self._batcher.pending():
            self.serve_once(force=True)
            if time.monotonic() > deadline:
                raise TimeoutError("drain did not empty the queue "
                                   f"within {timeout}s")

    # -- background loop ----------------------------------------------------

    def start(self) -> "TconvServer":
        if self._thread is None:
            self._running = True
            self._thread = self._spawn_drain()
            self._supervisor = threading.Thread(
                target=self._supervise, name="tconv-serve-supervisor",
                daemon=True)
            self._supervisor.start()
        return self

    def stop(self) -> None:
        """Stop the loop and settle every queued request (served, failed,
        or — last resort — errored with :class:`ServerClosed`): no caller
        is ever left blocked on :meth:`Request.result`."""
        if self._thread is None:
            return
        self._running = False
        self._wake.set()
        self._thread.join(timeout=30.0)
        self._thread = None
        if self._supervisor is not None:
            self._supervisor.join(timeout=30.0)
            self._supervisor = None
        try:
            self.drain()  # whatever raced in after the loop exited
        except Exception:  # noqa: BLE001 — never leave requests hanging
            pass
        closing = ServerClosed("server stopped before request was served")
        self._fail_inflight(closing)
        for spec, reqs in self._batcher.pop_all():
            self._fail_requests(spec, reqs, closing)

    def _spawn_drain(self) -> threading.Thread:
        t = threading.Thread(target=self._loop_guard, name="tconv-serve",
                             daemon=True)
        t.start()
        return t

    def _loop_guard(self) -> None:
        """One drain-thread lifetime.  A crash that escapes ``serve_once``
        fails the crashed iteration's in-flight requests (never wedges
        their callers) and ends the thread; the supervisor restarts it."""
        try:
            self._loop()
        except BaseException as err:  # noqa: BLE001 — supervised
            with self._lock:
                self._drain_crashes += 1
            self._fail_inflight(err)

    def _supervise(self) -> None:
        """Restart the drain thread whenever it dies while serving."""
        while self._running:
            t = self._thread
            if t is None:
                break
            t.join(timeout=0.05)
            if self._running and not t.is_alive():
                with self._lock:
                    self._drain_restarts += 1
                self._thread = self._spawn_drain()

    def _loop(self) -> None:
        while self._running:
            if self.serve_once():
                continue
            nd = self._batcher.next_deadline()
            wait = (self.max_wait_s if nd is None
                    else max(nd - time.monotonic(), 0.0))
            with phase("serve.wait", self._loop_stats):
                self._wake.wait(min(wait, 0.05))
            self._wake.clear()

    def __enter__(self) -> "TconvServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- observability ------------------------------------------------------

    def warmup(self, *, precisions: Tuple[str, ...] = ("f32",),
               batches: Optional[Tuple[int, ...]] = None):
        """Pre-compile every admitted bucket (see ``serve/warmup.py``)."""
        return warmup_mod.warm_server(self, precisions=precisions,
                                      batches=batches)

    def stats(self) -> dict:
        """Point-in-time snapshot of every bucket's counters, and the drain
        loop's.  Counts and ``*_s`` seconds are cumulative since the server
        was built, so a window's share is the difference of two snapshots;
        the ratios and means are over the same span."""
        loop = self._loop_stats
        with self._lock:
            by_key = {spec.key: spec for spec in self._buckets.values()}
            buckets = {str(key): self._stats[key].snapshot(
                           by_key[key], self._breakers.get(key))
                       for key in self._stats}
            out = {"buckets": buckets, "rejected": self._rejected,
                   "pending": self._batcher.pending(),
                   "drain_crashes": self._drain_crashes,
                   "drain_restarts": self._drain_restarts,
                   "form_s": loop.form_s, "drain_wait_s": loop.drain_wait_s,
                   "ticks": loop.ticks, "empty_ticks": loop.empty_ticks}
        if self.injector is not None:
            out["fault_injection"] = self.injector.stats()
        return out
