"""Chaos suite for the resilient serving layer (ISSUE 10).

The invariant under test, everywhere: **no submitted request is ever
left unfulfilled** — every request either completes (possibly on a lower
ladder rung), fails with a typed error (``DeadlineExceeded``, a
``ShedError`` subclass at admission, ``LadderExhausted``,
``DrainLoopCrash``, ``ServerClosed``), and the counters in
``server.stats()`` account for all of it
(``requests == completed + failed + pending``, sheds separate).

Most tests drive a jax-free ``FakeRunner`` through the real server and
ladder machinery with injected clocks/sleeps, so the state machines are
deterministic; one integration test pushes a real (tiny) model through
an injected fault and checks the rescued outputs.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.serve import resilience
from repro.serve.bucketing import CircuitOpenError, QueueFullError, ShedError
from repro.serve.resilience import (BREAKER_CLOSED, BREAKER_HALF_OPEN,
                                    BREAKER_OPEN, CircuitBreaker,
                                    DeadlineExceeded, DegradationLadder,
                                    DispatchFault, DrainLoopCrash,
                                    FaultInjector, InjectedFault,
                                    LadderExhausted, PoisonedBucket,
                                    ResilienceConfig, RUNG_F32,
                                    RUNG_HEURISTIC, RUNG_LAX, RUNG_TUNED,
                                    TransientFault, is_transient,
                                    ladder_rungs, run_ladder)
from repro.serve.server import ServerClosed, TconvServer

NOSLEEP = lambda s: None  # noqa: E731 — injected backoff sleep


# ---------------------------------------------------------------------------
# A jax-free runner: every ladder rung produces a distinct marker value,
# and each rung's failure mode is switchable per test.
# ---------------------------------------------------------------------------

MARK_TUNED, MARK_HEURISTIC, MARK_LAX = 1.0, 2.0, 3.0
MARK_TUNED_INT8 = 1.5


class _FakeSpec:
    def forward(self, params, x, *, options=None, policy=None):
        if getattr(policy, "fail", False):
            raise RuntimeError("policy forward broken")
        return jnp.ones_like(x) * getattr(policy, "marker", MARK_LAX)


class _FakePolicy:
    def __init__(self, marker, fail=False):
        self.marker = marker
        self.fail = fail


class FakeRunner:
    """Duck-typed GeneratorRunner: shape (4,), no tuned plans anywhere."""

    name = "fake"
    spec = _FakeSpec()
    params = {}
    options = {}

    def __init__(self):
        self.fail_tuned = None      # exception *instance* to raise, or None
        self.fail_tuned_times = 0   # raise only the first N calls (0 = all)
        self.fail_heuristic = False
        self.tuned_calls = 0

    def input_shape(self):
        return (4,)

    def tconv_problems(self):
        return {}

    def example_inputs(self, batch, seed=0):
        return np.zeros((batch, 4), np.float32)

    def has_compiled(self, *, batch, precision="f32"):
        return False

    def policy(self, precision="f32", plans=None):
        return _FakePolicy(MARK_HEURISTIC, fail=self.fail_heuristic)

    def jitted(self, *, batch, precision="f32"):
        mark = MARK_TUNED_INT8 if precision == "int8" else MARK_TUNED

        def fn(x):
            self.tuned_calls += 1
            if self.fail_tuned is not None:
                if (self.fail_tuned_times == 0
                        or self.tuned_calls <= self.fail_tuned_times):
                    raise self.fail_tuned
            return jnp.ones((batch, 4)) * mark

        return fn


def _server(runner=None, **kw):
    runner = runner or FakeRunner()
    kw.setdefault("max_wait_s", 60.0)  # batches flush on force only
    kw.setdefault("candidate_batches", (2,))
    kw.setdefault("default_batch", 2)
    return runner, TconvServer({"fake": runner}, **kw)


def _x():
    return np.zeros(4, np.float32)


# ---------------------------------------------------------------------------
# Exceptions / ladder-rung structure.
# ---------------------------------------------------------------------------


def test_exception_taxonomy_and_transience():
    assert issubclass(DeadlineExceeded, TimeoutError)
    assert issubclass(QueueFullError, ShedError)
    assert issubclass(CircuitOpenError, ShedError)
    assert issubclass(InjectedFault, TransientFault)
    assert is_transient(InjectedFault("x"))
    assert is_transient(OSError("dma timeout"))
    assert not is_transient(DispatchFault("x"))
    assert not is_transient(ValueError("shape"))


def test_ladder_rung_order():
    assert ladder_rungs("f32") == (RUNG_TUNED, RUNG_HEURISTIC, RUNG_LAX)
    assert ladder_rungs("int8") == (RUNG_TUNED, RUNG_HEURISTIC, RUNG_F32,
                                    RUNG_LAX)


# ---------------------------------------------------------------------------
# run_ladder (injected sleep; no server).
# ---------------------------------------------------------------------------


def _run(runner, *, precision="f32", injector=None,
         config=None, batch_index=1):
    return run_ladder(DegradationLadder(runner), np.zeros((2, 4), np.float32),
                      bucket="fake:4:f32:b2", batch=2, precision=precision,
                      batch_index=batch_index,
                      config=config or ResilienceConfig(),
                      injector=injector, rng=np.random.default_rng(0),
                      sleep=NOSLEEP)


def test_ladder_healthy_serves_tuned():
    out, rung, retries = _run(FakeRunner())
    assert rung == RUNG_TUNED and retries == 0
    np.testing.assert_array_equal(out, np.full((2, 4), MARK_TUNED))


def test_ladder_transient_fault_retries_in_place():
    r = FakeRunner()
    r.fail_tuned, r.fail_tuned_times = TransientFault("blip"), 1
    out, rung, retries = _run(r)
    assert rung == RUNG_TUNED and retries == 1   # retry rescued the rung
    np.testing.assert_array_equal(out, np.full((2, 4), MARK_TUNED))


def test_ladder_nontransient_descends_without_retry():
    r = FakeRunner()
    r.fail_tuned = ValueError("deterministic")
    out, rung, retries = _run(r)
    assert rung == RUNG_HEURISTIC and retries == 0
    assert r.tuned_calls == 1                    # exactly one attempt
    np.testing.assert_array_equal(out, np.full((2, 4), MARK_HEURISTIC))


def test_ladder_persistent_transient_descends_after_one_retry():
    r = FakeRunner()
    r.fail_tuned = TransientFault("always")      # every attempt fails
    out, rung, retries = _run(r)
    assert rung == RUNG_HEURISTIC and retries == 1
    assert r.tuned_calls == 2                    # attempt + one retry only


def test_ladder_knows_which_rungs_have_compiled():
    """``compiled`` (the ``serve.dispatch`` span's id): the runner's own
    record for its rungs, a rung that has run for the others."""
    r = FakeRunner()
    r.fail_tuned = ValueError("deterministic")
    ladder = DegradationLadder(r)
    kw = dict(batch=2, precision="f32")
    assert not ladder.compiled(RUNG_HEURISTIC, **kw)
    ladder.fn(RUNG_HEURISTIC, **kw)(np.zeros((2, 4), np.float32))
    assert ladder.compiled(RUNG_HEURISTIC, **kw)
    assert not ladder.compiled(RUNG_LAX, **kw)
    assert not ladder.compiled(RUNG_TUNED, **kw)  # FakeRunner never has
    # A call that raised compiled nothing the next call can reuse.
    r.fail_heuristic = True
    failing = DegradationLadder(r)
    with pytest.raises(RuntimeError, match="policy forward broken"):
        failing.fn(RUNG_HEURISTIC, **kw)(np.zeros((2, 4), np.float32))
    assert not failing.compiled(RUNG_HEURISTIC, **kw)


def test_ladder_falls_to_lax_bottom():
    r = FakeRunner()
    r.fail_tuned = ValueError("broken")
    r.fail_heuristic = True
    out, rung, _ = _run(r)
    assert rung == RUNG_LAX
    np.testing.assert_array_equal(out, np.full((2, 4), MARK_LAX))


def test_ladder_int8_precision_rung():
    r = FakeRunner()
    orig = r.jitted

    def jitted(*, batch, precision="f32"):
        if precision == "int8":
            def broken(x):
                raise ValueError("int8 path broken")
            return broken
        return orig(batch=batch, precision=precision)

    r.jitted = jitted
    r.fail_heuristic = True
    out, rung, _ = _run(r, precision="int8")
    assert rung == RUNG_F32                      # rescued by the f32 forward
    np.testing.assert_array_equal(out, np.full((2, 4), MARK_TUNED))


def test_ladder_exhausted_raises_typed_with_cause():
    r = FakeRunner()
    r.fail_tuned = ValueError("broken")
    r.fail_heuristic = True
    broken_spec = _FakeSpec()
    r.spec = broken_spec
    # break the lax rung too: _ReferencePolicy has no marker, so make the
    # forward itself reject reference policies
    r.spec.forward = lambda params, x, options=None, policy=None: (
        (_ for _ in ()).throw(RuntimeError("lax broken")))
    with pytest.raises(LadderExhausted) as ei:
        _run(r)
    assert ei.value.__cause__ is not None


def test_ladder_memoizes_rung_fns():
    ladder = DegradationLadder(FakeRunner())
    f1 = ladder.fn(RUNG_TUNED, batch=2, precision="f32")
    f2 = ladder.fn(RUNG_TUNED, batch=2, precision="f32")
    assert f1 is f2
    assert ladder.fn(RUNG_TUNED, batch=4, precision="f32") is not f1


# ---------------------------------------------------------------------------
# Lane-dense transfer: the batch crosses to the device as a (-1, 128)
# slab when it qualifies, and every rung still sees the batch's own shape.
# ---------------------------------------------------------------------------


class _ShapedSpec:
    """Heuristic/lax rungs: a marker times ones, recording the shape each
    trace received; a failing policy raises as ``_FakeSpec``'s does."""

    def __init__(self):
        self.seen = []

    def forward(self, params, x, *, options=None, policy=None):
        self.seen.append(tuple(x.shape))
        if getattr(policy, "fail", False):
            raise RuntimeError("policy forward broken")
        return jnp.ones_like(x) * getattr(policy, "marker", MARK_LAX)


class ShapedRunner(FakeRunner):
    """FakeRunner at any per-request shape; its tuned rung computes
    ``out(x)`` and records the type and shape of each ``x`` it gets."""

    def __init__(self, shape, out=lambda x: x * 2.0 + 1.0):
        super().__init__()
        self.shape, self.out, self.seen = tuple(shape), out, []
        self.spec = _ShapedSpec()

    def input_shape(self):
        return self.shape

    def jitted(self, *, batch, precision="f32"):
        def fn(x):
            self.seen.append((type(x), tuple(x.shape)))
            self.tuned_calls += 1
            if self.fail_tuned is not None and (
                    self.fail_tuned_times == 0
                    or self.tuned_calls <= self.fail_tuned_times):
                raise self.fail_tuned
            return self.out(x)

        return fn


def _batch(shape):
    return np.random.default_rng(0).standard_normal(shape).astype(np.float32)


def _run_shaped(runner, xs, monkeypatch):
    """run_ladder with a bucket's counters; also returns each span's ids."""
    from repro.serve import server as server_mod, telemetry

    ids = {}

    def recording(name, stats=None, **kw):
        ids[name] = kw
        return telemetry.phase(name, stats, **kw)

    monkeypatch.setattr(resilience, "phase", recording)
    stats = server_mod._BucketStats()
    out, rung, retries = run_ladder(
        DegradationLadder(runner), xs, bucket="shaped", batch=xs.shape[0],
        precision="f32", batch_index=1, config=ResilienceConfig(),
        rng=np.random.default_rng(0), sleep=NOSLEEP, stats=stats)
    return out, rung, retries, stats, ids


@pytest.mark.parametrize("shape, dtype, want", [
    ((2, 16, 16, 4), np.float32, True),     # 2,048 elements, C = 4
    ((8, 64, 64, 3), np.float32, True),     # DCGAN's output
    ((8, 100), np.float32, False),          # DCGAN's z: 800 elements
    ((2, 8, 100), np.float32, False),       # 1,600: not whole tiles
    ((2, 8, 128), np.float32, False),       # minor dim already lane-wide
    ((2, 16, 16, 4), np.int32, False),      # not float32
    ((0, 16, 16, 4), np.float32, False),    # empty
])
def test_lane_dense_rule(shape, dtype, want):
    xs = np.zeros(shape, dtype)
    assert resilience.lane_dense(xs) == want
    assert not resilience.lane_dense(jnp.asarray(xs))   # not a host batch


@pytest.mark.parametrize("shape, out, slab", [
    ((2, 16, 16, 4), lambda x: x * 2.0 + 1.0, True),
    ((2, 16, 16, 4), lambda x: np.asarray(x) * 2.0, True),   # numpy out
    ((2, 16, 16, 4), lambda x: (x * 4.0).astype(jnp.int32), True),
    ((2, 8, 100), lambda x: x - 1.0, False),       # not whole tiles
    ((2, 8, 128), lambda x: x - 1.0, False),       # lane-wide already
    # DCGAN's shape of path: a z in, an image out.
    ((2, 100), lambda x: jnp.tile(x[:, :48], (1, 32)).reshape(2, 16, 16, 6),
     False),
], ids=["nhwc", "numpy-out", "int-out", "odd-size", "lane-wide", "z-to-image"])
def test_run_ladder_slab_path_is_bit_identical(shape, out, slab,
                                               monkeypatch):
    """Output as the plain path gives it, byte for byte, in the rung's
    shape and dtype; the rung got the batch's shape on the device; the
    counter and the span's ``slab=`` say whether the input was a slab."""
    xs = _batch(shape)
    r = ShapedRunner(shape[1:], out)
    got, rung, _, stats, ids = _run_shaped(r, xs, monkeypatch)
    want = np.asarray(out(jnp.asarray(xs)))
    assert rung == RUNG_TUNED
    assert got.shape == want.shape and got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()
    assert r.seen == [(type(jnp.asarray(xs)), shape)]
    assert stats.slab_in == int(slab)
    assert ids["serve.put"]["slab"] == slab
    assert "slab" not in ids["serve.fetch"]


@pytest.mark.parametrize("fail, heuristic_broken, want_rung, want_calls", [
    (TransientFault("blip"), False, RUNG_TUNED, 2),   # retried in place
    (ValueError("deterministic"), False, RUNG_HEURISTIC, 1),
    (ValueError("deterministic"), True, RUNG_LAX, 1),
])
def test_rung_failure_on_slab_path_walks_the_ladder(
        fail, heuristic_broken, want_rung, want_calls, monkeypatch):
    """A batch that crossed as a slab descends the ladder as before, and
    every rung tried receives the batch in its own shape."""
    shape = (2, 16, 16, 4)
    r = ShapedRunner(shape[1:], lambda x: x * 0.0 + MARK_TUNED)
    r.fail_tuned, r.fail_tuned_times = fail, 1 if want_calls == 2 else 0
    r.fail_heuristic = heuristic_broken
    got, rung, _, stats, _ = _run_shaped(r, _batch(shape), monkeypatch)
    marks = {RUNG_TUNED: MARK_TUNED, RUNG_HEURISTIC: MARK_HEURISTIC,
             RUNG_LAX: MARK_LAX}
    assert rung == want_rung and r.tuned_calls == want_calls
    np.testing.assert_array_equal(got, np.full(shape, marks[want_rung]))
    assert {s for _, s in r.seen} == {shape}
    assert set(r.spec.seen) <= {shape}
    assert stats.slab_in == 1


# ---------------------------------------------------------------------------
# Circuit breaker state machine (injected clock).
# ---------------------------------------------------------------------------


def test_breaker_trips_after_threshold_and_probes():
    b = CircuitBreaker(threshold=3, cooldown_s=10.0)
    assert b.state == BREAKER_CLOSED and b.allow(now=0.0)
    assert not b.record_failure(now=1.0)
    assert not b.record_failure(now=2.0)
    assert b.record_failure(now=3.0)             # third consecutive: trips
    assert b.state == BREAKER_OPEN and b.trips == 1
    assert not b.allow(now=3.1)                  # open: shed
    assert not b.allow(now=12.9)                 # cooldown not elapsed
    assert b.allow(now=13.0)                     # half-open probe admitted
    assert b.state == BREAKER_HALF_OPEN
    assert not b.allow(now=13.0)                 # only one probe at a time
    b.record_success()                           # probe ok: closed
    assert b.state == BREAKER_CLOSED and b.consecutive_failures == 0
    assert b.allow(now=13.1)


def test_breaker_failed_probe_reopens():
    b = CircuitBreaker(threshold=1, cooldown_s=5.0)
    assert b.record_failure(now=0.0)             # threshold 1: instant trip
    assert b.allow(now=5.0)                      # probe
    assert b.record_failure(now=5.1)             # probe failed: re-open
    assert b.state == BREAKER_OPEN and b.trips == 2
    assert not b.allow(now=10.0)                 # new cooldown from 5.1
    assert b.allow(now=10.2)


def test_breaker_success_resets_consecutive_count():
    b = CircuitBreaker(threshold=2, cooldown_s=1.0)
    b.record_failure(now=0.0)
    b.record_success()
    b.record_failure(now=1.0)                    # 1 again, not 2: no trip
    assert b.state == BREAKER_CLOSED and b.trips == 0


# ---------------------------------------------------------------------------
# FaultInjector determinism + trigger semantics.
# ---------------------------------------------------------------------------


def test_injector_fail_nth_targets_tuned_rung_only():
    inj = FaultInjector(fail_nth_batch=2)
    inj.before_batch("b", 1, rung=RUNG_TUNED, attempt=0)      # not nth
    with pytest.raises(InjectedFault):
        inj.before_batch("b", 2, rung=RUNG_TUNED, attempt=0)
    with pytest.raises(InjectedFault):
        inj.before_batch("b", 2, rung=RUNG_TUNED, attempt=1)  # retry too
    inj.before_batch("b", 2, rung=RUNG_HEURISTIC, attempt=0)  # lower rung ok
    assert inj.injected == {"fail": 2}


def test_injector_poison_hits_every_rung_of_matching_bucket():
    inj = FaultInjector(poison_bucket="fake:")
    for rung in ladder_rungs("int8"):
        with pytest.raises(PoisonedBucket):
            inj.before_batch("fake:4x4:int8:b2", 7, rung=rung, attempt=0)
    inj.before_batch("other:4:f32:b1", 7, rung=RUNG_TUNED, attempt=0)
    assert inj.injected["poison"] == 4


def test_injector_dispatch_raise_wraps_fn():
    inj = FaultInjector(raise_in_dispatch_nth=3)
    ok = inj.wrap(lambda x: x, "b", 2, rung=RUNG_TUNED, attempt=0)
    assert ok("payload") == "payload"
    bad = inj.wrap(lambda x: x, "b", 3, rung=RUNG_TUNED, attempt=0)
    with pytest.raises(DispatchFault):
        bad("payload")
    # lower rungs get the real fn even on the nth batch
    low = inj.wrap(lambda x: x, "b", 3, rung=RUNG_LAX, attempt=0)
    assert low("payload") == "payload"


def test_injector_crash_fires_once():
    inj = FaultInjector(crash_drain_at_batch=2)
    inj.maybe_crash(1)
    with pytest.raises(DrainLoopCrash):
        inj.maybe_crash(2)
    inj.maybe_crash(3)                           # once only
    assert inj.injected == {"drain_crash": 1}


def test_injector_is_deterministic_across_replays():
    def play():
        inj = FaultInjector(fail_nth_batch=2, seed=7)
        for n in range(1, 9):
            try:
                inj.before_batch("b", n, rung=RUNG_TUNED, attempt=0)
            except InjectedFault:
                pass
        return dict(inj.injected)

    assert play() == play() == {"fail": 4}


# ---------------------------------------------------------------------------
# Server: deadlines, shedding, breaker at admission.
# ---------------------------------------------------------------------------


def test_deadline_expired_request_fails_fast():
    _, srv = _server()
    req = srv.submit("fake", _x(), deadline_s=0.0)  # dead on arrival
    live = srv.submit("fake", _x())                 # no deadline
    assert srv.serve_once(force=True) == 2
    with pytest.raises(DeadlineExceeded):
        req.result(timeout=0)
    assert live.result(timeout=0) is not None       # live one still served
    b = srv.stats()["buckets"]["fake:4:f32:b2"]
    assert b["deadline_expired"] == 1 and b["failed"] == 1
    assert b["completed"] == 1
    assert b["requests"] == b["completed"] + b["failed"]


def test_default_deadline_from_config():
    _, srv = _server(resilience_config=ResilienceConfig(
        default_deadline_s=0.0))
    req = srv.submit("fake", _x())
    srv.serve_once(force=True)
    with pytest.raises(DeadlineExceeded):
        req.result(timeout=0)


def test_queue_full_sheds_without_enqueueing():
    _, srv = _server(resilience_config=ResilienceConfig(max_queue_depth=2))
    admitted = [srv.submit("fake", _x()) for _ in range(2)]
    for _ in range(3):
        with pytest.raises(QueueFullError):
            srv.submit("fake", _x())
    srv.serve_once(force=True)
    assert all(r.result(timeout=0) is not None for r in admitted)
    b = srv.stats()["buckets"]["fake:4:f32:b2"]
    assert b["shed"] == 3 and b["requests"] == 2 == b["completed"]


def test_breaker_trips_then_sheds_then_half_open_recovers():
    r, srv = _server(resilience_config=ResilienceConfig(
        breaker_threshold=2, breaker_cooldown_s=0.0))
    r.fail_tuned = ValueError("broken")
    r.fail_heuristic = True
    r.spec = _FakeSpec()                         # fresh: no class-level leak
    r.spec.forward = lambda params, x, options=None, policy=None: (
        (_ for _ in ()).throw(RuntimeError("lax broken")))
    failed = []
    for _ in range(2):                           # two fully-failed batches
        failed.append(srv.submit("fake", _x()))
        srv.serve_once(force=True)
    for q in failed:
        with pytest.raises(LadderExhausted):
            q.result(timeout=0)
    b = srv.stats()["buckets"]["fake:4:f32:b2"]
    assert b["breaker"]["state"] == BREAKER_OPEN
    assert b["breaker"]["trips"] == 1
    # cooldown 0: next submit is the half-open probe; heal the runner
    r.fail_tuned = None
    probe = srv.submit("fake", _x())
    srv.serve_once(force=True)
    assert probe.result(timeout=0) is not None
    assert srv.stats()["buckets"]["fake:4:f32:b2"]["breaker"]["state"] == \
        BREAKER_CLOSED


def test_breaker_open_sheds_with_typed_error():
    r, srv = _server(resilience_config=ResilienceConfig(
        breaker_threshold=1, breaker_cooldown_s=600.0))
    r.fail_tuned = ValueError("broken")
    r.fail_heuristic = True
    r.spec = _FakeSpec()
    r.spec.forward = lambda params, x, options=None, policy=None: (
        (_ for _ in ()).throw(RuntimeError("lax broken")))
    doomed = srv.submit("fake", _x())
    srv.serve_once(force=True)
    with pytest.raises(LadderExhausted):
        doomed.result(timeout=0)
    with pytest.raises(CircuitOpenError):        # open + long cooldown
        srv.submit("fake", _x())
    assert srv.stats()["buckets"]["fake:4:f32:b2"]["shed"] == 1


# ---------------------------------------------------------------------------
# Server: ladder accounting, injector composition.
# ---------------------------------------------------------------------------


def test_server_records_rungs_and_degraded():
    r, srv = _server(fault_injector=FaultInjector(fail_nth_batch=2))
    reqs = []
    for _ in range(4):                           # 4 serial partial batches
        reqs.append(srv.submit("fake", _x()))
        srv.serve_once(force=True)
    outs = [q.result(timeout=0) for q in reqs]
    # batches 2 and 4 were injected: retried (transient) then descended
    np.testing.assert_array_equal(outs[0], np.full(4, MARK_TUNED))
    np.testing.assert_array_equal(outs[1], np.full(4, MARK_HEURISTIC))
    b = srv.stats()["buckets"]["fake:4:f32:b2"]
    assert b["rungs"] == {RUNG_TUNED: 2, RUNG_HEURISTIC: 2}
    assert b["degraded"] == 2 and b["retries"] == 2
    assert b["completed"] == 4 and b["failed"] == 0
    assert srv.stats()["fault_injection"]["fail"] == 4  # 2 per bad batch


@pytest.mark.parametrize("shape, exhausted, want", [
    ((16, 16, 4), False, 3),    # each batch crosses as a slab
    ((4,), False, 0),           # too small: the plain copy
    ((16, 16, 4), True, 3),     # crossed, then every rung failed
])
def test_server_counts_slab_batches(shape, exhausted, want):
    """``slab_in`` counts exactly the batches whose input crossed as a
    slab: three batches of 2 (two full, one forced)."""
    r = ShapedRunner(shape)
    if exhausted:
        r.fail_tuned, r.fail_heuristic = ValueError("broken"), True
        r.spec.forward = lambda params, x, options=None, policy=None: (
            (_ for _ in ()).throw(RuntimeError("lax broken")))
    _, srv = _server(r)
    reqs = [srv.submit("fake", np.ones(shape, np.float32)) for _ in range(5)]
    assert srv.serve_once(force=True) == 5
    [b] = srv.stats()["buckets"].values()
    assert b["batches"] == 3 and b["slab_in"] == want
    assert b["failed"] == (5 if exhausted else 0)
    if not exhausted:
        for q in reqs:
            np.testing.assert_array_equal(q.result(timeout=0),
                                          np.full(shape, 3.0))


def test_server_straggler_composition_counts_stalls():
    from repro.runtime.fault_tolerance import StragglerSimulator

    straggler = StragglerSimulator(p=1.0, delay_s=0.0, seed=3)
    _, srv = _server(fault_injector=FaultInjector(straggler=straggler))
    q = srv.submit("fake", _x())
    srv.serve_once(force=True)
    assert q.result(timeout=0) is not None
    assert srv.stats()["fault_injection"]["straggler_stalls"] == 1


# ---------------------------------------------------------------------------
# Drain-loop supervision.
# ---------------------------------------------------------------------------


def test_supervisor_restarts_crashed_drain_and_fails_inflight():
    _, srv = _server(max_wait_s=0.01,
                     fault_injector=FaultInjector(crash_drain_at_batch=1))
    with srv:
        crashed = srv.submit("fake", _x())
        with pytest.raises(DrainLoopCrash):
            crashed.result(timeout=10.0)         # failed, not wedged
        # the supervisor restarted the drain thread: traffic flows again
        deadline = time.monotonic() + 10.0
        while srv.stats()["drain_restarts"] == 0:
            assert time.monotonic() < deadline, "supervisor never restarted"
            time.sleep(0.01)
        healthy = srv.submit("fake", _x())
        assert healthy.result(timeout=10.0) is not None
    s = srv.stats()
    assert s["drain_crashes"] == 1 and s["drain_restarts"] >= 1
    assert s["fault_injection"]["drain_crash"] == 1


def test_crash_in_serve_once_counts_request_as_failed():
    _, srv = _server(fault_injector=FaultInjector(crash_drain_at_batch=1))
    q = srv.submit("fake", _x())
    with pytest.raises(DrainLoopCrash):
        srv.serve_once(force=True)               # synchronous caller path
    # the popped request is in-flight; failing it is the guard's job —
    # simulate what _loop_guard does
    srv._fail_inflight(DrainLoopCrash("from guard"))
    with pytest.raises(DrainLoopCrash):
        q.result(timeout=0)
    b = srv.stats()["buckets"]["fake:4:f32:b2"]
    assert b["failed"] == 1 and srv.stats()["pending"] == 0


# ---------------------------------------------------------------------------
# Integration: a real model rescued by the ladder.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fsrcnn_runner():
    from repro.models.runner import make_runner

    return make_runner("fsrcnn", key=jax.random.PRNGKey(0),
                       init_kw={"d": 8, "s": 4, "m": 1}, input_hw=8)


def test_real_model_chaos_every_request_served(fsrcnn_runner):
    """fail-every-2nd-batch against a real runner: every request completes
    (tuned or rescued by the heuristic rung), outputs finite, counters
    consistent — the chaos invariant end to end.  Batch-1 buckets driven
    synchronously make the batch indices (and so the injections)
    deterministic: 6 requests -> batches 1..6, of which 2/4/6 fail."""
    inj = FaultInjector(fail_nth_batch=2)
    srv = TconvServer({"fsrcnn": fsrcnn_runner}, max_wait_s=60.0,
                      candidate_batches=(1,), default_batch=1,
                      fault_injector=inj)
    x = np.asarray(fsrcnn_runner.example_inputs(1, seed=0))[0]
    reqs = [srv.submit("fsrcnn", x) for _ in range(6)]
    assert srv.serve_once(force=True) == 6
    outs = [q.result(timeout=0) for q in reqs]
    assert all(np.isfinite(np.asarray(o)).all() for o in outs)
    [b] = srv.stats()["buckets"].values()
    assert b["completed"] == 6 and b["failed"] == 0
    assert b["degraded"] == 3 and b["retries"] == 3
    assert b["rungs"] == {RUNG_TUNED: 3, RUNG_HEURISTIC: 3}
    assert inj.injected["fail"] == 6             # 2 attempts per bad batch
    # rescued rows are numerically the same forward
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(outs[0]),
                               rtol=1e-5, atol=1e-5)


def test_real_model_heuristic_rung_output_matches_reference(fsrcnn_runner):
    """The heuristic rung is numerically the same forward — explicit
    default plans change scheduling, not math."""
    ladder = DegradationLadder(fsrcnn_runner)
    x = jnp.asarray(np.asarray(fsrcnn_runner.example_inputs(2, seed=1)))
    tuned = np.asarray(ladder.fn(RUNG_TUNED, batch=2, precision="f32")(x))
    heur = np.asarray(ladder.fn(RUNG_HEURISTIC, batch=2, precision="f32")(x))
    lax = np.asarray(ladder.fn(RUNG_LAX, batch=2, precision="f32")(x))
    np.testing.assert_allclose(heur, tuned, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lax, tuned, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# `python -m repro.serve` exit status: degradation never passes quietly.
# ---------------------------------------------------------------------------


def _refuse_tuned_rung(monkeypatch):
    """Every tuned-rung forward raises, as a kernel the chip's compiler
    refuses would; the heuristic rung still serves."""
    build = DegradationLadder._build

    def build_or_refuse(self, rung, *, batch, precision):
        if rung != RUNG_TUNED:
            return build(self, rung, batch=batch, precision=precision)

        def refused(x):
            raise RuntimeError("tuned kernel refused by the compiler")
        return refused

    monkeypatch.setattr(DegradationLadder, "_build", build_or_refuse)


@pytest.mark.parametrize("refuse,chaos,code", [
    (False, False, 0),   # healthy run
    (True, False, 1),    # degraded with no fault injected: fails the run
    (True, True, 0),     # the chaos leg degrades on purpose
])
def test_serve_cli_exit_status_on_degraded_batches(monkeypatch, refuse,
                                                    chaos, code):
    import sys

    from repro.serve import __main__ as cli

    monkeypatch.setattr(cli, "enable_compile_cache", lambda: "")
    if refuse:
        _refuse_tuned_rung(monkeypatch)
    argv = ["repro.serve", "--models", "fsrcnn", "--precisions", "f32",
            "--requests", "4", "--rate", "1000"]
    if chaos:
        argv += ["--chaos-fail-nth", "1"]
    monkeypatch.setattr(sys, "argv", argv)
    assert cli.main() == code
