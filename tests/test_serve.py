"""Serving layer: bucketed admission, wait-or-flush batching, warmup.

Covers the ISSUE-8 serve surface: ``bucketing.snap`` snapping to the
best tuned-plan batch (and rejecting what would trigger a recompile
storm), ``Batcher`` flush-on-full vs flush-on-deadline with an injected
clock, warmup really consuming shipped-table plans (asserted through
``ops.consumed_plans()`` tier attribution), and request -> response
round trips through ``TconvServer`` at f32 AND int8 — compared against
the batched padded forward, which is the *defined* behavior (the models
compute batch statistics inline, so outputs depend on batch
composition; see ``serve/server.py``).
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import autotune, plan_table
from repro.core.autotune import TIER_SHIPPED, TIER_USER_CACHE, cache_key
from repro.kernels import ops
from repro.kernels.registry import Plan
from repro.models.runner import make_runner
from repro.serve import bucketing
from repro.serve.batcher import (Batcher, FLUSH_DEADLINE, FLUSH_FULL,
                                 Request)
from repro.serve.bucketing import AdmissionError, BucketKey, BucketSpec
from repro.serve.server import TconvServer
from repro.serve.warmup import warm_runner

DCGAN_KW = dict(init_kw={"scale_down": 16})


@pytest.fixture(scope="module")
def dcgan_params():
    from repro.models import gan

    params, _ = gan.init_dcgan_g(jax.random.PRNGKey(0), **DCGAN_KW["init_kw"])
    return params


def _fresh_runner(dcgan_params):
    """New runner over shared params: fresh jit memo, so plan consumption
    happens inside the calling test."""
    return make_runner("dcgan", params=dcgan_params)


def _isolate_plans(monkeypatch, tmp_path):
    """Empty user cache + empty shipped-table dir, memos reset."""
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "cache.json"))
    monkeypatch.setenv(plan_table.TABLE_DIR_ENV, str(tmp_path / "plans"))
    monkeypatch.delenv(ops.AUTOLOAD_ENV, raising=False)
    autotune.reset_shared_caches()
    plan_table.reset_shipped_tables()
    ops.clear_consumed_plans()
    return autotune.shared_cache(), tmp_path / "plans"


def _write_shipped(table_dir, entries, backend="cpu"):
    table_dir.mkdir(parents=True, exist_ok=True)
    doc = {"version": plan_table.TABLE_VERSION,
           "provenance": {"backend": backend, "jax": "0.4.37", "repeats": 2,
                          "created": 1754000000.0, "note": "test"},
           "entries": {k: {"plan": p.to_json()} for k, p in entries.items()}}
    (table_dir / f"{backend}.json").write_text(json.dumps(doc))
    plan_table.reset_shipped_tables()


# ---------------------------------------------------------------------------
# Admission / bucketing.
# ---------------------------------------------------------------------------


def test_snap_prefers_fully_tuned_batch(monkeypatch, tmp_path, dcgan_params):
    cache, _ = _isolate_plans(monkeypatch, tmp_path)
    r = _fresh_runner(dcgan_params)
    for prob in r.tconv_problems().values():
        cache.put(cache_key(prob, dtype=jnp.float32, batch=4),
                  autotune.default_plan(prob))
    spec = bucketing.snap(r, r.input_shape(), "f32",
                          candidate_batches=(8, 4, 2, 1))
    assert spec.key.batch == 4 and spec.fully_tuned
    assert dict(spec.tiers) == {TIER_USER_CACHE: spec.total_layers}
    # int8 keys were not seeded: falls back to the heuristic default
    spec8 = bucketing.snap(r, r.input_shape(), "int8",
                           candidate_batches=(8, 4, 2, 1), default_batch=1)
    assert spec8.key.batch == 1 and spec8.tuned_layers == 0
    autotune.reset_shared_caches()


def test_snap_partial_coverage_beats_none(monkeypatch, tmp_path,
                                          dcgan_params):
    cache, _ = _isolate_plans(monkeypatch, tmp_path)
    r = _fresh_runner(dcgan_params)
    prob = next(iter(r.tconv_problems().values()))
    cache.put(cache_key(prob, dtype=jnp.float32, batch=2),
              autotune.default_plan(prob))
    spec = bucketing.snap(r, r.input_shape(), "f32",
                          candidate_batches=(8, 2, 1))
    assert spec.key.batch == 2
    assert 0 < spec.tuned_layers < spec.total_layers
    assert not spec.fully_tuned
    autotune.reset_shared_caches()


def test_snap_heuristic_fallback(monkeypatch, tmp_path, dcgan_params):
    _isolate_plans(monkeypatch, tmp_path)
    r = _fresh_runner(dcgan_params)
    spec = bucketing.snap(r, r.input_shape(), "f32", default_batch=2)
    assert spec.key.batch == 2 and spec.tuned_layers == 0
    assert dict(spec.tiers) == {bucketing.TIER_HEURISTIC: spec.total_layers}
    assert str(spec.key) == f"dcgan:{r.input_shape()[0]}:f32:b2"


def test_snap_rejects_bad_shape_and_precision(dcgan_params):
    r = _fresh_runner(dcgan_params)
    with pytest.raises(AdmissionError, match="shape"):
        bucketing.snap(r, (3, 3, 3), "f32")
    with pytest.raises(AdmissionError, match="precision"):
        bucketing.snap(r, r.input_shape(), "fp16")


# ---------------------------------------------------------------------------
# Batcher (pure, injected clock — no jax).
# ---------------------------------------------------------------------------


def _spec(batch, name="m"):
    return BucketSpec(key=BucketKey(name, (4,), "f32", batch),
                      tuned_layers=0, total_layers=0, tiers=())


def _req(rid, t):
    return Request(rid, "m", np.zeros(4, np.float32), "f32", t)


def test_batcher_flush_on_full_is_immediate():
    b = Batcher(max_wait_s=10.0)
    spec = _spec(2)
    for i in range(5):
        b.put(spec, _req(i, t=0.0))
    out = b.ready(now=0.0)
    assert [(len(reqs), reason) for _, reqs, reason in out] == [
        (2, FLUSH_FULL), (2, FLUSH_FULL)]
    assert b.pending() == 1                     # partial stays queued
    assert b.ready(now=5.0) == []               # deadline not reached
    [(_, reqs, reason)] = b.ready(now=10.0)     # oldest waited max_wait
    assert reason == FLUSH_DEADLINE and [r.rid for r in reqs] == [4]
    assert b.pending() == 0


def test_batcher_deadline_and_force():
    b = Batcher(max_wait_s=0.5)
    spec = _spec(8)
    b.put(spec, _req(0, t=1.0))
    b.put(spec, _req(1, t=1.2))
    assert b.next_deadline() == pytest.approx(1.5)   # oldest + max_wait
    assert b.ready(now=1.4) == []
    [(_, reqs, reason)] = b.ready(now=1.5)
    assert reason == FLUSH_DEADLINE and len(reqs) == 2
    # force flushes a fresh partial immediately (drain/shutdown path)
    b.put(spec, _req(2, t=2.0))
    [(_, reqs, reason)] = b.ready(now=2.0, force=True)
    assert reason == FLUSH_DEADLINE and [r.rid for r in reqs] == [2]


def test_request_result_timeout_and_error():
    r = _req(0, t=0.0)
    with pytest.raises(TimeoutError):
        r.result(timeout=0.01)
    r.set_error(RuntimeError("boom"), t_done=1.0)
    assert r.done() and r.latency_s == 1.0
    with pytest.raises(RuntimeError, match="boom"):
        r.result(timeout=0)


def test_request_unfulfilled_wait_never_returns_none():
    """Regression (ISSUE 10): an unfulfilled ``result(timeout=)`` must
    raise ``TimeoutError``, never return a value — ``None`` would be
    indistinguishable from a legitimately-``None`` payload."""
    r = _req(0, t=0.0)
    with pytest.raises(TimeoutError, match="not served"):
        r.result(timeout=0)
    # a real None payload, by contrast, is returned as-is
    r2 = _req(1, t=0.0)
    r2.set_result(None, t_done=1.0)
    assert r2.result(timeout=0) is None
    # and a fulfilled event with neither value nor error is an invariant
    # violation, reported as such rather than handed back as a result
    r3 = _req(2, t=0.0)
    r3._event.set()
    with pytest.raises(RuntimeError, match="no result/error"):
        r3.result(timeout=0)


def test_batcher_bounded_queue_and_expiry():
    from repro.serve.bucketing import QueueFullError

    b = Batcher(max_wait_s=10.0, max_queue_depth=2)
    spec = _spec(8)
    b.put(spec, _req(0, t=0.0))
    b.put(spec, _req(1, t=0.0))
    with pytest.raises(QueueFullError):
        b.put(spec, _req(2, t=0.0))
    assert b.pending() == 2                      # overflow was not enqueued
    # expiry: deadline-carrying request is removed before batching, FIFO
    # order of the survivors kept
    exp = Request(3, "m", np.zeros(4, np.float32), "f32", 0.0, deadline=1.0)
    b2 = Batcher(max_wait_s=10.0)
    b2.put(spec, _req(4, t=0.0))
    b2.put(spec, exp)
    b2.put(spec, _req(5, t=0.0))
    [(_, dead)] = b2.pop_expired(now=1.0)
    assert [r.rid for r in dead] == [3]
    [(_, live, _)] = b2.ready(now=0.0, force=True)
    assert [r.rid for r in live] == [4, 5]


# ---------------------------------------------------------------------------
# Warmup consumes the shipped table (tier attribution).
# ---------------------------------------------------------------------------


def test_warmup_consumes_shipped_table_plans(monkeypatch, tmp_path):
    from repro.models import gan

    _, table_dir = _isolate_plans(monkeypatch, tmp_path)
    # Unique channel widths (base=768): trace-time consumption records
    # only on a fresh trace, and ops._dispatch's jit cache is keyed by
    # shapes — a problem key another test already traced (under its own
    # plan environment) would replay without consulting the tiers (the
    # same caveat tests/test_plan_table.py documents).
    params, _ = gan.init_dcgan_g(jax.random.PRNGKey(3), base=768,
                                 scale_down=16)
    r = make_runner("dcgan", params=params)
    probs = r.tconv_problems()
    _write_shipped(table_dir,
                   {cache_key(p, dtype=jnp.float32, batch=2):
                    autotune.default_plan(p) for p in probs.values()})

    ops.clear_consumed_plans()
    rec = warm_runner(r, batch=2)
    assert rec.model == "dcgan" and rec.batch == 2 and rec.seconds > 0
    assert rec.tuned_layers == rec.total_layers == len(probs)
    assert dict(rec.tiers) == {TIER_SHIPPED: len(probs)}
    # the compile itself consumed shipped-table plans at trace time
    assert len(rec.consumed) == len(probs)
    assert {tier for _, tier in rec.consumed} == {TIER_SHIPPED}
    assert r.has_compiled(batch=2)
    autotune.reset_shared_caches()
    plan_table.reset_shipped_tables()


# ---------------------------------------------------------------------------
# Server round trips.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_server_round_trip_matches_batched_forward(dcgan_params, precision):
    """3 requests through a target-batch-2 bucket: one full batch + one
    zero-padded partial.  Outputs must equal the batched padded forward
    row-for-row (the defined behavior under inline batch statistics)."""
    r = _fresh_runner(dcgan_params)
    server = TconvServer({"dcgan": r}, max_wait_s=30.0,
                         candidate_batches=(2, 1), default_batch=2)
    xs = np.asarray(r.example_inputs(batch=3, seed=9))
    reqs = [server.submit("dcgan", xs[i], precision=precision)
            for i in range(3)]
    assert server.serve_once(force=True) == 3
    fn = r.jitted(batch=2, precision=precision)
    want_full = np.asarray(fn(jnp.asarray(xs[:2])))
    padded = np.zeros((2,) + xs.shape[1:], np.float32)
    padded[0] = xs[2]
    want_part = np.asarray(fn(jnp.asarray(padded)))[0]
    np.testing.assert_allclose(np.asarray(reqs[0].result(timeout=0)),
                               want_full[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reqs[1].result(timeout=0)),
                               want_full[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(reqs[2].result(timeout=0)),
                               want_part, rtol=1e-6, atol=1e-6)

    stats = server.stats()
    key = f"dcgan:{r.input_shape()[0]}:{precision}:b2"
    b = stats["buckets"][key]
    assert b["requests"] == b["completed"] == 3 and b["failed"] == 0
    assert b["batches"] == 2
    assert b["flush_full"] == 1 and b["flush_deadline"] == 1
    assert b["batch_fill_ratio"] == pytest.approx(0.75)  # (2/2 + 1/2) / 2
    assert stats["pending"] == 0 and stats["rejected"] == 0


def test_server_threaded_with_warmup_compile_hits(dcgan_params):
    r = _fresh_runner(dcgan_params)
    server = TconvServer({"dcgan": r}, max_wait_s=0.02,
                         candidate_batches=(2, 1), default_batch=2)
    records = server.warmup()
    assert len(records) == 1 and records[0].batch == 2
    assert r.has_compiled(batch=2)
    xs = np.asarray(r.example_inputs(batch=2, seed=4))
    with server:
        reqs = [server.submit("dcgan", xs[i]) for i in range(2)]
        outs = [req.result(timeout=60) for req in reqs]
    assert all(np.isfinite(np.asarray(o)).all() for o in outs)
    b = server.stats()["buckets"][f"dcgan:{r.input_shape()[0]}:f32:b2"]
    assert b["completed"] == 2
    assert b["compile_hits"] == b["batches"]    # warmup pre-compiled
    assert b["queue_wait_max_s"] <= 0.02 + 0.25  # deadline-bounded (+slack)


ALL_MODELS = {
    "dcgan": dict(init_kw={"scale_down": 16}),
    "pix2pix": dict(init_kw={"depth": 4, "scale_down": 16}),
    "fsrcnn": dict(init_kw={"d": 8, "s": 4, "m": 1}, input_hw=8),
    "styletransfer": dict(init_kw={"base": 8, "n_res": 1}, input_hw=16),
}


@pytest.fixture(scope="module")
def all_runners():
    return {name: make_runner(name, key=jax.random.PRNGKey(i), **kw)
            for i, (name, kw) in enumerate(ALL_MODELS.items())}


@pytest.mark.parametrize("precision", ["f32", "int8"])
@pytest.mark.parametrize("name", sorted(ALL_MODELS))
def test_round_trip_every_ported_runner(all_runners, name, precision):
    """Request -> response through the server for each of the four ported
    families, f32 and int8: the output is the runner's own jitted bucket
    forward, row for row."""
    r = all_runners[name]
    server = TconvServer({name: r}, candidate_batches=(1,), default_batch=1)
    x = np.asarray(r.example_inputs(1, seed=2))[0]
    req = server.submit(name, x, precision=precision)
    assert server.serve_once(force=True) == 1
    want = np.asarray(r.jitted(batch=1, precision=precision)(
        jnp.asarray(x)[None]))[0]
    got = np.asarray(req.result(timeout=0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert np.isfinite(got).all()


def test_server_rejects_and_counts(dcgan_params):
    r = _fresh_runner(dcgan_params)
    server = TconvServer({"dcgan": r})
    with pytest.raises(AdmissionError, match="unknown model"):
        server.submit("vae", np.zeros(4, np.float32))
    with pytest.raises(AdmissionError, match="shape"):
        server.submit("dcgan", np.zeros(7, np.float32))
    assert server.stats()["rejected"] == 2


# ---------------------------------------------------------------------------
# Shutdown / drain edge paths (ISSUE 10): no request left unfulfilled.
# ---------------------------------------------------------------------------


class _EchoRunner:
    """Minimal duck-typed runner: instant zero outputs, no jax."""

    name = "echo"

    def input_shape(self):
        return (4,)

    def tconv_problems(self):
        return {}

    def has_compiled(self, *, batch, precision="f32"):
        return False

    def jitted(self, *, batch, precision="f32"):
        return lambda x: np.zeros((batch, 4), np.float32)


def test_server_drain_timeout_raises():
    """``drain`` must raise ``TimeoutError`` when the queue cannot empty
    within the budget — here execution re-submits a request per batch, so
    pending never reaches zero."""
    r = _EchoRunner()
    server = TconvServer({"echo": r}, candidate_batches=(1,),
                         default_batch=1)

    def resubmitting(x):
        server.submit("echo", np.zeros(4, np.float32))
        return np.zeros((1, 4), np.float32)

    r.jitted = lambda *, batch, precision="f32": resubmitting
    server.submit("echo", np.zeros(4, np.float32))
    with pytest.raises(TimeoutError, match="drain"):
        server.drain(timeout=0.2)
    assert server._batcher.pending() >= 1        # really never emptied


def test_server_stop_serves_requests_in_flight():
    """``stop()`` with queued requests drains them: every request is
    fulfilled, none left blocking its caller."""
    server = TconvServer({"echo": _EchoRunner()}, max_wait_s=60.0,
                         candidate_batches=(4,), default_batch=4)
    server.start()
    reqs = [server.submit("echo", np.zeros(4, np.float32))
            for _ in range(6)]
    server.stop()
    assert all(r.done() for r in reqs)
    outs = [r.result(timeout=0) for r in reqs]
    assert all(o.shape == (4,) for o in outs)
    s = server.stats()
    [b] = s["buckets"].values()
    assert b["completed"] == 6 and s["pending"] == 0


def test_server_stop_fails_unservable_requests_typed():
    """When execution cannot succeed at any ladder rung, ``stop()`` still
    settles every request — failed with a typed error, not wedged."""
    from repro.serve.resilience import LadderExhausted

    r = _EchoRunner()

    def broken(x):
        raise ValueError("permanently broken")

    r.jitted = lambda *, batch, precision="f32": broken
    server = TconvServer({"echo": r}, max_wait_s=60.0,
                         candidate_batches=(2,), default_batch=2)
    server.start()
    reqs = [server.submit("echo", np.zeros(4, np.float32))
            for _ in range(3)]
    server.stop()
    assert all(q.done() for q in reqs)
    for q in reqs:
        with pytest.raises(LadderExhausted):
            q.result(timeout=0)
    s = server.stats()
    [b] = s["buckets"].values()
    assert b["failed"] == 3 and s["pending"] == 0


# ---------------------------------------------------------------------------
# Compile cache placement (entry points call enable_compile_cache first).
# ---------------------------------------------------------------------------


def test_compile_cache_goes_where_the_environment_says(monkeypatch):
    from pathlib import Path

    from repro import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    # Set from outside: JAX reads the variable itself; no other path is set.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/caller")
    assert compile_cache.enable_compile_cache() == "/placed/by/caller"
    assert updates == []
    # Unset: the same fixed directory in the checkout, every run, and git
    # ignores it.
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
