"""The serving loop's phase counters (``serve/telemetry.py``): one count
per phase per batch, the wait of a due batch for the drain thread on an
injected clock, the drain loop's own counters, and that the drain
thread's counted seconds cover its lifetime."""

import collections
import time

import numpy as np
import pytest

from repro.serve import resilience, server as server_mod, telemetry
from repro.serve.batcher import Batcher, FLUSH_DEADLINE, FLUSH_FULL, Request
from repro.serve.server import TconvServer

BATCH_PHASES = ("serve.pad", "serve.put", "serve.dispatch", "serve.fetch",
                "serve.fulfil")


class _SleepRunner:
    """Duck-typed runner: each call sleeps ``call_s`` and returns zeros."""

    name = "sleep"

    def __init__(self, call_s=0.0):
        self.call_s = call_s

    def input_shape(self):
        return (4,)

    def tconv_problems(self):
        return {}

    def has_compiled(self, *, batch, precision="f32"):
        return True

    def jitted(self, *, batch, precision="f32"):
        def fn(x):
            time.sleep(self.call_s)
            return np.zeros((batch, 4), np.float32)
        return fn


def _server(call_s=0.0, *, max_wait_s=60.0, batch=2):
    return TconvServer({"sleep": _SleepRunner(call_s)}, max_wait_s=max_wait_s,
                       candidate_batches=(batch,), default_batch=batch)


def _submit(server):
    return server.submit("sleep", np.zeros(4, np.float32))


def _bucket(server):
    [b] = server.stats()["buckets"].values()
    return b


def test_batch_due_times():
    b = Batcher(max_wait_s=0.5)
    reqs = [Request(0, "m", None, "f32", 10.0),
            Request(1, "m", None, "f32", 10.2)]
    assert b.due(reqs, FLUSH_FULL, 11.0) == 10.2       # at its last member
    assert b.due(reqs[:1], FLUSH_DEADLINE, 11.0) == 10.5  # head + max_wait
    assert b.due(reqs[:1], FLUSH_DEADLINE, 10.1) == 10.1  # forced: its tick


def test_each_batch_passes_each_phase_once(monkeypatch):
    seen = collections.Counter()
    real = telemetry.phase

    def counting(name, stats=None, **ids):
        seen[name] += 1
        return real(name, stats, **ids)

    monkeypatch.setattr(server_mod, "phase", counting)
    monkeypatch.setattr(resilience, "phase", counting)
    server = _server(call_s=0.01)
    for _ in range(5):
        _submit(server)
    assert server.serve_once(force=True) == 5   # two full, one forced
    assert server.serve_once() == 0
    b, s = _bucket(server), server.stats()
    assert b["batches"] == 3
    assert all(seen[name] == 3 for name in BATCH_PHASES), seen
    assert seen["serve.submit"] == 5
    assert seen["serve.form"] == s["ticks"] == 2
    assert s["empty_ticks"] == 1
    assert b["dispatch_s"] >= 3 * 0.01           # the calls' own sleeps
    assert all(b[f] > 0 for f in ("pad_s", "put_s", "fetch_s", "fulfil_s"))
    assert s["form_s"] > 0 and s["drain_wait_s"] == 0.0  # no drain thread


def test_due_wait_counts_a_full_batch_behind_another():
    server = _server(call_s=0.1)
    reqs = [_submit(server) for _ in range(2)]
    assert server.serve_once(now=reqs[-1].t_enqueue) == 2
    alone = _bucket(server)["due_wait_s"]
    assert alone < 0.05                          # ran as it became due
    reqs = [_submit(server) for _ in range(4)]
    assert server.serve_once(now=reqs[-1].t_enqueue) == 4
    assert [r.batch for r in reqs] == [2, 2, 3, 3]
    # The second batch was due at the tick and waited for the first's call.
    behind = _bucket(server)["due_wait_s"] - alone
    assert 0.1 <= behind < 1.0


def test_due_wait_of_deadline_and_forced_flushes():
    server = _server(max_wait_s=0.2)
    r = _submit(server)
    assert server.serve_once(now=r.t_enqueue + 0.1) == 0  # not due yet
    assert server.serve_once(now=r.t_enqueue + 0.5) == 1  # due at +0.2
    b = _bucket(server)
    assert b["due_wait_s"] == pytest.approx(0.3, abs=0.05)
    # queue_wait_mean_s runs from enqueue to the tick that popped the batch.
    assert b["queue_wait_mean_s"] == pytest.approx(0.5)
    r = _submit(server)
    assert server.serve_once(now=r.t_enqueue + 0.05, force=True) == 1
    assert _bucket(server)["due_wait_s"] - b["due_wait_s"] < 0.05
    assert r.batch == 2


def test_drain_thread_counters_cover_its_time():
    """The drain thread's spans are flat and cover its loop, so their
    counters add up to its lifetime."""
    server = _server(call_s=0.005, max_wait_s=0.02)
    t0 = time.monotonic()
    server.start()
    reqs = []
    for _ in range(15):
        reqs.append(_submit(server))
        time.sleep(0.01)
    for r in reqs:
        r.result(timeout=30)
    time.sleep(0.1)
    server.stop()
    wall = time.monotonic() - t0
    s, b = server.stats(), _bucket(server)
    counted = s["form_s"] + s["drain_wait_s"] + sum(
        b[telemetry.COUNTERS[name]] for name in BATCH_PHASES)
    assert 0.9 * wall <= counted <= wall, (counted, wall)
    assert b["completed"] == 15 and s["empty_ticks"] > 0
