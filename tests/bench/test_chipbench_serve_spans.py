"""The serving loop's spans as the benchmark's trace reader sees them, and
its counters as the benchmark's ``serve_*`` metrics read them."""

import os
import time

import numpy as np
import pytest

from chipbench import harness, trace

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "chipbench")
DRAIN_SPANS = ("serve.wait", "serve.form", "serve.pad", "serve.put",
               "serve.dispatch", "serve.fetch", "serve.fulfil")
BATCH_SPANS = DRAIN_SPANS[2:]


def _stats(path):
    """(name, stats dict) of every ``serve.*`` host event."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        out.append((e.name, dict(e.stats)))
    return out


def test_drain_thread_spans_reach_the_trace(tmp_path):
    import jax

    from repro.models.runner import make_runner
    from repro.serve import TconvServer

    runner = make_runner("fsrcnn", key=jax.random.PRNGKey(0),
                         init_kw={"d": 8, "s": 4, "m": 1}, input_hw=8)
    server = TconvServer({"fsrcnn": runner}, max_wait_s=0.02,
                         candidate_batches=(2,), default_batch=2)
    x = np.asarray(runner.example_inputs(1, seed=0))[0]
    with jax.profiler.trace(str(tmp_path)):
        with server:
            reqs = [server.submit("fsrcnn", x) for _ in range(5)]
            for r in reqs:
                r.result(timeout=300)
    path = trace.find_xplane(tmp_path)
    host, _ = trace.load_events(path)

    drain = sorted((s, e, n) for n, _, s, e in host if n in DRAIN_SPANS)
    assert {n for _, _, n in drain} == set(DRAIN_SPANS)
    for (_, e, _), (s, _, _) in zip(drain, drain[1:]):
        assert e <= s, "the drain thread's spans do not overlap"
    assert sum(n == "serve.submit" for n, _, _, _ in host) == 5

    batches = {r.batch for r in reqs}
    assert batches == {1, 2, 3}                 # two full, one partial
    events = _stats(path)
    for name in BATCH_SPANS:
        ids = [st.get("batch") for n, st in events if n == name]
        assert set(ids) == batches, (name, ids)
    assert all({"rung", "attempt", "compiled"} <= st.keys()
               for n, st in events if n == "serve.dispatch")
    assert sorted(st["rid"] for n, st in events if n == "serve.submit") \
        == sorted(r.rid for r in reqs)


SERVE_METRICS = ("serve_host_ms", "serve_fetch_ms", "serve_due_wait_ms")


def _reader(name):
    return harness.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                               "test_metric_" + name)


def _ctx(batches, **fields):
    zero = dict.fromkeys(fields, 0.0)
    return harness.Context(stats0=dict(zero, batches=10),
                           stats1=dict(fields, batches=10 + batches))


@pytest.mark.parametrize("name, fields, want", [
    ("serve_host_ms", {"pad_s": 0.04, "put_s": 0.01, "dispatch_s": 0.02,
                       "fulfil_s": 0.01, "fetch_s": 9.0}, 2.0),
    ("serve_fetch_ms", {"fetch_s": 0.16, "pad_s": 9.0}, 4.0),
    ("serve_due_wait_ms", {"due_wait_s": 0.4, "fetch_s": 9.0}, 10.0),
])
def test_serve_metrics_read_their_counters_per_batch(name, fields, want):
    assert _reader(name).read(_ctx(40, **fields)) == pytest.approx(want)
    assert _reader(name).read(_ctx(0, **fields)) is None   # no batch


@pytest.mark.parametrize("name", SERVE_METRICS)
def test_serve_metrics_read_nothing_without_the_counters(name):
    """A server without the phase counters (an older program) gives no
    reading, and no error."""
    assert _reader(name).read(_ctx(40, completed=320.0)) is None


class _SleepRunner:
    """Duck-typed runner: each call sleeps ``call_s`` and returns zeros."""

    def __init__(self, call_s):
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


def test_serve_metrics_read_a_servers_counters():
    """On ``bucket_stats`` of a running server: three batches of 2 (two
    full, one forced) from one tick, each call 10 ms.  The second full
    batch waits one call for the drain thread, the forced one two."""
    from repro.serve import TconvServer

    call_s = 0.01
    server = TconvServer({"s": _SleepRunner(call_s)}, max_wait_s=60.0,
                         candidate_batches=(2,), default_batch=2)
    x = np.zeros(4, np.float32)
    for _ in range(2):
        server.submit("s", x)
    server.serve_once(force=True)
    stats0 = harness.bucket_stats(server)
    for _ in range(5):
        server.submit("s", x)
    server.serve_once(force=True)
    ctx = harness.Context(stats0=stats0, stats1=harness.bucket_stats(server))
    assert ctx.stat_delta("batches") == 3
    got = {name: _reader(name).read(ctx) for name in SERVE_METRICS}
    assert got["serve_host_ms"] >= 1e3 * call_s      # the call dispatches
    assert 0.0 <= got["serve_fetch_ms"] < got["serve_host_ms"]
    assert got["serve_due_wait_ms"] >= 1e3 * call_s  # (0 + 1 + 2) calls / 3
