"""Arrival schedules, due-time latency and lateness arithmetic."""

import numpy as np
import pytest

from chipbench import loadgen

POISSON = {"loop": "open", "phases": [{"seconds": 1.0, "rate": 500}]}
BURST = {"loop": "open", "phases": [{"seconds": 0.5, "rate": 1500},
                                    {"seconds": 1.5, "rate": 0}]}


def test_schedule_is_seeded_sorted_and_of_fixed_size():
    a = loadgen.open_schedule(POISSON, 4.0, 2 ** 33 + 1)
    b = loadgen.open_schedule(POISSON, 4.0, 2 ** 33 + 1)
    c = loadgen.open_schedule(POISSON, 4.0, 2 ** 33 + 2)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # Every seed offers the same work: 500/s for 4 s, only the times move.
    assert a.size == c.size == 2000
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 4.0


def test_burst_phases():
    s = loadgen.open_schedule(BURST, 4.0, 3)
    assert s.size == 1500
    on = ((s % 2.0) < 0.5)
    assert on.all()
    assert loadgen.offered_rate(BURST) == pytest.approx(375.0)


def test_quantile_is_a_real_sample():
    v = np.arange(1, 101, dtype=float)
    assert loadgen.quantile(v, 0.5) == 50
    assert loadgen.quantile(v, 0.95) == 95
    assert loadgen.quantile(v[::-1], 0.95) == 95
    assert loadgen.quantile([7.0], 0.95) == 7.0


def _served(due, service):
    """A single server taking requests in due order, each ``service`` s."""
    done = np.empty_like(due)
    free = 0.0
    for i, d in enumerate(due):
        free = max(free, d) + service
        done[i] = free
    return done


def test_a_stall_moves_the_tail_and_the_rate():
    due = np.arange(0.0, 10.0, 0.01)              # 100/s for 10 s
    ok = np.ones(due.size, bool)
    done = _served(due, 0.002)
    base = loadgen.latencies(due, done, ok, give_up=11.0)
    assert loadgen.quantile(base, 0.95) == pytest.approx(0.002)
    # The server stalls 1 s at t=5: requests due in the stall wait for it,
    # though they were sent on time; the due time counts the wait.
    stalled = done.copy()
    hit = (due >= 5.0) & (due < 6.0)
    stalled[hit] = 6.0 + 0.002 * np.arange(1, hit.sum() + 1)
    after = due >= 6.0
    stalled[after] = np.maximum(stalled[after], stalled[hit][-1]
                                + 0.002 * np.arange(1, after.sum() + 1))
    lat = loadgen.latencies(due, stalled, ok, give_up=11.0)
    assert loadgen.quantile(lat, 0.95) > 0.5
    assert loadgen.quantile(lat, 0.50) == pytest.approx(0.002, abs=1e-9)
    # Completions inside [4, 6): the stall removes a second of them.
    assert loadgen.window_rate(done, ok, 4.0, 6.0) == pytest.approx(100.0)
    assert loadgen.window_rate(stalled, ok, 4.0, 6.0) < 55.0


def test_missing_requests_count_as_the_longest_wait():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    done = np.array([0.01, np.nan, 0.21, 0.31])
    ok = np.array([True, False, True, False])
    lat = loadgen.latencies(due, done, ok, give_up=5.0)
    np.testing.assert_allclose(lat, [0.01, 4.9, 0.01, 4.7])
    assert loadgen.window_rate(done, ok, 0.0, 1.0) == pytest.approx(2.0)


def test_lateness():
    due = np.array([1.0, 2.0, 3.0])
    sent = np.array([1.002, 1.9, 3.5])
    np.testing.assert_allclose(loadgen.lateness(due, sent), [0.002, 0, 0.5])


def test_longest_stall_between_batch_completions():
    from chipbench import harness

    # Batches complete every 10 ms, with one 300 ms hole from t=5.0; the
    # window's edges count as completions, and times outside it do not.
    done = np.concatenate([np.arange(4.0, 5.0, 0.01),
                           np.arange(5.3, 7.0, 0.01)])
    assert harness.longest_stall(np.repeat(done, 8), 4.0, 7.0) == \
        pytest.approx(0.31)
    assert harness.longest_stall(done, 5.5, 6.0) == pytest.approx(0.01)
    assert harness.longest_stall([np.nan, 9.0], 0.0, 2.0) == 2.0


def _sweep():
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[2] / "chipbench" / "sweep.py"
    spec = importlib.util.spec_from_file_location("chipbench_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _point(rate, completed, p95, missing=0):
    return {"rate": rate, "completed_per_s": completed, "p95_ms": p95,
            "missing": missing}


def test_knee_is_the_last_rate_that_keeps_up():
    sweep = _sweep()
    lines = [_point(500, 500, 16.0), _point(1000, 1000, 42.0),
             _point(2000, 1999, 7.0), _point(2500, 2490, 9.0),
             _point(3000, 2995, 8.0), _point(3000, 2700, 30.0),
             _point(3500, 3480, 100.0)]
    # 3000/s fell behind in one of its two windows, so 3500/s does not
    # count though it kept up; a tail that swings does not decide.
    assert sweep.knee(lines) == 2500
    assert sweep.knee(lines[:3] + [_point(2500, 2000, 9.0)]) == 2000
    assert sweep.knee(lines[:3] + [_point(2500, 2500, 9.0, missing=3)]) \
        == 2000
    assert sweep.knee([]) is None
    assert sweep.cell_rate(3500) == 2800
    assert sweep.cell_rate(430) == 340
