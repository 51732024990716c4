"""Recorded v5e traces held to the numbers ``trace.reduce_events`` gave
them when they were recorded, keyed by the trace file's name."""

import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")

PINNED = {
    "dcgan.int8.poisson.v5e.xplane.pb": {
        "window_s": 0.453107978, "busy_s": 0.057380365,
        "kernel_s": 0.025011058, "kernel_events": 744, "n_devices": 1,
        "device_ops": [0.013163485, 0.008924374, 0.008134858, 0.007927624,
                       0.004529598, 0.003629462, 0.001841996, 0.001753305,
                       0.00133644, 0.000665841],
        "idle_gaps": [
            ("np.asarray(jax.Array)", 0.354363195),
            ("chipbench.await_arrival", 0.041359636),
            ("shard_args", 4.167e-06),
            ("PjitFunction(_dcgan_forward)", 3.4e-07),
            ("XlaLinearize", 1.36e-07), ("host idle", 6.5e-08),
            ("chipbench.submit", 5e-08), ("H2D Dispatch", 2.4e-08)],
    },
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_recorded_trace_reduces_to_its_numbers(name):
    want = PINNED[name]
    host, devices = trace.load_events(os.path.join(DATA, name))
    r = trace.reduce_events(host, devices,
                            trace.kernel_spec()["tconv_kernel_patterns"])
    for key in ("window_s", "busy_s", "kernel_s"):
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    assert (r["kernel_events"], r["n_devices"]) == (want["kernel_events"],
                                                    want["n_devices"])
    assert [v for _, v in r["breakdown"]["device_ops"]] == pytest.approx(
        want["device_ops"], rel=1e-6)
    idle = r["breakdown"]["idle_gaps"]
    assert [n for n, _ in idle] == [n for n, _ in want["idle_gaps"]]
    assert [v for _, v in idle] == pytest.approx(
        [v for _, v in want["idle_gaps"]], rel=1e-6)
