"""The reduction from a profiler trace to busy time, kernel time and the
breakdown: on synthetic events, and on a small trace recorded on a TPU
v5e (``data/``)."""

import glob
import os

import pytest

from chipbench import trace

HERE = os.path.dirname(__file__)
MS = 1_000_000  # ns


def _host(name, s, e, thread="python"):
    return (name, thread, s * MS, e * MS)


def _dev(name, s, e, long_name=""):
    return (name, long_name, s * MS, e * MS)


def test_busy_is_the_union_inside_the_window():
    host = [_host("chipbench.window", 10, 110)]
    dev = {"/device:TPU:0": [
        _dev("fusion.1", 0, 20),            # half outside the window
        _dev("mm2im_tconv", 15, 30),         # overlaps fusion.1
        _dev("mm2im_tconv", 50, 60),
        _dev("copy.3", 105, 130),            # clipped at 110
    ]}
    r = trace.reduce_events(host, dev, ["mm2im"])
    assert r["window_s"] == pytest.approx(0.100)
    # [10, 30] + [50, 60] + [105, 110]
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["kernel_s"] == pytest.approx(0.025)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["mm2im_tconv"] == pytest.approx(0.025)
    assert ops["fusion.1"] == pytest.approx(0.010)


def test_busy_is_averaged_over_devices_and_kernels_match_long_names():
    host = [_host("chipbench.window", 0, 100)]
    dev = {"/device:TPU:0": [_dev("custom-call.7", 0, 40, "mm2im kernel")],
           "/device:TPU:1": [_dev("fusion", 0, 20)]}
    r = trace.reduce_events(host, dev, ["mm2im"])
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["kernel_s"] == pytest.approx(0.020)
    assert r["n_devices"] == 2


def test_a_kernel_event_matches_every_pattern():
    host = [_host("chipbench.window", 0, 100)]
    dev = {"/device:TPU:0": [
        _dev("%_dispatch_impl.4 = custom-call() tpu_custom_call", 0, 10),
        _dev("%other.2 = custom-call() tpu_custom_call", 20, 50),
        _dev("%_dispatch_impl.5 = reshape()", 60, 70)]}
    r = trace.reduce_events(host, dev, ["tpu_custom_call", "%_dispatch_impl"])
    assert r["kernel_s"] == pytest.approx(0.010)
    assert r["kernel_events"] == 1
    assert r["busy_s"] == pytest.approx(0.050)


def test_idle_gaps_go_to_what_the_host_was_doing():
    host = [_host("chipbench.window", 0, 100),
            _host("chipbench.client_wait", 0, 100, "client0"),
            _host("PjitFunction(fn)", 21, 29, "drain"),
            _host("ToLiteral", 62, 78, "drain")]
    dev = {"/device:TPU:0": [_dev("a", 0, 20), _dev("b", 30, 60),
                             _dev("c", 80, 90)]}
    r = trace.reduce_events(host, dev, ["mm2im"])
    idle = dict(r["breakdown"]["idle_gaps"])
    assert idle["PjitFunction(fn)"] == pytest.approx(0.010)
    assert idle["ToLiteral"] == pytest.approx(0.020)
    # The last gap overlaps only the clients' background wait.
    assert idle["chipbench.client_wait"] == pytest.approx(0.010)
    assert r["kernel_s"] == 0.0


def test_no_window_or_no_device_reads_nothing():
    assert trace.reduce_events([], {"/device:TPU:0": []}, ["mm2im"]) == {}
    assert trace.reduce_events([_host("chipbench.window", 0, 1)], {},
                               ["mm2im"]) == {}


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_chip_trace(path):
    host, devices = trace.load_events(path)
    assert devices, "the recorded trace holds a TPU plane"
    r = trace.reduce_events(host, devices,
                            trace.kernel_spec()["tconv_kernel_patterns"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["kernel_s"] < r["busy_s"]
    # DCGAN has four TCONV layers: the kernel events come in fours.
    assert r["kernel_events"] > 0 and r["kernel_events"] % 4 == 0
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert r["breakdown"]["idle_gaps"]
