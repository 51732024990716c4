"""The harness: device refusal, files found by name, the result line."""

import json
import os
import shutil

import pytest

from chipbench import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "chipbench")


class _Dev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


class _Jax:
    def __init__(self, *devs):
        self._devs = list(devs)

    def devices(self):
        return self._devs


PEAKS = harness.read_json(os.path.join(BENCH, "peaks.json"))


def test_refuses_a_device_that_is_not_a_tpu():
    with pytest.raises(harness.NoDevice, match="not a TPU"):
        harness.check_device(_Jax(_Dev("cpu", "cpu")), 1, PEAKS)


def test_refuses_an_unknown_device_kind():
    with pytest.raises(harness.NoDevice, match="peak table"):
        harness.check_device(_Jax(_Dev("tpu", "TPU v9 imaginary")), 1, PEAKS)


def test_refuses_too_few_chips():
    with pytest.raises(harness.NoDevice, match="asks for 4"):
        harness.check_device(_Jax(_Dev("tpu", "TPU v5 lite")), 4, PEAKS)


def test_accepts_the_v5e():
    dev = harness.check_device(_Jax(*[_Dev("tpu", "TPU v5 lite")] * 4), 1,
                               PEAKS)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_run_exits_nonzero_without_a_result_off_the_chip(tmp_path, monkeypatch,
                                                         capsys):
    import importlib.util

    monkeypatch.setattr(harness, "STATE_DIR", tmp_path)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "x.json"))
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", "dcgan.int8.poisson", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 3
    assert out.out == ""
    assert "not a TPU" in out.err


def _snapshot(path):
    return {os.path.relpath(os.path.join(d, f), path):
            os.path.getmtime(os.path.join(d, f))
            for d, _, fs in os.walk(path) for f in fs
            if "__pycache__" not in d}


def test_new_config_traffic_and_metric_files_are_found_by_name(tmp_path):
    before = _snapshot(BENCH)
    bench_dir = tmp_path / "chipbench"
    for sub in ("traffic", "metrics", "reference", "limits", "configs"):
        (bench_dir / sub).mkdir(parents=True)
    # A new configuration: its file, its reference and its limits.
    cfg = harness.read_json(os.path.join(BENCH, "configs", "dcgan-64.json"))
    cfg.update(name="dcgan-new", reference="dcgan-new", base=512)
    cfg_file = bench_dir / "configs" / "dcgan-new.json"
    cfg_file.write_text(json.dumps(cfg))
    shutil.copy(os.path.join(BENCH, "reference", "dcgan-64.py"),
                bench_dir / "reference" / "dcgan-new.py")
    (bench_dir / "limits" / "dcgan-new.int8.json").write_text(json.dumps(
        {"reference": {"xla": "default"}, "limits": {"max_abs_err": 1.0},
         "min_batches": 1}))
    # A new traffic mix: bursts.
    (bench_dir / "traffic" / "burst.int8.json").write_text(json.dumps(
        {"loop": "open", "precision": "int8", "target_batch": 8,
         "max_wait_s": 0.05, "pool": 64,
         "phases": [{"seconds": 0.5, "rate": 3000},
                    {"seconds": 1.5, "rate": 500}]}))
    # A new per-layer metric.
    (bench_dir / "metrics" / "batches_seen.py").write_text(
        "def read(ctx):\n    return ctx.stat_delta('batches')\n")
    bench = {
        "configs": [{"name": "dcgan-new", "file": str(cfg_file)}],
        "workloads": [{"name": "dcgan-new.int8.burst", "config": "dcgan-new",
                       "traffic": "burst.int8", "chips": 1}],
        "end_to_end": [{"name": "latency_p95_ms", "unit": "ms"}],
        "per_layer": [{"name": "batches_seen", "unit": "batches",
                       "workloads": ["dcgan-new.int8.burst"]},
                      {"name": "elsewhere", "unit": "x",
                       "workloads": ["another.cell"]}],
    }
    cell = harness.Cell(bench, "dcgan-new.int8.burst", bench_dir=bench_dir)
    assert cell.cfg["base"] == 512 and cell.precision == "int8"
    assert cell.traffic["phases"][0]["rate"] == 3000
    assert [m["name"] for m in cell.per_layer] == ["batches_seen"]
    assert cell.model.param_shapes(cell.cfg)["t1"] == (5, 5, 256, 512)
    ctx = harness.Context(stats0={"batches": 10}, stats1={"batches": 52})
    got = harness.read_per_layer(cell, ctx, bench_dir=bench_dir)
    assert got == {"batches_seen": {"value": 42.0, "unit": "batches"}}
    assert _snapshot(BENCH) == before


def test_every_listed_file_exists():
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        assert cell.layers
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))
