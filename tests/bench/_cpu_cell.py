"""Drive whole benchmark runs on the CPU at reduced widths (the chip look
skipped), for the tests of the check."""

import os
import time

import jax.numpy as jnp

from chipbench import harness, plain

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SMALL = {"dcgan-64": {"base": 64},
         "pix2pix-256": {"base": 16, "depth": 5, "image_size": 32}}
SMALL_TRAFFIC = {"closed": {"pool": 32},
                 "open": {"phases": [{"seconds": 1.0, "rate": 60}],
                          "pool": 64}}


def cell(name):
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    w = {w["name"]: w for w in bench["workloads"]}[name]
    traffic = harness.read_json(os.path.join(
        ROOT, "chipbench", "traffic", f"{w['traffic']}.json"))
    return harness.Cell(bench, name, cfg_update=SMALL[w["config"]],
                        traffic_update=SMALL_TRAFFIC[traffic["loop"]])


def run(c, seed, hook=None, monkeypatch=None, tmp_path=None):
    if monkeypatch is not None:
        monkeypatch.setattr(harness, "STATE_DIR", tmp_path)
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "p.json"))
    return harness.run_cell(c, seed, 2.0, False, t_start=time.perf_counter(),
                            require_device=False, compile_cache=False,
                            server_hook=hook)


def _runner(server):
    (runner,) = server.runners.values()
    return runner


def alter_answer(server):
    """Fault: answers altered where they are produced: the first two
    requests of every batch get each other's images."""
    runner = _runner(server)
    orig = runner.jitted

    def jitted(*, batch, precision="f32"):
        fn = orig(batch=batch, precision=precision)
        return lambda x: fn(x)[jnp.array([1, 0] + list(range(2, batch)))]

    runner.jitted = jitted


def reference_in_place(c, **changes):
    """Control: the reference at a lower precision in the program's place."""
    import jax

    def hook(server):
        runner = _runner(server)
        prec = harness.reference_prec(c, **changes)
        if c.precision == "int8":
            sc = plain.int_scales(plain.calibrate(c.model, runner.params,
                                                  c.cfg, prec), prec.int_bits)
            f = jax.jit(lambda p, x: plain.forward_int(c.model, p, x, c.cfg,
                                                       prec, sc))
        else:
            f = jax.jit(lambda p, x: plain.forward_f32(c.model, p, x, c.cfg,
                                                       prec))
        runner.jitted = lambda *, batch, precision="f32": (
            lambda x: f(runner.params, x))

    return hook


def compared(result, c):
    """The number the cell's check compares, with its limit."""
    (name,) = c.limits["limits"]
    return result["checks"][name]["value"], result["checks"][name]["limit"]
