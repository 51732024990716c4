"""End-to-end system behaviour: training reduces loss; the launchers run;
the dry-run machinery works on a scaled mesh (subprocess: own XLA flags)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.data.pipeline import DataConfig, make_batch
from repro.models import lm
from repro.optim import adamw
from repro.runtime import steps as steps_mod
from repro.runtime.fault_tolerance import elastic_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_lm_training_reduces_loss():
    cfg = registry.get("qwen2.5-3b").smoke
    mesh = elastic_mesh(1)
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40,
                                schedule="constant")
    with jax.set_mesh(mesh):
        bundle = steps_mod.make_train_step(cfg, mesh, opt_cfg, batch=4,
                                           seq=32, donate=False)
        params, _ = lm.init(cfg, jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw.init(params, opt_cfg)}
        data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
        losses = []
        for step in range(30):
            batch = make_batch(data, step % 2, mesh)  # 2 repeating batches
            state, m = bundle.fn(state, batch)
            losses.append(float(jax.device_get(m["loss"])))
    assert losses[-1] < losses[0] - 0.5, losses[::6]


def test_prefill_step_runs():
    cfg = registry.get("internvl2-1b").smoke
    mesh = elastic_mesh(1)
    with jax.set_mesh(mesh):
        bundle = steps_mod.make_prefill_step(cfg, mesh, batch=2, seq=16)
        params, _ = lm.init(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jnp.zeros((2, 16), jnp.int32),
            "prefix_embeds": jnp.zeros((2, cfg.frontend_len, cfg.d_model),
                                       jnp.bfloat16),
        }
        out = bundle.fn(params, batch)
        assert out.shape[0] == 2 and not bool(jnp.isnan(out).any())


def test_decode_step_runs_and_advances_cache():
    cfg = registry.get("recurrentgemma-9b").smoke
    mesh = elastic_mesh(1)
    with jax.set_mesh(mesh):
        bundle = steps_mod.make_decode_step(cfg, mesh, batch=2, seq=32)
        params, _ = lm.init(cfg, jax.random.PRNGKey(0))
        cache = lm.init_cache(cfg, 2, 32, length=8)
        logits, cache2 = bundle.fn(params, cache,
                                   jnp.zeros((2, 1), jnp.int32))
        assert int(cache2.length) == 9
        assert not bool(jnp.isnan(logits).any())


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """The real dry-run entry point on a scaled (4-device) mesh."""
    env = dict(os.environ, DRYRUN_DEVICES="4", DRYRUN_MESH="2x2",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2.5-3b",
         "--shape", "decode_32k"],
        capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert "1/1 cells compiled" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_compressed_pod_trainstep_subprocess():
    """int8 cross-pod gradient compression: compile + run on a 2x2x2 mesh."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import registry
from repro.runtime import steps as steps_mod
from repro.models import lm
from repro.optim import adamw
from repro.data.pipeline import DataConfig, make_batch
cfg = registry.get("qwen2.5-3b").smoke
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
with jax.set_mesh(mesh):
    b = steps_mod.make_train_step_compressed(cfg, mesh, batch=4, seq=16)
    params, specs = lm.init(cfg, jax.random.PRNGKey(0))
    opt_cfg = adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype)
    state = {"params": params, "opt": adamw.init(params, opt_cfg)}
    err = jax.tree.map(lambda p: jnp.zeros((2,) + p.shape, jnp.float32), params)
    batch = make_batch(DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4), 0, mesh)
    state, err, m = b.fn(state, err, batch)
    loss = float(jax.device_get(m["loss"]))
    assert loss == loss and loss < 20, loss
    print("COMPRESSED_OK", loss)
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=560, cwd=REPO)
    assert "COMPRESSED_OK" in out.stdout, out.stdout + out.stderr[-2000:]
