"""Fault tolerance: preemption resume bit-exactness, stragglers, elastic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.data.pipeline import DataConfig
from repro.models import lm
from repro.optim import adamw
from repro.runtime import steps as steps_mod
from repro.runtime.fault_tolerance import (LoopConfig, Preempted,
                                           PreemptionSimulator,
                                           StragglerSimulator, TrainLoop,
                                           elastic_mesh)


def _setup(tmp_path, total=8, ckpt_every=2):
    cfg = registry.get("qwen2.5-3b").smoke
    mesh = elastic_mesh(1)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0, schedule="constant")
    with jax.set_mesh(mesh):
        bundle = steps_mod.make_train_step(cfg, mesh, opt_cfg, batch=2, seq=16,
                                           donate=False)
        params, specs = lm.init(cfg, jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw.init(params, opt_cfg)}
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=2)
    loop = TrainLoop(bundle.fn, state, data_cfg,
                     LoopConfig(total_steps=total, ckpt_every=ckpt_every,
                                log_every=100),
                     CheckpointManager(tmp_path), mesh=mesh,
                     specs={"params": specs, "opt": adamw.state_specs(specs)},
                     log=lambda *_: None)
    return loop, mesh


def test_preemption_then_resume_bit_exact(tmp_path):
    # Uninterrupted run.
    loop_a, mesh = _setup(tmp_path / "a")
    with jax.set_mesh(mesh):
        state_a, _ = loop_a.run()

    # Interrupted at step 5, then resumed.
    loop_b, _ = _setup(tmp_path / "b")
    loop_b.preempt = PreemptionSimulator(at_step=5)
    with jax.set_mesh(mesh):
        with pytest.raises(Preempted):
            loop_b.run()
        loop_c, _ = _setup(tmp_path / "b")
        assert loop_c.resume() == 4  # last multiple of ckpt_every before 5
        state_c, _ = loop_c.run()

    for a, c in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(state_c["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), rtol=1e-6,
                                   atol=1e-6)


def test_straggler_simulator_deterministic():
    s = StragglerSimulator(p=0.5, delay_s=0.0, seed=1)
    hits1 = [s.maybe_stall(i) for i in range(20)]
    hits2 = [s.maybe_stall(i) for i in range(20)]
    assert hits1 == hits2
    assert any(hits1) and not all(hits1)


def test_elastic_mesh_uses_all_devices():
    m = elastic_mesh(1)
    assert m.devices.size == len(jax.devices())
    assert m.axis_names == ("data", "model")
