"""Fault-tolerance runtime: preemption-safe training, elastic re-meshing,
straggler mitigation.

Design for 1000+ nodes (DESIGN.md §6):

* **Checkpoint/restart** — CheckpointManager (atomic + async) saves every
  ``ckpt_every`` steps; on restart the loop resumes from LATEST, and the
  data pipeline skips ahead deterministically (batches are pure functions
  of (seed, step) — no stream replay).
* **Elastic scaling** — ``elastic_mesh()`` builds the largest valid
  (data, model) mesh from *currently live* devices; checkpoints restore
  onto any topology (specs travel in the manifest).  A pod loss at 512
  chips => resume on 256 with the same global batch (per-device batch
  doubles) and identical numerics.
* **Straggler mitigation** — at-scale, the scheduler re-dispatches a slow
  shard's work; because batches are (seed, step)-pure, any host can
  recompute any shard.  ``StragglerSimulator`` injects synthetic delays to
  exercise the path in tests; on real clusters this hooks the collective
  timeout watchdog.
* **Preemption simulation** — ``PreemptionSimulator`` raises at a chosen
  step; tests assert bit-exact resume.

The injection primitives are shared with the *serving* chaos harness
(``serve/resilience.py``): ``FaultInjector`` composes a
``StragglerSimulator`` for per-batch stalls, and its transient-fault
retry uses :func:`jittered_backoff` — one backoff policy for training
re-dispatch and serving retries.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.mesh import make_mesh


class Preempted(RuntimeError):
    pass


@dataclasses.dataclass
class PreemptionSimulator:
    at_step: Optional[int] = None

    def check(self, step: int):
        if self.at_step is not None and step == self.at_step:
            raise Preempted(f"simulated preemption at step {step}")


@dataclasses.dataclass
class StragglerSimulator:
    """Inject per-step delay with probability p (tests the watchdog path).

    Deterministic per ``(seed, step)`` — replaying the same step sequence
    stalls the same steps — and observable via the ``stalls`` counter
    (the serving chaos harness surfaces it in ``server.stats()``).
    """
    p: float = 0.0
    delay_s: float = 0.05
    seed: int = 0
    stalls: int = 0

    def maybe_stall(self, step: int):
        if self.p <= 0:
            return False
        rng = np.random.default_rng((self.seed, step))
        if rng.random() < self.p:
            self.stalls += 1
            time.sleep(self.delay_s)
            return True
        return False


def jittered_backoff(attempt: int, *, base_s: float = 0.01,
                     jitter: float = 0.5,
                     rng: Optional[np.random.Generator] = None) -> float:
    """Exponential backoff with multiplicative jitter, in seconds.

    ``base_s * 2**attempt`` scaled by a uniform factor in
    ``[1 - jitter, 1 + jitter]`` — the jitter decorrelates retriers that
    failed together (the classic thundering-herd fix), and a caller-owned
    seeded ``rng`` keeps chaos tests deterministic.
    """
    rng = np.random.default_rng(0) if rng is None else rng
    jitter = min(max(float(jitter), 0.0), 1.0)
    scale = 1.0 + jitter * (2.0 * float(rng.random()) - 1.0)
    return float(base_s) * (2.0 ** attempt) * scale


def elastic_mesh(model_parallel: int = 1, devices=None):
    """Largest (data, model) mesh over the devices that are live NOW."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return make_mesh((n // mp, mp), ("data", "model"),
                     devices=devices[: (n // mp) * mp])


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    log_every: int = 10


class TrainLoop:
    """Preemption-safe training loop with deterministic skip-ahead."""

    def __init__(self, step_fn: Callable, state: Any, data_cfg: DataConfig,
                 loop_cfg: LoopConfig, ckpt: CheckpointManager,
                 mesh=None, specs: Any = None,
                 preempt: Optional[PreemptionSimulator] = None,
                 straggler: Optional[StragglerSimulator] = None,
                 log: Callable[[str], None] = print):
        self.step_fn, self.state = step_fn, state
        self.data_cfg, self.loop_cfg = data_cfg, loop_cfg
        self.ckpt, self.mesh, self.specs = ckpt, mesh, specs
        self.preempt = preempt or PreemptionSimulator()
        self.straggler = straggler or StragglerSimulator()
        self.log = log
        self.start_step = 0

    def resume(self):
        """Restore from LATEST if present (elastic: onto the current mesh)."""
        got = self.ckpt.restore_latest(self.state, mesh=self.mesh,
                                       specs=self.specs)
        if got[0] is not None:
            self.start_step = got[0]
            self.state = got[1]
            self.log(f"[resume] restored step {self.start_step}")
        return self.start_step

    def run(self) -> Any:
        metrics = {}
        for step in range(self.start_step, self.loop_cfg.total_steps):
            self.preempt.check(step)
            self.straggler.maybe_stall(step)
            batch = make_batch(self.data_cfg, step, self.mesh)
            self.state, metrics = self.step_fn(self.state, batch)
            if (step + 1) % self.loop_cfg.ckpt_every == 0:
                self.ckpt.save(step + 1, self.state, specs=self.specs)
            if (step + 1) % self.loop_cfg.log_every == 0:
                loss = float(jax.device_get(metrics.get("loss", np.nan)))
                self.log(f"[train] step {step + 1} loss {loss:.4f}")
        self.ckpt.wait()
        return self.state, metrics
