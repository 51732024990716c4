"""Training launcher: ``python -m repro.launch.train --arch qwen2.5-3b``.

On CPU this runs the *smoke* config by default (use ``--full`` on real
hardware).  Demonstrates the full substrate: sharded synthetic data,
AdamW, activation sharding, async checkpointing, preemption-safe resume,
elastic re-meshing.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import registry
from repro.data.pipeline import DataConfig
from repro.checkpoint.manager import CheckpointManager
from repro.optim import adamw
from repro.models import lm
from repro.runtime import steps as steps_mod
from repro.runtime.fault_tolerance import (LoopConfig, PreemptionSimulator,
                                           TrainLoop, elastic_mesh)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (needs real HW)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--preempt-at", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    spec = registry.get(args.arch)
    cfg = spec.model if args.full else spec.smoke
    mesh = elastic_mesh(args.model_parallel)
    print(f"[mesh] {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"arch={cfg.name}")

    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(args.steps // 10, 1),
                                state_dtype=cfg.opt_state_dtype)
    with jax.set_mesh(mesh):
        bundle = steps_mod.make_train_step(cfg, mesh, opt_cfg,
                                           batch=args.batch, seq=args.seq)
        params, specs = lm.init(cfg, jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw.init(params, opt_cfg)}

        data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                              global_batch=args.batch)
        ckpt = CheckpointManager(args.ckpt_dir)
        loop = TrainLoop(
            bundle.fn, state, data_cfg,
            LoopConfig(total_steps=args.steps,
                       ckpt_every=max(args.steps // 3, 1), log_every=5),
            ckpt, mesh=mesh,
            specs={"params": specs, "opt": adamw.state_specs(specs)},
            preempt=PreemptionSimulator(args.preempt_at))
        if args.resume:
            loop.resume()
        state, metrics = loop.run()
        print(f"[done] final loss "
              f"{float(jax.device_get(metrics['loss'])):.4f}")


if __name__ == "__main__":
    main()
