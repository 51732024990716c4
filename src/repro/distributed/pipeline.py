"""GPipe-style pipeline parallelism over the 'pod' axis.

DESIGN.md §6 names PP as the optional strategy for cross-pod-bound
workloads: instead of replicating the model across pods and paying the DCN
gradient all-reduce, the `pod` axis is re-purposed as a pipeline axis —
each pod holds a *stage* (a contiguous slice of layers) and microbatch
activations flow pod-to-pod through `ppermute` (activations are orders of
magnitude smaller than gradients for deep models).

`pipeline_apply` is the schedule primitive: a manual shard_map over 'pod'
running the classic GPipe bubble schedule (T = n_micro + n_stages - 1
ticks).  Stage s processes microbatch m at tick t = m + s; stage 0 injects
inputs; the last stage's outputs are collected and broadcast.  All stages
execute the same SPMD program — per-stage behaviour is `jnp.where` /
dynamic indexing on `lax.axis_index('pod')`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, *,
                   mesh, axis: str = "pod"):
    """Run microbatches through pipeline stages sharded over ``axis``.

    Args:
      stage_fn: (params_one_stage, activation (mb, ...)) -> activation.
      stage_params: pytree with leading dim = n_stages (sharded over axis).
      x_micro: (n_micro, mb, ...) inputs (replicated over axis).
      mesh: mesh containing ``axis``.
    Returns:
      (n_micro, mb, ...) outputs of the final stage (replicated over axis).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params_local, xs):
        params_one = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)   # from previous stage
        outputs = jnp.zeros((n_micro,) + mb_shape, xs.dtype)

        def tick(t, state):
            carry_in, outputs = state
            m_in = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, m_in, keepdims=False)
            live = jnp.logical_and(stage <= t, t - stage < n_micro)
            x_in = jnp.where(stage == 0, inject, carry_in)
            y = stage_fn(params_one, x_in)
            y = jnp.where(live, y, jnp.zeros_like(y))
            # collect at the last stage (microbatch index t - (S-1))
            m_out = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            upd = jnp.where(take, y, jax.lax.dynamic_index_in_dim(
                outputs, m_out, keepdims=False))
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, upd, m_out, axis=0)
            # hand activations to the next stage
            carry_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return carry_next, outputs

        _, outputs = jax.lax.fori_loop(0, ticks, tick,
                                       (carry_in, outputs))
        # Only the last stage holds real outputs; mask + psum broadcasts
        # them to every stage (ppermute requires unique sources).
        outputs = jnp.where(stage == n_stages - 1, outputs,
                            jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis}, check_vma=False)
    return sm(stage_params, x_micro)
