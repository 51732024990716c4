"""GPipe pipeline-parallel primitive vs sequential execution (subprocess:
needs its own multi-device XLA flags)."""

import os
import subprocess
import sys

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply

for S, M in [(2, 4), (4, 6), (2, 2)]:
    mesh = jax.make_mesh((S, 8 // S // 1, 1)[:3] if False else (S, 8 // S, 1),
                         ("pod", "data", "model"))
    mb, D = 8, 16
    k = jax.random.PRNGKey(S * 10 + M)
    W = jax.random.normal(k, (S, D, D)) * 0.3
    b = jax.random.normal(jax.random.fold_in(k, 1), (S, D)) * 0.1
    params = {"w": W, "b": b}
    x = jax.random.normal(jax.random.fold_in(k, 2), (M, mb, D))
    stage_fn = lambda p, a: jnp.tanh(a @ p["w"] + p["b"])
    with jax.set_mesh(mesh):
        y = jax.jit(lambda pp, xx: pipeline_apply(
            stage_fn, pp, xx, mesh=mesh))(params, x)
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ W[s] + b[s])
    err = float(jnp.abs(np.asarray(y) - np.asarray(ref)).max())
    assert err < 1e-6, (S, M, err)
    print(f"PIPE_OK S={S} M={M} err={err:.1e}")
"""


@pytest.mark.slow
def test_gpipe_matches_sequential():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=560, cwd=REPO)
    assert out.stdout.count("PIPE_OK") == 3, out.stdout + out.stderr[-2000:]
