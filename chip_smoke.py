"""Chip smoke: serve full-width DCGAN through the MM2IM kernels on one TPU.

    python chip_smoke.py [--seed N]

Run from the root of a checkout on a machine with a TPU.  In one process
it builds DCGAN's generator at its published width (z_dim=100, base=1024,
64x64x3 images; random weights from ``--seed``) behind the normal entry
points, ``make_runner`` -> ``TconvServer``, warms an f32 and an int8
bucket, checks them, and serves 48 requests.  It exits nonzero
if:

* the default device is not a TPU (checked before anything is built);
* a bucket's compiled forward holds fewer Mosaic kernels
  (``tpu_custom_call``) than the generator has TCONV layers;
* the kernel forward of a seeded batch disagrees with the same forward
  through XLA's ``conv_transpose`` (``method="lax"``) beyond the
  tolerances below;
* a request failed or was shed, or a batch was served below the tuned
  rung of the degradation ladder.

Plans come only from the committed plan tables and the heuristic: the
per-user autotune cache is pointed at an empty file under
``chiprun_out/chip_smoke/``.  The compile cache is ``$JAX_COMPILATION_
CACHE_DIR`` or ``.jax_cache/`` in the checkout.  Lines before the last are
informative; the last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
BATCH = 8  # the bucket every request is padded to
REQUESTS = 48  # served, half f32 and half int8, in waves of one batch each
# Max |kernel - lax| on the f32 images (tanh outputs in [-1, 1]), both
# forwards at "highest" matmul precision.  They differ only in f32
# summation order: about 2^-24 * sqrt(K) relative per TCONV (K <= 6400
# terms), compounded over four layers and three batch norms, well under
# 1e-4.  A bf16-pass matmul (2^-8 relative) or a misplaced tap is expected
# at 1e-2 or more; the run prints that control (the lax forward at the
# default precision against itself at "highest") beside the reading.
F32_TOL = 1e-3
# Max |kernel - lax| on the int8 images, in requant steps of the output
# layer (its y_scale, max|pre-activation| / 127).  Both paths quantize
# with the same calibrated scales; the kernel accumulates exactly in
# int32 and the lax path in f32, which is exact while |acc| < 2^24 (the
# DCGAN layers reach about 2^21 at full-range int8 inputs).  So the images
# agree up to a requant tie that XLA's float ops around the layers break
# differently in the two programs; two steps allow for that, while a
# wrong kernel is off by O(1).
INT8_TOL_STEPS = 2


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    OUT.mkdir(parents=True, exist_ok=True)
    user_cache = OUT / "autotune_cache.json"
    user_cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(user_cache)
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache

    print(f"[chip_smoke] compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"[chip_smoke] no TPU: default device is {device}",
              file=sys.stderr)
        sys.exit(2)
    print(f"[chip_smoke] device: {device}")

    from repro.models.runner import make_runner
    from repro.serve.server import TconvServer

    key = jax.random.PRNGKey(args.seed)
    runner = make_runner("dcgan", key=key)  # published width: base=1024
    n_layers = len(runner.tconv_problems())
    out_shape = (BATCH, 64, 64, 3)
    print(f"[chip_smoke] dcgan: {n_layers} TCONV layers "
          f"{dict(runner.tconv_problems())}")

    # --- Warm the served buckets (f32 and int8 at the target batch).
    server = TconvServer({"dcgan": runner}, max_wait_s=0.25,
                         default_batch=BATCH)
    for rec in server.warmup(precisions=("f32", "int8")):
        print(f"[chip_smoke] bucket {rec.model}:b{rec.batch}:{rec.precision}"
              f" compile+first run {rec.seconds:.2f} s, plan tiers "
              f"{dict(rec.tiers)}")
        if rec.batch != BATCH:
            fail(f"bucket warmed at batch {rec.batch}, expected {BATCH}")

    # --- Every TCONV layer of each bucket runs as a Mosaic kernel.
    for precision in ("f32", "int8"):
        hlo = runner.lower(batch=BATCH, precision=precision).compile() \
            .as_text()
        n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
        print(f"[chip_smoke] {precision} bucket: {n_kernels} tpu_custom_call"
              f" for {n_layers} TCONV layers")
        if n_kernels < n_layers:
            fail(f"{precision} bucket runs {n_kernels} Mosaic kernels for "
                 f"{n_layers} TCONV layers")

    # --- Whole seeded batches against the lax forward (the generator
    # normalizes with batch statistics, so only whole batches compare).
    z = jax.random.normal(jax.random.fold_in(key, 1), (BATCH, 100))
    # Same params, so the same one-shot int8 calibration — made here, at
    # the default precision the served buckets were calibrated at.
    ref_runner = make_runner("dcgan", params=runner.params, method="lax")
    quant = runner.quant_scales()
    if ref_runner.quant_scales() != quant:
        fail("the lax runner calibrated different int8 scales")
    last = list(runner.tconv_problems())[-1]  # the output layer
    int8_tol = INT8_TOL_STEPS * quant[last].y_scale
    for precision, tol in (("f32", F32_TOL), ("int8", int8_tol)):
        with jax.default_matmul_precision("highest"):
            got = np.asarray(runner.jitted(batch=BATCH,
                                           precision=precision)(z))
            want = np.asarray(ref_runner.jitted(batch=BATCH,
                                                precision=precision)(z))
        if got.shape != out_shape or not np.isfinite(got).all():
            fail(f"{precision} forward gave shape {got.shape}, finite="
                 f"{bool(np.isfinite(got).all())}")
        err = float(np.max(np.abs(got - want)))
        print(f"[chip_smoke] {precision} kernel vs lax (highest): max abs "
              f"error {err:.3e} (tolerance {tol:g}), mean "
              f"{float(np.mean(np.abs(got - want))):.3e}")
        if not err <= tol:
            fail(f"{precision} kernel forward differs from lax by {err}")
        if precision == "f32":
            # Informative controls at the served (default) precision: what
            # a bf16-pass XLA forward scores against the same reference,
            # and what the served f32 bucket itself scores.
            for name, r in (("lax", ref_runner), ("served kernel", runner)):
                ctl = np.asarray(r.jitted(batch=BATCH, precision="f32")(z))
                print(f"[chip_smoke] control: f32 {name} forward at default"
                      f" precision vs lax (highest): max abs error "
                      f"{float(np.max(np.abs(ctl - want))):.3e}")

    # --- Serve requests through the background drain thread.
    n_waves = REQUESTS // (2 * BATCH)
    rng = np.random.default_rng(args.seed)
    latencies = []
    with server:
        t0 = time.perf_counter()
        for wave in range(n_waves):
            sent = {}
            for precision in ("f32", "int8"):
                zs = rng.standard_normal((BATCH, 100)).astype(np.float32)
                sent[precision] = (zs, [server.submit("dcgan", zi,
                                                      precision=precision)
                                        for zi in zs])
            for precision, (zs, reqs) in sent.items():
                rows = np.stack([r.result(timeout=300) for r in reqs])
                latencies += [r.latency_s for r in reqs]
                if rows.shape != out_shape or not np.isfinite(rows).all():
                    fail(f"served {precision} batch has shape {rows.shape}")
                if wave == 0:
                    # The served rows are the batched forward of the same
                    # requests, in order.
                    direct = np.asarray(runner.jitted(
                        batch=BATCH, precision=precision)(jnp.asarray(zs)))
                    if not np.array_equal(rows, direct):
                        fail(f"served {precision} batch differs from the "
                             f"runner's forward of the same inputs")
        wall = time.perf_counter() - t0

    lat_ms = np.sort(np.asarray(latencies) * 1e3)
    served = len(lat_ms)
    print(f"[chip_smoke] served {served} requests in {wall:.2f} s: "
          f"p50 {np.percentile(lat_ms, 50):.1f} ms, "
          f"p99 {np.percentile(lat_ms, 99):.1f} ms")
    stats = server.stats()
    for name, b in stats["buckets"].items():
        print(f"[chip_smoke] bucket {name}: completed {b['completed']} "
              f"failed {b['failed']} shed {b['shed']} degraded "
              f"{b['degraded']} rungs {b['rungs']}")
        if b["failed"] or b["shed"] or b["degraded"] or \
                set(b["rungs"]) != {"tuned"}:
            fail(f"bucket {name} was not served cleanly on the tuned rung")
    if served != 2 * BATCH * n_waves or stats["rejected"]:
        fail(f"served {served} of {2 * BATCH * n_waves} requests")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
