"""Smoke the TCONV server from the command line.

``python -m repro.serve --models dcgan,fsrcnn --requests 24 --rate 200``

Builds CPU-sized runners, warms every (model, precision) bucket, pushes
open-loop synthetic traffic through the background drain thread, and
prints the per-bucket stats snapshot.  Serving is measured on the chip
by ``chipbench/`` (``chipbench/README.md``); where the server's time goes
is in its ``serve.*`` spans and counters (``serve/telemetry.py``,
DESIGN.md §9.3), which a profile of any running server records.

Resilience knobs (``serve/resilience.py``): ``--max-queue-depth`` bounds
each bucket's queue (overflow sheds), ``--deadline-ms`` attaches a
per-request deadline, and ``--chaos-fail-nth`` injects a deterministic
transient fault every Nth batch to exercise the degradation ladder.
Exit status is **nonzero when any bucket ends with ``failed > 0``**, or
when a bucket served any batch below the tuned rung although no fault was
injected (a kernel that fails to compile must not hide behind the ladder).
The full stats dump goes to stdout first, so the CI smoke legs can assert
healthy runs with a plain shell check.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.models.runner import make_runner
from repro.serve.resilience import FaultInjector, ResilienceConfig
from repro.serve.server import TconvServer

SMOKE_RUNNERS = {
    "dcgan": dict(init_kw={"scale_down": 16}),
    "pix2pix": dict(init_kw={"depth": 4, "scale_down": 16}),
    "fsrcnn": dict(init_kw={"d": 8, "s": 4, "m": 1}, input_hw=8),
    "styletransfer": dict(init_kw={"base": 8, "n_res": 1}, input_hw=16),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="dcgan,fsrcnn")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="mean arrival rate, requests/s (Poisson)")
    ap.add_argument("--precisions", default="f32,int8")
    ap.add_argument("--max-wait-ms", type=float, default=50.0)
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="per-bucket queue cap; overflow is shed "
                         "(default: unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; expired requests fail "
                         "fast with DeadlineExceeded (default: none)")
    ap.add_argument("--chaos-fail-nth", type=int, default=None,
                    help="inject a transient fault every Nth batch "
                         "(degradation-ladder smoke)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    names = [m for m in args.models.split(",") if m]
    precisions = tuple(p for p in args.precisions.split(",") if p)
    runners = {n: make_runner(n, key=jax.random.PRNGKey(i),
                              **SMOKE_RUNNERS[n])
               for i, n in enumerate(names)}
    config = ResilienceConfig(
        max_queue_depth=args.max_queue_depth,
        default_deadline_s=(None if args.deadline_ms is None
                            else args.deadline_ms / 1e3))
    injector = (FaultInjector(fail_nth_batch=args.chaos_fail_nth,
                              seed=args.seed)
                if args.chaos_fail_nth else None)
    server = TconvServer(runners, max_wait_s=args.max_wait_ms / 1e3,
                         resilience_config=config, fault_injector=injector)

    t0 = time.perf_counter()
    records = server.warmup(precisions=precisions)
    print(f"[serve] warmed {len(records)} buckets in "
          f"{time.perf_counter() - t0:.2f}s")
    for rec in records:
        print(f"[serve]   {rec.model}:b{rec.batch}:{rec.precision} "
              f"compile={rec.seconds:.2f}s tuned={rec.tuned_layers}"
              f"/{rec.total_layers} tiers={dict(rec.tiers)}")

    rng = np.random.default_rng(args.seed)
    gaps = rng.exponential(1.0 / args.rate, args.requests)
    reqs, shed, failed = [], 0, 0
    with server:
        t0 = time.perf_counter()
        for i in range(args.requests):
            time.sleep(gaps[i])
            name = names[i % len(names)]
            precision = precisions[(i // len(names)) % len(precisions)]
            x = np.asarray(runners[name].example_inputs(1, seed=i))[0]
            try:
                reqs.append(server.submit(name, x, precision=precision))
            except Exception as err:  # noqa: BLE001 — shed/open breaker
                shed += 1
                print(f"[serve] request {i} shed: {err}")
        done = []
        for r in reqs:
            try:
                done.append(r.result(timeout=300))
            except Exception as err:  # noqa: BLE001 — typed request failure
                failed += 1
                print(f"[serve] request {r.rid} failed: "
                      f"{type(err).__name__}: {err}")
        wall = time.perf_counter() - t0

    lats = sorted(1e3 * r.latency_s for r in reqs if r.latency_s is not None)
    if lats:
        print(f"[serve] {len(done)}/{len(reqs)} requests ok "
              f"({shed} shed, {failed} failed) in {wall:.2f}s "
              f"({len(reqs) / wall:.1f} req/s), "
              f"p50={lats[len(lats) // 2]:.1f}ms p99={lats[-1]:.1f}ms")
    stats = server.stats()
    print(json.dumps(stats, indent=2, default=str))
    bad = {key: b["failed"] for key, b in stats["buckets"].items()
           if b["failed"] > 0}
    if bad:
        print(f"[serve] FAILED buckets: {bad}", file=sys.stderr)
        return 1
    degraded = {key: b["rungs"] for key, b in stats["buckets"].items()
                if b["degraded"] > 0}
    if degraded and injector is None:
        print(f"[serve] DEGRADED buckets with no fault injected: {degraded}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
