"""Knee sweep of an open-loop cell: offered rate against latency, in one
process on one built server.

    python3 chipbench/sweep.py --workload <name> --rates 500,1000,2000 --seconds 5

For each rate (in the order given) it runs one window of the cell's
traffic with its rate replaced, and prints one JSON line: offered and
completed rate, p50/p95 latency from due time, generator lateness, batch
fill and queue wait, the longest gap between batch completions, the
garbage collector's pauses and any compile inside the window.  A rate
may be listed more than once, to see how much its tail swings.  The
knee is the highest rate whose completed rate keeps up with the offered
one (``knee``); the cell's traffic file then holds 0.8 of it
(``cell_rate``), which the last line prints.  Lines are also appended
to ``chiprun_out/chipbench/sweep.<workload>.jsonl``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


KEEPS_UP = 0.98   # completed over offered at a sustained rate
SHARE = 0.8       # the cell runs at this share of the knee


def knee(lines):
    """The highest offered rate at which every window, at it and at every
    lower rate, kept up: completed at least ``KEEPS_UP`` of the offered
    rate with no request missing.  ``None`` when none did.  The tail is
    printed but does not decide: on the v5e it swung with host freezes
    at every rate, low ones included (PERF.md)."""
    kept = {}
    for ln in lines:
        r = ln["rate"]
        ok = ln["completed_per_s"] >= KEEPS_UP * r and ln["missing"] == 0
        kept[r] = kept.get(r, True) and ok
    best = None
    for r in sorted(kept):
        if not kept[r]:
            break
        best = r
    return best


def cell_rate(k: float) -> float:
    """``SHARE`` of the knee, rounded down to two significant figures."""
    x = SHARE * k
    step = 10 ** (math.floor(math.log10(x)) - 1)
    return float(math.floor(x / step) * step)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import harness, loadgen

    bench = harness.read_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell(bench, args.workload)
    s = harness.setup(cell, args.seed)
    out = harness.STATE_DIR / f"sweep.{args.workload}.jsonl"
    lines = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["phases"] = [{"seconds": 1.0, "rate": rate}]
        w = harness.run_window(s, cell, args.seed + k, args.seconds, False,
                               time.perf_counter())
        e2e = harness.end_to_end(w)
        n = w.n_sent
        late = loadgen.lateness(w.rec.due[:n], w.rec.sent[:n])
        d = {f: w.stats1[f] - w.stats0[f]
             for f in ("batches", "completed", "fill_sum", "wait_sum")}
        line = {"workload": args.workload, "rate": rate, "sent": n,
                "completed_per_s": e2e["images_per_s"],
                "p50_ms": e2e["latency_p50_ms"],
                "p95_ms": e2e["latency_p95_ms"],
                "gen_late_p95_ms": 1e3 * loadgen.quantile(late, 0.95),
                "batch_fill": d["fill_sum"] / max(d["batches"], 1),
                "queue_wait_ms": 1e3 * d["wait_sum"] / max(d["completed"], 1),
                "missing": int(np.sum(~w.rec.ok[:n])),
                "settle_s": w.give_up - w.t_close,
                "stall_s": harness.longest_stall(w.rec.done[:n], w.t0, w.t1),
                "gc": w.gc,
                "freezes": w.freezes,
                "window_compiles": harness.Compiles.delta(w.compiles0,
                                                          w.compiles1)}
        print(json.dumps(line), flush=True)
        lines.append(line)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
    k = knee(lines)
    print(json.dumps({"workload": args.workload, "knee": k,
                      "cell_rate": None if k is None else cell_rate(k)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
