"""Run one benchmark cell and print its result as the last stdout line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Exits 3 without a result when the default device is not a
TPU, is not in ``peaks.json``, or there are fewer devices than the cell's
``chips``.  Set-up (``setup_s``) runs from the start of this process to the
start of the measured window.  The numbers the check compared are the last
lines on standard error, each beside its limit, and the ``checks`` key of
the result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the raw profiler trace under chiprun_out/")
    args = ap.parse_args(argv)

    from chipbench import harness

    bench = harness.read_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell(bench, args.workload)
    try:
        result, extra = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, keep_trace=args.keep_trace)
    except harness.NoDevice as err:
        print(f"[chipbench] {err}", file=sys.stderr)
        return 3
    print(f"[chipbench] {json.dumps(extra)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"[chipbench] check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
