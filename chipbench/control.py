"""Readings that set the limits of the check: the program's and the
controls', on many seeds, in one process.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 3

For each seed it makes one run of the cell (short window, no trace) and
prints one JSON line: the served outputs' gaps from the reference, and
each control's gap from the same reference on the same batches.  The
controls are the reference computed one step below the precision that
the configuration states (``CONTROLS``).  The benchmark's own runs do not
compute them.  Lines are also appended to
``chiprun_out/chipbench/control.<workload>.jsonl``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

# Below what the configurations state, as changes to the reference's
# precision (``limits/``): everything in bfloat16 for an f32 forward whose
# XLA layers run at the default precision; TCONV products at one bf16
# pass, or at HIGH (three passes), in place of HIGHEST; int4 for the int8
# TCONVs.
CONTROLS = {
    "f32": {"bf16": {"xla": "bfloat16"},
            "tconv_default": {"tconv": "default"},
            "tconv_high": {"tconv": "high"}},
    "int8": {"int4": {"int_bits": 4}},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    from chipbench import harness

    bench = harness.read_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell(bench, args.workload)
    out = harness.STATE_DIR / f"control.{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        result, extra = harness.run_cell(
            cell, seed, args.seconds, False, t_start=t,
            controls=CONTROLS[cell.precision])
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"], "attempted": result["attempted"],
                "failed": result["failed"],
                "batches_compared": result["checks"]["batches_compared"],
                "setup_s": extra["end_to_end"]["setup_s"],
                "check_s": extra["check_s"], "readings": extra["readings"]}
        print(json.dumps(line), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
