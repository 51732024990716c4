#!/usr/bin/env python
"""CI perf gate: fresh ``BENCH_mm2im.json`` vs the committed baseline.

The benchmark harness used to *upload* its distilled perf artifact and
hope someone diffed it; this tool turns the artifact into a gate with two
legs:

**Rank leg (hard).**  Both docs' recorded head-to-heads (sb-vs-db and
folded-vs-grid — ``core/model_fit.pairs_from_bench``) are re-scored at
gate time with the *same* model (the shipped per-backend calibration when
one exists, else the raw roofline), and the candidate fails outright when
it misranks more decisive pairs than the baseline does.  Decisive means
the measured ratio is beyond the ``--decisive-band`` (ordering pairs
inside the noise band is chance, not signal).  Re-scoring both sides at
gate time, rather than trusting scores embedded in the docs, keeps a
model change from shifting the goalposts for only one side.

**Latency leg (soft, banded).**  Absolute microseconds are meaningless
across CI machines, so the latency comparison is dimensionless: each
``autotune_*`` tuned row records its tuned-vs-default speedup on *its
own* machine, and the gate compares the geomean of candidate/baseline
speedup ratios over the problems both docs measured.  A geomean below
``--noise-band`` fails; anything inside the band is reported but passes
(interpret-mode wall time on shared CI runners drifts with neighbors).

Exit codes: 0 pass, 1 gate failure, 2 unusable input.

Typical CI invocation (after ``benchmarks.run --json`` regenerated the
repo-root ``BENCH_mm2im.json``)::

    git show HEAD:BENCH_mm2im.json > /tmp/bench_baseline.json
    PYTHONPATH=src python tools/bench_gate.py \
        --candidate BENCH_mm2im.json --baseline /tmp/bench_baseline.json
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT / "src") not in sys.path:
    sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.core import model_fit

#: Candidate/baseline speedup-ratio geomean below this fails the latency
#: leg.  Generous by design: tuned-vs-default ratios from 2-3 repeat
#: interpret-mode timings swing hard on shared runners, and the geomean
#: over a handful of problems only partly damps that.
DEFAULT_NOISE_BAND = 0.5

def load_doc(path: str) -> dict:
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, ValueError) as e:
        raise SystemExit(f"bench_gate: cannot read {path}: {e}")
    return doc


def tuned_speedups(doc: dict) -> dict:
    """name -> tuned-vs-default speedup from the doc's autotune rows.

    Rows carry ``speedup=<x.xx>x`` in their derived strings
    (``benchmarks/bench_autotune.py``); comparison-only rows (dbcmp,
    fold head-to-heads) have none and are skipped.
    """
    out = {}
    for r in doc.get("autotune", []):
        for part in str(r.get("derived", "")).split(";"):
            k, _, v = part.partition("=")
            if k == "speedup" and v.endswith("x"):
                try:
                    s = float(v[:-1])
                except ValueError:
                    continue
                if s > 0 and math.isfinite(s):
                    out[r.get("name", "")] = s
    return out


def rank_leg(cand: dict, base: dict, fit, decisive_band: float) -> tuple:
    """(ok, report_lines) for the hard rank-agreement comparison."""
    lines = []
    scores = {}
    for label, doc in (("baseline", base), ("candidate", cand)):
        pairs = model_fit.pairs_from_bench(doc)
        if not pairs:
            lines.append(f"  {label}: no head-to-head rows")
            scores[label] = None
            continue
        s = model_fit.rank_agreement(pairs, fit, decisive_band=decisive_band)
        scores[label] = s
        lines.append(
            f"  {label}: {s['n_agree']}/{s['n_pairs']} agree "
            f"({s['n_decisive']} decisive, {s['n_misranks']} misranks, "
            f"mean |log2 err| {s['mean_abs_log2_err']})")
        for r in s["pairs"]:
            flag = "ok" if r["agree"] else \
                ("MISRANK" if r["decisive"] else "miss(noise)")
            lines.append(f"    {flag:11s} {r['name']}: measured "
                         f"{r['measured_ratio']}x, predicted "
                         f"{r['predicted_ratio']}x")
    if scores.get("baseline") is None:
        lines.append("  pass: no baseline head-to-heads to compare against")
        return True, lines
    if scores.get("candidate") is None:
        lines.append("  FAIL: baseline records head-to-heads but the "
                     "candidate has none (benchmark emission regression?)")
        return False, lines
    cand_m = scores["candidate"]["n_misranks"]
    base_m = scores["baseline"]["n_misranks"]
    if cand_m > base_m:
        lines.append(f"  FAIL: candidate misranks {cand_m} decisive "
                     f"head-to-heads, baseline misranked {base_m}")
        return False, lines
    lines.append(f"  pass: misranks {cand_m} (baseline {base_m})")
    return True, lines


def latency_leg(cand: dict, base: dict, noise_band: float) -> tuple:
    """(ok, report_lines) for the banded tuned-speedup comparison."""
    lines = []
    cs, bs = tuned_speedups(cand), tuned_speedups(base)
    shared = sorted(set(cs) & set(bs))
    if not shared:
        lines.append("  pass: no commonly-measured tuned rows to compare")
        return True, lines
    logs = []
    for name in shared:
        ratio = cs[name] / bs[name]
        logs.append(math.log(ratio))
        lines.append(f"  {name}: speedup {bs[name]:.2f}x -> {cs[name]:.2f}x "
                     f"(ratio {ratio:.2f})")
    geomean = math.exp(sum(logs) / len(logs))
    if geomean < noise_band:
        lines.append(f"  FAIL: tuned-speedup geomean ratio {geomean:.2f} "
                     f"below the noise band {noise_band} over "
                     f"{len(shared)} problems")
        return False, lines
    lines.append(f"  pass: geomean ratio {geomean:.2f} over {len(shared)} "
                 f"problems (band {noise_band})")
    return True, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--candidate", required=True,
                    help="freshly distilled BENCH_mm2im.json")
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_mm2im.json to gate against")
    ap.add_argument("--noise-band", type=float, default=DEFAULT_NOISE_BAND,
                    help="latency leg fails when the candidate/baseline "
                         "tuned-speedup geomean ratio drops below this")
    ap.add_argument("--decisive-band", type=float,
                    default=model_fit.DECISIVE_BAND,
                    help="head-to-heads measured closer to 1.0x than this "
                         "are noise, not rank signal")
    ap.add_argument("--uncalibrated", action="store_true",
                    help="score ranks with the raw roofline even when a "
                         "shipped calibration exists")
    args = ap.parse_args(argv)

    cand = load_doc(args.candidate)
    base = load_doc(args.baseline)
    fit = None if args.uncalibrated else model_fit.shipped_fit()
    print(f"bench_gate: {args.candidate} vs {args.baseline} "
          f"({'calibrated' if fit is not None else 'roofline'} model)")

    rank_ok, lines = rank_leg(cand, base, fit, args.decisive_band)
    print("rank leg (hard):")
    print("\n".join(lines))
    lat_ok, lines = latency_leg(cand, base, args.noise_band)
    print("latency leg (soft, banded):")
    print("\n".join(lines))

    if rank_ok and lat_ok:
        print("bench_gate: PASS")
        return 0
    print("bench_gate: FAIL")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
