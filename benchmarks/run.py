"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json OUT.json]``

Emits ``name,us_per_call,derived`` CSV lines (stdout).  ``--json`` also
writes every emitted row (plus run metadata: backend, jax version,
timestamp) to a JSON file — the machine-readable perf-trajectory artifact
CI records per commit (``BENCH_autotune.json`` for the autotune slice) —
and additionally distills a compact repo-root ``BENCH_mm2im.json``
(per-method timings, modeled MXU utilization incl. folded-vs-grid, and
the autotune tier hit-rates) so the MM2IM perf trajectory has a single
small file to diff across commits.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent

MODULES = [
    ("drop_rates", "benchmarks.bench_drop_rates"),            # Fig. 1 / 7
    ("synthetic_261", "benchmarks.bench_synthetic_261"),      # Fig. 6
    ("model_layers", "benchmarks.bench_model_layers"),        # Table II
    ("accel_compare", "benchmarks.bench_accel_compare"),      # Table III
    ("gan_e2e", "benchmarks.bench_gan_e2e"),                  # Table IV
    ("perf_model_validation", "benchmarks.bench_perf_model_validation"),  # §V-F
    ("ablations", "benchmarks.bench_ablations"),              # kernel ablations
    ("autotune", "benchmarks.bench_autotune"),                # tuned vs default plans
    ("scale_roofline", "benchmarks.bench_scale_roofline"),    # §Roofline
]


def _parse_value(v: str):
    """Best-effort typed parse: int, then float, then the raw string.

    The distilled JSON previously shipped every derived value as a string
    (``tier_hits`` counts as ``"0"``/``"5"``), which made downstream
    consumers re-parse — and silently compare strings.
    """
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return v


def _parse_derived(derived: str) -> dict:
    """'k=v;k=v' derived strings -> dict with numeric values typed."""
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, _, v = part.partition("=")
            out[k] = _parse_value(v)
    return out


def mm2im_summary(rows: list) -> dict:
    """Distill the emitted rows into the compact MM2IM trajectory doc.

    Three sections, each present when its source ran (plus an
    always-available modeled section, so even an ``--only autotune`` run
    seeds a non-empty trajectory):

    * ``methods`` — per-method mean timing + modeled MXU utilization from
      the ``tableIII_summary_*`` rows;
    * ``autotune`` — every ``autotune*`` row verbatim (tuned-vs-default,
      sb-vs-db and folded-vs-grid head-to-heads);
    * ``tier_hits`` — the parsed ``autotune_tier_hits`` attribution;
    * ``modeled_fold`` — tile-quantized folded-vs-grid utilization on the
      batch-8 Table II rows straight from ``core/perf_model`` (no
      benchmarking required, so the field never goes empty);
    * ``rank_agreement`` — predicted-vs-measured ordering over this run's
      recorded head-to-heads (``core/model_fit.rank_agreement``), scored
      with the shipped per-backend calibration when one exists.  This is
      the section ``tools/bench_gate.py`` hard-gates on;
    * ``large_image`` — the og-vs-mm2im-vs-ks cross-method head-to-heads
      on the >=32x32 stride-4 regime (``autotune_large_*_ogcmp``), parsed
      so the gather-family trajectory diffs at a glance (the raw rows
      also stay in ``autotune`` for the rank-agreement gate).
    """
    methods = {}
    autotune_rows = []
    large_image = {}
    tier_hits = None
    for r in rows:
        name = r["name"]
        if name.startswith("tableIII_summary_"):
            d = _parse_derived(r["derived"])
            entry = {"us": r["us_per_call"]}
            if "mean_mxu_util" in d:
                entry["mean_mxu_util"] = float(d["mean_mxu_util"])
            methods[name[len("tableIII_summary_"):]] = entry
        elif name == "autotune_tier_hits":
            tier_hits = _parse_derived(r["derived"])
        elif name.startswith("autotune"):
            autotune_rows.append(r)
            if name.startswith("autotune_large_"):
                large_image[name] = _parse_derived(r["derived"])

    from repro.configs.paper_models import TABLE_II
    from repro.core.perf_model import mm2im_estimate

    modeled = {}
    for row in TABLE_II:
        g = mm2im_estimate(row.problem, 8, bits=8)
        f = mm2im_estimate(row.problem, 8, bits=8, fold_batch=True)
        modeled[row.name] = {
            "grid_mxu_util": round(g.mxu_utilization, 4),
            "fold_mxu_util": round(f.mxu_utilization, 4),
            "fold_speedup": round(g.t_overlapped / f.t_overlapped, 3),
        }
    rank = None
    if autotune_rows:
        from repro.core import model_fit

        pairs = model_fit.pairs_from_bench({"autotune": autotune_rows})
        if pairs:
            rank = model_fit.rank_agreement(pairs, model_fit.shipped_fit())

    return {"methods": methods, "autotune": autotune_rows,
            "tier_hits": tier_hits, "modeled_fold_b8": modeled,
            "rank_agreement": rank, "large_image": large_image}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names to run "
                         "(e.g. --only autotune,model_layers)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the emitted rows + run metadata as JSON "
                         "(the CI perf-trajectory artifact)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failures = 0
    ran = []
    for name, mod in MODULES:
        if only is not None and name not in only:
            continue
        t0 = time.time()
        try:
            __import__(mod, fromlist=["main"]).main()
            ran.append(name)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name} FAILED", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        import jax

        from benchmarks import common

        doc = {
            "schema": 1,
            "created": time.time(),
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "modules": ran,
            "failures": failures,
            "rows": common.rows(),
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(doc['rows'])} rows to {args.json}",
              file=sys.stderr)

        # Compact MM2IM trajectory file at the repo root — the per-commit
        # artifact CI uploads next to BENCH_autotune.json.
        compact = {
            "schema": 1,
            "created": doc["created"],
            "backend": doc["backend"],
            "jax": doc["jax"],
            "modules": ran,
        }
        compact.update(mm2im_summary(doc["rows"]))
        mm2im_path = REPO_ROOT / "BENCH_mm2im.json"
        with open(mm2im_path, "w") as f:
            json.dump(compact, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote MM2IM trajectory to {mm2im_path}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
