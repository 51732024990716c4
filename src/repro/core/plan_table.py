"""Shipped tuned-plan tables — read-only, backend-keyed, packaged with repro.

The autotuner's on-disk :class:`~repro.core.autotune.PlanCache` only ever
holds what one machine happened to tune; a fresh checkout runs the
``plan_blocks`` heuristic everywhere.  This module closes that gap: plan
tables produced by ``tools/tune_sweep.py`` (the full 261-config sweep
harness) are committed under ``src/repro/data/plans/`` and consulted as a
**third precedence tier** during automatic plan consumption
(docs/AUTOTUNER.md):

    explicit ``plan=``  >  user cache  >  shipped table  >  heuristic

Tables are keyed by JAX backend: ``shipped_table()`` loads
``<backend>.json`` for ``jax.default_backend()`` (``cpu.json``,
``tpu.json``, ...), so a TPU host never consumes interpret-mode timings
and vice versa.  The file format is the :class:`PlanCache` schema plus a
required ``provenance`` block recording how the table was produced::

    {
      "version": 2,
      "provenance": {"backend": "tpu", "jax": "0.4.37", "repeats": 5,
                     "created": 1754012345.0, "note": "full 261 sweep"},
      "entries": {"tconv:ih8:...|float32|tpu-v5e|b1": {"plan": {...}, ...}}
    }

Schema v2 adds the per-plan ``fold_batch`` field (batch folded into the
MatMul M-dimension); v1 tables still load leniently
(:data:`SUPPORTED_TABLE_VERSIONS`) with their plans read as unfolded, but
a v1 table *carrying* ``fold_batch`` fails validation — the field is
gated to version 2 so pre-fold readers never silently drop it.

Tables are **read-only**: nothing in the runtime ever writes one.  The
tune -> export -> commit workflow lives in ``tools/tune_sweep.py``; CI
schema-validates every committed table (:func:`validate_table_json`) and a
bad or missing table always degrades to the next tier — a shipped table
can never break inference.

``REPRO_PLAN_TABLE_DIR`` overrides the packaged directory (tests; site
deployments shipping their own tables).
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.kernels.registry import Plan

TABLE_DIR_ENV = "REPRO_PLAN_TABLE_DIR"
#: Current table schema.  v2 adds the per-plan ``fold_batch`` field
#: (batch folded into the MatMul M-dimension — kernels/registry.Plan).
TABLE_VERSION = 2
#: Versions the loader accepts.  v1 tables (no ``fold_batch`` anywhere)
#: keep loading leniently so committed pre-fold tables and site tables
#: survive the schema bump; their plans read back as unfolded.
SUPPORTED_TABLE_VERSIONS = (1, 2)

#: provenance keys every shipped table must carry (tools/tune_sweep.py
#: --export writes them; validate_table_json enforces them).
REQUIRED_PROVENANCE = ("backend", "jax", "repeats", "created")


def table_dir() -> Path:
    """Directory holding the shipped ``<backend>.json`` tables.

    ``$REPRO_PLAN_TABLE_DIR`` wins; otherwise the packaged
    ``repro/data/plans/`` directory, found relative to this file.
    (``importlib.resources`` is no help here: ``repro.data`` is a namespace
    package, and with the source tree on ``sys.path`` twice it resolves to
    a multiplexed path that is not a directory.)
    """
    env = os.environ.get(TABLE_DIR_ENV)
    if env:
        return Path(env).expanduser()
    return Path(__file__).resolve().parent.parent / "data" / "plans"


def available_backends(directory: Union[str, Path, None] = None
                       ) -> Tuple[str, ...]:
    """Backends with a shipped table present (``cpu``, ``tpu``, ...).

    Calibration records (``<backend>.fit.json`` — ``core/model_fit.py``)
    live in the same directory but are not plan tables; they are skipped.
    """
    d = Path(directory) if directory else table_dir()
    try:
        return tuple(sorted(f.stem for f in d.glob("*.json")
                            if not f.name.endswith(".fit.json")))
    except OSError:
        return ()


def validate_table_json(raw: object, *, source: str = "table") -> List[str]:
    """Schema-check one parsed table; returns problems (empty == valid).

    Enforced: the version tag (any of
    :data:`SUPPORTED_TABLE_VERSIONS` — v1 loads leniently), the
    :data:`REQUIRED_PROVENANCE` block, the ``tconv:...|dtype|hw|bN`` key
    shape, and that every entry's ``plan`` round-trips through
    :class:`~repro.kernels.registry.Plan` (positive blocks, known grid
    order).  The v2 ``fold_batch`` plan field is *gated*: a table claiming
    ``version: 1`` must not carry it (old readers would silently drop the
    fold and run a geometry the plan was never timed at).  Timing metadata
    (``us`` etc.) is optional but must be numeric when present.
    """
    errs: List[str] = []
    if not isinstance(raw, dict):
        return [f"{source}: top level must be an object, got {type(raw).__name__}"]
    version = raw.get("version")
    if version not in SUPPORTED_TABLE_VERSIONS:
        errs.append(f"{source}: version must be one of "
                    f"{SUPPORTED_TABLE_VERSIONS}, got {version!r}")
    prov = raw.get("provenance")
    if not isinstance(prov, dict):
        errs.append(f"{source}: missing 'provenance' object")
    else:
        for field in REQUIRED_PROVENANCE:
            if field not in prov:
                errs.append(f"{source}: provenance missing {field!r}")
    entries = raw.get("entries")
    if not isinstance(entries, dict):
        errs.append(f"{source}: missing 'entries' object")
        return errs
    for key, entry in entries.items():
        where = f"{source}: entries[{key!r}]"
        parts = key.split("|")
        if not key.startswith("tconv:") or len(parts) != 4 \
                or not parts[3].startswith("b"):
            errs.append(f"{where}: malformed cache key (want "
                        f"'tconv:...|dtype|hw|bN')")
        if not isinstance(entry, dict) or "plan" not in entry:
            errs.append(f"{where}: entry must be an object with a 'plan'")
            continue
        try:
            Plan.from_json(entry["plan"])
        except Exception as e:  # noqa: BLE001 — report, don't raise
            errs.append(f"{where}: bad plan {entry['plan']!r} ({e})")
        else:
            if version == 1:
                # The exporter writes the field into both plan dicts, so
                # the v1 gate must inspect both.
                for field in ("plan", "default_plan"):
                    if isinstance(entry.get(field), dict) \
                            and "fold_batch" in entry[field]:
                        errs.append(
                            f"{where}: {field!r} carries 'fold_batch', a "
                            f"schema-v2 field — stamp the table version 2 "
                            f"(tools/tune_sweep.py --export does)")
        for f in ("us", "default_us"):
            if f in entry and not isinstance(entry[f], (int, float)):
                errs.append(f"{where}: {f!r} must be numeric")
    return errs


class PlanTable:
    """One loaded, validated, immutable shipped-plan table.

    Read-side twin of :class:`~repro.core.autotune.PlanCache`: same
    ``get`` / ``get_entry`` / ``keys`` surface so the precedence chain in
    ``autotune.lookup_plan`` treats the tiers uniformly — but there is no
    ``put`` and nothing is ever written back.
    """

    def __init__(self, entries: Dict[str, dict], provenance: dict,
                 source: str = ""):
        self._entries = dict(entries)
        self.provenance = dict(provenance)
        self.source = source

    def get(self, key: str) -> Optional[Plan]:
        e = self._entries.get(key)
        return Plan.from_json(e["plan"]) if e else None

    def get_entry(self, key: str) -> Optional[dict]:
        e = self._entries.get(key)
        return dict(e) if e else None

    def keys(self) -> Sequence[str]:
        return tuple(self._entries)

    def __len__(self) -> int:
        return len(self._entries)

    def __repr__(self) -> str:
        return (f"PlanTable({self.source or '<memory>'}, "
                f"backend={self.provenance.get('backend')!r}, "
                f"{len(self)} entries)")


def load_table(backend: str, *, directory: Union[str, Path, None] = None,
               strict: bool = False) -> Optional[PlanTable]:
    """Parse + validate ``<backend>.json``; None when absent or invalid.

    ``strict=True`` raises ``ValueError`` with the validation report
    instead of degrading — that's the CI/tooling mode
    (``tools/tune_sweep.py --validate-tables``); the runtime always uses
    the lenient default so a bad table falls through to the heuristic.
    """
    d = Path(directory) if directory else table_dir()
    path = d / f"{backend}.json"
    try:
        raw = json.loads(path.read_text())
    except OSError:
        if strict:
            raise ValueError(f"no shipped table at {path}")
        return None
    except ValueError as e:
        if strict:
            raise ValueError(f"{path}: not valid JSON ({e})") from None
        return None
    errs = validate_table_json(raw, source=str(path))
    if errs:
        if strict:
            raise ValueError("invalid shipped plan table:\n  "
                             + "\n  ".join(errs))
        return None
    return PlanTable(raw["entries"], raw["provenance"], source=str(path))


_SHIPPED: dict = {}  # backend -> Optional[PlanTable] (per-process memo)


def shipped_table(backend: Optional[str] = None) -> Optional[PlanTable]:
    """The shipped table for ``backend`` (default: ``jax.default_backend()``).

    Memoized per process — shipped tables are immutable release artifacts,
    so unlike the user cache there is no mtime re-check.  Returns None
    when no table ships for this backend (most backends, until someone
    runs the sweep there).
    """
    if backend is None:
        import jax

        backend = jax.default_backend()
    if backend not in _SHIPPED:
        _SHIPPED[backend] = load_table(backend)
    return _SHIPPED[backend]


def reset_shipped_tables() -> None:
    """Drop the memo (tests; after pointing REPRO_PLAN_TABLE_DIR elsewhere)."""
    _SHIPPED.clear()
