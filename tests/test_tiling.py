"""Property-style invariants for the tiling planner (core/tiling, core/maps).

The slab relations are the correctness backbone of the tiled kernel: if a
``rows_slab`` range ever misses a contributing input row, the Pallas kernel
silently drops partial products.  These tests pin the invariants across a
sweep of strides / paddings / kernel sizes, with a randomized-geometry
property pass on top (hypothesis when installed, deterministic fallback
otherwise).
"""

import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core import tiling
from repro.core.maps import TConvProblem, max_slab_rows, rows_slab
from repro.core.perf_model import V5E
from repro.kernels.ref import crop_offsets

# (ih, iw, ic, ks, oc, s, padding) — SAME requires Ks >= S.
PROBLEMS = [
    (2, 2, 2, 3, 2, 1, "SAME"),
    (4, 4, 3, 5, 2, 2, "SAME"),
    (7, 7, 32, 3, 16, 1, "SAME"),
    (9, 9, 16, 5, 8, 2, "SAME"),
    (5, 6, 4, 4, 3, 2, "SAME"),
    (4, 4, 8, 7, 5, 2, "SAME"),
    (6, 6, 4, 2, 3, 2, "SAME"),
    (3, 3, 4, 3, 2, 1, "VALID"),
    (4, 5, 4, 5, 3, 2, "VALID"),
    (5, 5, 4, 3, 2, 3, "VALID"),
    (8, 8, 16, 9, 3, 1, "SAME"),
]


def _contributing_rows(p: TConvProblem, oh0: int, oh1: int) -> set:
    """Brute-force input rows feeding output rows [oh0, oh1] via
    ``oh = S*ih - ct + kh`` (the kernel's mapping relation)."""
    ct, _ = crop_offsets(p.ks, p.stride, p.padding)
    rows = set()
    for ih in range(p.ih):
        for kh in range(p.ks):
            oh = p.stride * ih - ct + kh
            if oh0 <= oh <= oh1 and 0 <= oh < p.oh:
                rows.add(ih)
    return rows


def _check_slab_invariants(p: TConvProblem, block_oh: int):
    heights = []
    for oh0 in range(0, p.oh, block_oh):
        start, end = rows_slab(p, oh0, block_oh)
        # Contiguous, in range, non-degenerate.
        assert 0 <= start <= end <= p.ih, (p, oh0, start, end)
        oh1 = min(oh0 + block_oh, p.oh) - 1
        need = _contributing_rows(p, oh0, oh1)
        if need:
            # Every contributing input row is inside the slab.
            assert need <= set(range(start, end)), (p, oh0, need, (start, end))
        heights.append(end - start)
    # max_slab_rows bounds every aligned block's slab height.
    assert max(heights) <= max_slab_rows(p, block_oh), (p, block_oh)


@pytest.mark.parametrize("case", PROBLEMS, ids=[str(c) for c in PROBLEMS])
def test_rows_slab_covers_contributors(case):
    ih, iw, ic, ks, oc, s, pad = case
    p = TConvProblem(ih, iw, ic, ks, oc, s, pad)
    for block_oh in (s, 2 * s, 4 * s):
        if block_oh > max(p.oh, s):
            continue
        _check_slab_invariants(p, block_oh)


@settings(max_examples=25, deadline=None)
@given(ih=st.integers(1, 12), iw=st.integers(1, 10), ks=st.integers(1, 7),
       s=st.integers(1, 3), padding=st.sampled_from(["SAME", "VALID"]),
       bi=st.integers(1, 6))
def test_rows_slab_property_random_geometry(ih, iw, ks, s, padding, bi):
    if padding == "SAME" and ks < s:
        return  # unsupported contract (asserted elsewhere)
    p = TConvProblem(ih, iw, 4, ks, 4, s, padding)
    block_oh = s * bi
    if block_oh > max(p.oh, s):
        return
    _check_slab_invariants(p, block_oh)


@pytest.mark.parametrize("case", PROBLEMS, ids=[str(c) for c in PROBLEMS])
def test_default_plan_vmem_within_budget(case):
    ih, iw, ic, ks, oc, s, pad = case
    p = TConvProblem(ih, iw, ic, ks, oc, s, pad)
    tp = tiling.plan(p)
    assert tp.vmem_bytes <= int(V5E.vmem_bytes * 0.75), tp.describe()
    assert tp.block_oh % s == 0 and tp.block_oc >= 1
    assert tp.grid_order in ("bcj", "cbj")


@pytest.mark.parametrize("case", PROBLEMS, ids=[str(c) for c in PROBLEMS])
def test_candidate_plans_legal_and_include_default(case):
    ih, iw, ic, ks, oc, s, pad = case
    p = TConvProblem(ih, iw, ic, ks, oc, s, pad)
    budget = int(V5E.vmem_bytes * 0.75)
    cands = tiling.candidate_plans(p)
    assert cands, p
    seen = set()
    for c in cands:
        assert c.block_oh % s == 0 and c.block_oh >= s
        assert 1 <= c.block_oc
        assert c.grid_order in ("bcj", "cbj")
        assert c.method in ("mm2im", "mm2im_db", "mm2im_ks", "mm2im_og")
        assert c.vmem_bytes <= budget, c.describe()
        if c.method == "mm2im_db":
            # Pipelining needs at least two row blocks to overlap.
            assert c.n_row_blocks >= 2, c.describe()
        key = (c.method, c.block_oh, c.block_oc, c.grid_order)
        assert key not in seen, f"duplicate candidate {key}"
        seen.add(key)
    # The heuristic default geometry is in the enumerated space.
    tp = tiling.plan(p)
    assert (tp.method, tp.block_oh, tp.block_oc, tp.grid_order) in seen


def test_candidate_plans_db_variant_coverage():
    """Problems with >= 2 row blocks enumerate every registered kernel
    family, and the db residency model frees VMEM vs whole-input
    residency."""
    p = TConvProblem(16, 16, 32, 3, 16, 1)
    cands = tiling.candidate_plans(p)
    methods = {c.method for c in cands}
    assert methods == {"mm2im", "mm2im_db", "mm2im_ks", "mm2im_og"}
    assert (tiling.vmem_bytes(p, 4, 16, bits=32, method="mm2im_db")
            < tiling.vmem_bytes(p, 4, 16, bits=32, method="mm2im"))
    # Geometry-identical pairs differ only in modeled residency.
    sb = tiling.plan(p, block_oh=4, block_oc=16, grid_order="bcj")
    db = tiling.plan(p, block_oh=4, block_oc=16, grid_order="bcj",
                     method="mm2im_db")
    assert (sb.n_slab, sb.n_row_blocks) == (db.n_slab, db.n_row_blocks)
    assert db.vmem_bytes < sb.vmem_bytes


def test_candidate_plans_skip_families_the_chip_refuses(monkeypatch):
    """On a TPU, a family whose kernel Mosaic refuses for this problem is
    never offered to the autotuner (so it is never timed as a loser)."""
    p = TConvProblem(16, 16, 32, 3, 16, 1)
    probed = []

    def compiles(method, problem, batch, bits, *geometry):
        probed.append((method, problem, batch, bits))
        return method != "mm2im_og"

    monkeypatch.setattr(tiling.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tiling, "_compiles_on_device", compiles)
    methods = {c.method for c in tiling.candidate_plans(p, batch=8)}
    assert methods == {"mm2im", "mm2im_db", "mm2im_ks"}
    assert ("mm2im_og", p, 8, 8) in probed


def test_compile_probe_drops_only_compiler_refusals(monkeypatch):
    """A compiler refusal drops the plan with one warning that names the
    family and the problem; any other error is a bug and propagates."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import ops

    p = TConvProblem(4, 4, 8, 3, 8, 2)
    probe = tiling._compiles_on_device.__wrapped__

    def refuse(*args, **kw):
        raise pltpu.LoweringException("unsupported shape cast")

    monkeypatch.setattr(ops, "run_registered", refuse)
    with pytest.warns(RuntimeWarning, match=r"mm2im_og .*TConvProblem"
                      r".*unsupported shape cast"):
        assert probe("mm2im_og", p, 1, 32, 2, 128, False) is False

    def broken(*args, **kw):
        raise TypeError("bad argument")

    monkeypatch.setattr(ops, "run_registered", broken)
    with pytest.raises(TypeError, match="bad argument"):
        probe("mm2im_og", p, 1, 32, 2, 128, False)


def test_plan_compiles_keys_on_geometry_not_grid_order(monkeypatch):
    """On a TPU each (family, geometry, fold) is compiled once; the grid
    order shares the answer, and off the chip every plan is accepted."""
    from repro.kernels.registry import Plan

    p = TConvProblem(16, 16, 32, 3, 16, 1)
    assert tiling.plan_compiles(p, Plan(4, 128, "cbj", "mm2im_ks"))
    probed = []

    def compiles(*key):
        probed.append(key)
        return key[4] != 8  # refuse block_oh 8

    monkeypatch.setattr(tiling.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tiling, "_compiles_on_device", compiles)
    assert tiling.plan_compiles(p, Plan(4, 128, "cbj", "mm2im_ks"), bits=32)
    assert not tiling.plan_compiles(p, Plan(8, 128, "bcj"), batch=2, bits=32)
    assert probed == [("mm2im_ks", p, 1, 32, 4, 128, False),
                      ("mm2im", p, 2, 32, 8, 128, False)]


def test_explicit_plan_override_roundtrip():
    p = TConvProblem(8, 8, 16, 5, 12, 2)
    tp = tiling.plan(p, block_oh=4, block_oc=8, grid_order="cbj")
    assert (tp.block_oh, tp.block_oc, tp.grid_order) == (4, 8, "cbj")
    # Partial override keeps the explicit half.
    tp2 = tiling.plan(p, block_oc=8)
    assert tp2.block_oc == 8


def test_invalid_block_oh_rejected():
    p = TConvProblem(8, 8, 16, 5, 12, 2)
    with pytest.raises(ValueError):
        tiling.plan(p, block_oh=3, block_oc=8)  # not a multiple of stride
    with pytest.raises(ValueError):
        tiling.plan(p, block_oh=0, block_oc=8)
