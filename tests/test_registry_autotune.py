"""Kernel registry dispatch + block-size autotuner behaviour.

Covers the ISSUE-1 acceptance surface: registered-vs-default dispatch
equivalence against the ``kernels/ref.py`` oracles, cache write->read
round-trips across PlanCache instances (simulating separate processes),
cache-key stability, and a tuned plan executing correctly through
``ops.tconv`` and the layer/model plumbing.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.autotune import (PlanCache, autotune_result, cache_key,
                                 default_plan, measure_plan)
from repro.core.maps import TConvProblem
from repro.kernels import ref, registry
from repro.kernels.ops import tconv
from repro.kernels.registry import Plan
from repro.layers import common as layers_common

RNG = np.random.default_rng(7)


def _xw(ih=5, iw=5, ic=4, ks=3, oc=4, b=1):
    x = RNG.standard_normal((b, ih, iw, ic)).astype(np.float32)
    w = (RNG.standard_normal((ks, ks, oc, ic)) * 0.1).astype(np.float32)
    return x, w


# ---------------------------------------------------------------------------
# Registry dispatch
# ---------------------------------------------------------------------------


def test_builtin_methods_registered():
    assert set(registry.names()) >= {"mm2im", "iom_unfused", "zero_insertion",
                                     "tdc", "lax"}


def test_unknown_method_raises():
    x, w = _xw()
    with pytest.raises(ValueError, match="method must be one of"):
        tconv(x, w, stride=2, method="nope")


@pytest.mark.parametrize("method", ["mm2im", "iom_unfused", "zero_insertion",
                                    "tdc", "lax"])
def test_registered_dispatch_matches_reference(method):
    """Every built-in method agrees with the lax gold oracle through the
    registry-dispatched ``ops.tconv`` — bias and activation included."""
    x, w = _xw()
    b = RNG.standard_normal(4).astype(np.float32)
    got = np.asarray(tconv(x, w, b, stride=2, method=method,
                           activation="relu"))
    want = np.maximum(np.asarray(ref.tconv_lax(x, w, stride=2)) + b, 0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_register_custom_kernel_dispatch():
    """A plugged-in implementation dispatches by name, then unregisters."""

    @registry.register("direct_test",
                       description="ref.tconv_direct as a plugin")
    def _direct(x, w, *, stride, padding, epilogue, plan):
        return ref.tconv_direct(x, w, stride=stride, padding=padding)

    try:
        x, w = _xw()
        got = np.asarray(tconv(x, w, stride=2, method="direct_test"))
        want = np.asarray(tconv(x, w, stride=2, method="mm2im"))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    finally:
        assert registry.unregister("direct_test") is not None
    with pytest.raises(ValueError):
        registry.get("direct_test")


def test_mixed_fuse_capabilities_get_full_epilogue():
    """A kernel fusing only one of bias/activation still gets the other
    applied by the dispatcher (regression: the unfused half was dropped).

    Under the Epilogue-typed contract the kernel receives only the fused
    *prefix* of present stages — 'fuse_act_only' with a bias present gets
    an empty kernel epilogue (activation cannot run before the unfused
    bias add) and the dispatcher applies both stages itself.
    """
    from repro.core.epilogue import apply_epilogue

    def _direct(x, w, *, stride, padding, epilogue, plan):
        out = ref.tconv_direct(x, w, stride=stride, padding=padding)
        return apply_epilogue(out, epilogue)

    registry.register("fuse_bias_only", fuses=("bias",))(_direct)
    registry.register("fuse_act_only", fuses=("activation",))(_direct)
    try:
        x, w = _xw()
        b = RNG.standard_normal(4).astype(np.float32)
        want = np.maximum(np.asarray(ref.tconv_lax(x, w, stride=2)) + b, 0)
        for method in ("fuse_bias_only", "fuse_act_only"):
            got = np.asarray(tconv(x, w, b, stride=2, method=method,
                                   activation="relu"))
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                       err_msg=method)
    finally:
        registry.unregister("fuse_bias_only")
        registry.unregister("fuse_act_only")


def test_plan_rejected_for_untiled_method():
    x, w = _xw()
    with pytest.raises(ValueError, match="does not accept an explicit"):
        tconv(x, w, stride=2, method="lax", plan=(2, 4))


def test_plan_tuple_normalization():
    assert registry.as_plan((4, 8)) == Plan(4, 8, "auto")
    assert registry.as_plan((4, 8, "cbj")) == Plan(4, 8, "cbj")
    assert registry.as_plan(None) is None
    with pytest.raises(ValueError):
        registry.as_plan("bogus")
    with pytest.raises(ValueError):
        Plan(0, 4)
    with pytest.raises(ValueError):
        Plan(2, 4, "zzz")


def test_plan_method_json_roundtrip():
    """Plan.method survives the cache JSON format; legacy entries (no
    'method' key) read back as method=None."""
    p = Plan(4, 8, "cbj", "mm2im_db")
    assert Plan.from_json(p.to_json()) == p
    assert "method" not in Plan(4, 8).to_json()  # legacy-shaped output
    assert Plan.from_json({"block_oh": 4, "block_oc": 8}) == Plan(4, 8)


def test_explicit_plan_through_tconv_matches_default():
    x, w = _xw(ih=6, iw=6, ic=8, ks=5, oc=6)
    want = np.asarray(tconv(x, w, stride=2))
    for plan in [(2, 4), (4, 2, "cbj"), Plan(2, 6, "bcj")]:
        got = np.asarray(tconv(x, w, stride=2, plan=plan))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------


def test_cache_roundtrip_across_instances(tmp_path):
    path = tmp_path / "cache.json"
    c1 = PlanCache(path)
    plan = Plan(4, 16, "cbj")
    c1.put("some:key", plan, meta={"us": 12.5})
    # Fresh instance = fresh process: must read what the first wrote.
    c2 = PlanCache(path)
    assert c2.get("some:key") == plan
    assert c2.get_entry("some:key")["us"] == 12.5
    assert c2.get("missing") is None
    assert len(c2) == 1


def test_cache_sees_external_writes(tmp_path):
    """A long-lived PlanCache re-reads the file when another instance (or
    process) writes it — the same-process tune-then-consume path."""
    import os

    path = tmp_path / "cache.json"
    long_lived = PlanCache(path)
    assert long_lived.get("k") is None  # primes the (empty) memo
    writer = PlanCache(path)
    writer.put("k", Plan(4, 8))
    # Force a distinct mtime even on coarse-mtime filesystems.
    os.utime(path, ns=(1, 1))
    assert long_lived.get("k") == Plan(4, 8)


def test_cache_tolerates_corruption(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text("{not json")
    c = PlanCache(path)
    with pytest.warns(UserWarning, match="corrupt"):
        assert c.get("k") is None
    c.put("k", Plan(2, 8))
    assert PlanCache(path).get("k") == Plan(2, 8)


def test_corrupt_cache_quarantined_with_oneshot_warning(tmp_path):
    """ISSUE 10: a cache that does not parse is moved to ``<path>.corrupt``
    and warned about once (naming the file), instead of being silently
    read as empty forever."""
    from repro.core import autotune

    autotune.reset_shared_caches()
    path = tmp_path / "cache.json"
    path.write_text('{"version": 1, "entries": {')   # truncated write
    with pytest.warns(UserWarning, match=str(path)) as rec:
        assert PlanCache(path)._read_disk() == {}
    assert len(rec) == 1
    assert (tmp_path / "cache.json.corrupt").read_text().startswith(
        '{"version"')                                # bytes kept for triage
    assert not path.exists()                         # path cleared for writes
    # one-shot per path per process: a second corrupt read warns nothing
    path.write_text("[1, 2, 3]")                     # non-object JSON
    import warnings as _w
    with _w.catch_warnings(record=True) as again:
        _w.simplefilter("always")
        assert PlanCache(path)._read_disk() == {}
    assert again == []
    autotune.reset_shared_caches()                   # clears the warn memo


def test_structural_garbage_quarantined_not_attribute_error(tmp_path):
    """Valid JSON that is not the cache schema (non-object top level,
    non-object entries) used to escape the old ``(OSError, ValueError)``
    net as an AttributeError; now it quarantines like any corruption."""
    from repro.core import autotune

    for payload in ('["not", "a", "dict"]',
                    '{"version": 1, "entries": [1, 2]}'):
        autotune.reset_shared_caches()
        path = tmp_path / "garbage.json"
        path.write_text(payload)
        with pytest.warns(UserWarning, match="corrupt"):
            assert PlanCache(path)._read_disk() == {}
        assert not path.exists()
        (tmp_path / "garbage.json.corrupt").unlink()
    autotune.reset_shared_caches()


def test_version_mismatch_still_silently_empty(tmp_path):
    """A *valid* cache from another schema generation is not corruption:
    read as empty with no warning and no quarantine (documented behavior
    — see the ``_CACHE_VERSION`` note in ``core/autotune.py``)."""
    import json as _json
    import warnings as _w

    from repro.core import autotune

    autotune.reset_shared_caches()
    path = tmp_path / "cache.json"
    path.write_text(_json.dumps({"version": 999, "entries": {"k": {}}}))
    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        assert PlanCache(path)._read_disk() == {}
    assert rec == [] and path.exists()


def test_cache_key_stability():
    p = TConvProblem(4, 4, 8, 3, 4, 2)
    key = cache_key(p, dtype=jnp.float32, batch=2)
    assert key == "tconv:ih4:iw4:ic8:ks3:oc4:s2:SAME|float32|tpu-v5e|b2"
    # Same inputs -> same key (no process-dependent state).
    assert key == cache_key(TConvProblem(4, 4, 8, 3, 4, 2),
                            dtype=jnp.float32, batch=2)
    assert cache_key(p, dtype=jnp.int8) != key


# ---------------------------------------------------------------------------
# Autotuner end-to-end
# ---------------------------------------------------------------------------


def test_autotune_and_execute_through_ops(tmp_path):
    p = TConvProblem(4, 4, 2, 3, 2, 2)
    cache = PlanCache(tmp_path / "tune.json")
    res = autotune_result(p, cache=cache, max_measure=2, repeats=1)
    assert not res.from_cache and res.n_measured >= 2
    assert res.plan.block_oh % p.stride == 0

    # The tuned plan computes the right answer through ops.tconv.
    x, w = _xw(ih=p.ih, iw=p.iw, ic=p.ic, ks=p.ks, oc=p.oc)
    got = np.asarray(tconv(x, w, stride=p.stride, plan=res.plan))
    want = np.asarray(ref.iom_reference(x, w, stride=p.stride))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    # Second call is a cache hit with the identical plan; a fresh PlanCache
    # object (separate process in spirit) sees it too.
    res2 = autotune_result(p, cache=cache, max_measure=2, repeats=1)
    assert res2.from_cache and res2.plan == res.plan
    assert PlanCache(tmp_path / "tune.json").get(res.key) == res.plan


def test_autotune_never_times_a_plan_the_compiler_refuses(tmp_path,
                                                         monkeypatch):
    """Plans the chip's compiler refuses are skipped in rank order: the
    autotuner still times ``max_measure`` plans, none of them refused."""
    from repro.core import autotune, tiling

    p = TConvProblem(16, 16, 32, 3, 16, 1)
    timed = []

    def fake_measure(problem, plan, **kw):
        timed.append(plan)
        return 1.0 + len(timed)

    refused = []

    def compiles(problem, plan, **kw):  # refuse every other plan asked
        asked = compiles.calls = getattr(compiles, "calls", 0) + 1
        if asked % 2:
            refused.append(plan)
        return not asked % 2

    monkeypatch.setattr(tiling, "family_compiles", lambda *a, **kw: True)
    monkeypatch.setattr(tiling, "plan_compiles", compiles)
    monkeypatch.setattr(autotune, "measure_plan", fake_measure)
    res = autotune_result(p, cache=PlanCache(tmp_path / "tune.json"),
                          max_measure=3, repeats=1)
    assert len(timed) == 3 and res.n_measured == 3
    assert refused and not set(refused) & set(timed), (refused, timed)


def test_tconv_int8_explicit_bad_plan_raises():
    """tconv_int8 surfaces the same block_oh-vs-stride ValueError as tconv
    for an explicit plan, instead of deferring to a deeper kernel assert."""
    from repro.kernels.ops import tconv_int8

    x = RNG.integers(-128, 128, (1, 4, 4, 2)).astype(np.int8)
    w = RNG.integers(-128, 128, (3, 3, 2, 2)).astype(np.int8)
    b = np.zeros((2,), np.int32)
    with pytest.raises(ValueError, match="multiple of"):
        tconv_int8(x, w, b, 0.05, stride=2, plan=Plan(3, 2))


def test_bwd_zero_bias_keeps_weight_dtype():
    """Gradients through the bias-free MM2IM path must not silently
    promote bf16 to f32 (regression: bwd hardcoded an f32 zero-bias)."""
    import jax

    x = jnp.asarray(RNG.standard_normal((1, 4, 4, 2)), jnp.bfloat16)
    w = jnp.asarray(RNG.standard_normal((3, 3, 2, 2)) * 0.1, jnp.bfloat16)
    dx, dw = jax.grad(
        lambda xx, ww: tconv(xx, ww, stride=2).astype(jnp.float32).sum(),
        argnums=(0, 1))(x, w)
    assert dx.dtype == jnp.bfloat16 and dx.shape == x.shape
    assert dw.dtype == jnp.bfloat16 and dw.shape == w.shape


def test_default_plan_matches_heuristic():
    p = TConvProblem(8, 8, 16, 5, 12, 2)
    d = default_plan(p)
    from repro.kernels.mm2im_pallas import VMEM_BUDGET, plan_blocks
    boh, boc = plan_blocks(p.ih, p.iw, p.ic, p.ks, p.oc, p.stride, p.padding,
                           vmem_budget=VMEM_BUDGET, in_bytes=4)
    assert (d.block_oh, d.block_oc) == (boh, boc)


def test_measure_plan_returns_positive_time():
    p = TConvProblem(3, 3, 2, 3, 2, 1)
    us = measure_plan(p, Plan(1, 2), repeats=1, warmup=1)
    assert us > 0


def test_measure_plan_int8_times_requant_epilogue():
    """int8 candidates must be timed with a representative bias +
    per-tensor out_scale so the measured program is the int8-output
    requant kernel tconv_int8 will actually run — not a bare int32-output
    MatMul (regression: the epilogue was silently dropped)."""
    from repro.core.autotune import measure_epilogue

    p = TConvProblem(3, 3, 2, 3, 2, 1)
    bias, out_scale = measure_epilogue(p, jnp.int8)
    assert bias is not None and bias.shape == (p.oc,)
    assert bias.dtype == jnp.int32
    assert isinstance(out_scale, float) and out_scale > 0
    # Float dtypes keep the epilogue-free forward.
    assert measure_epilogue(p, jnp.float32) == (None, None)
    # And the int8 measurement path runs end-to-end through the kernel.
    us = measure_plan(p, Plan(1, 2), dtype=jnp.int8, repeats=1, warmup=1)
    assert us > 0


def test_cache_save_merges_concurrent_writers(tmp_path):
    """_save must merge only its dirty keys over current on-disk entries:
    a writer whose memo predates another process's writes neither drops
    that process's *new* keys nor reverts its *re-tuned* ones
    (last-writer-wins per *key*, not per file)."""
    path = tmp_path / "cache.json"
    c1 = PlanCache(path)
    c1.put("k1", Plan(2, 2))
    # Another process adds k2 AND re-tunes k1 behind c1's back.
    other = PlanCache(path)
    other.put("k2", Plan(4, 4))
    other.put("k1", Plan(16, 16))
    # Simulate the read-modify-write race: c1's memo is stale (old k1, no
    # k2) but its recorded mtime matches the file, so _load() trusts the
    # memo — exactly the state a slow writer is in between load and save.
    c1._loaded_mtime = c1._mtime()
    c1._entries = {"k1": {"plan": Plan(2, 2).to_json()}}
    c1.put("k3", Plan(8, 8))
    survivors = PlanCache(path)
    assert survivors.get("k2") == Plan(4, 4), "concurrent new key clobbered"
    assert survivors.get("k1") == Plan(16, 16), \
        "stale memo reverted a concurrent re-tune of an untouched key"
    assert survivors.get("k3") == Plan(8, 8)


def test_cache_concurrent_processes_lose_no_keys(tmp_path):
    """Two real processes writing disjoint keys into one cache file at
    the same time: the flock-serialized merge in _save keeps every key —
    the property tune_sweep's zero-re-measurement resumability relies on
    when shards share a cache."""
    import os
    import subprocess
    import sys

    from pathlib import Path

    path = tmp_path / "cache.json"
    script = (
        "import sys\n"
        "from repro.core.autotune import PlanCache\n"
        "from repro.kernels.registry import Plan\n"
        f"c = PlanCache({str(path)!r})\n"
        "tag = sys.argv[1]\n"
        "for i in range(15):\n"
        "    c.put(f'{tag}:{i}', Plan(2, 2))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        str(Path(__file__).resolve().parent.parent / "src")
        + os.pathsep + env.get("PYTHONPATH", ""))
    procs = [subprocess.Popen([sys.executable, "-c", script, tag],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for tag in ("a", "b")]
    for pr in procs:
        out, err = pr.communicate(timeout=300)
        assert pr.returncode == 0, err.decode()
    final = PlanCache(path)
    missing = [f"{t}:{i}" for t in ("a", "b") for i in range(15)
               if final.get(f"{t}:{i}") is None]
    assert not missing, f"concurrent writers lost keys: {missing}"


def test_cache_hit_without_timings_reports_nan_speedup(tmp_path):
    """An entry lacking us/default_us (imported table, hand-written) must
    not report speedup 0.0 — that reads as a 0x slowdown; NaN means
    'unknown' (regression)."""
    import math

    from repro.core.autotune import autotune_result, cache_key

    p = TConvProblem(4, 4, 2, 3, 2, 2)
    cache = PlanCache(tmp_path / "tune.json")
    cache.put(cache_key(p), Plan(2, 2))  # no us / default_us metadata
    res = autotune_result(p, cache=cache, max_measure=2, repeats=1)
    assert res.from_cache
    assert math.isnan(res.us) and math.isnan(res.default_us)
    assert math.isnan(res.speedup_vs_default)
    # Timed entries still report a real ratio.
    from repro.core.autotune import TuningResult
    ok = TuningResult(key="k", plan=Plan(2, 2), us=50.0,
                      default_plan=Plan(2, 2), default_us=100.0,
                      n_candidates=1, n_measured=1, from_cache=False)
    assert ok.speedup_vs_default == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Automatic plan-cache consumption (no explicit plans= anywhere)
# ---------------------------------------------------------------------------


def _fresh_autoload(monkeypatch, tmp_path):
    """Point auto-consumption at an empty tmp cache and reset memos.

    Also isolates the shipped-table tier (an empty tmp dir) so the
    committed ``src/repro/data/plans/`` tables cannot serve these tests'
    problems behind the user cache's back.
    """
    from repro.core import autotune, plan_table
    from repro.kernels import ops

    path = tmp_path / "auto_cache.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    monkeypatch.setenv(plan_table.TABLE_DIR_ENV, str(tmp_path / "no_plans"))
    monkeypatch.delenv(ops.AUTOLOAD_ENV, raising=False)
    autotune.reset_shared_caches()
    plan_table.reset_shipped_tables()
    ops.clear_consumed_plans()
    return autotune.PlanCache(path)


def test_tconv_layer_consumes_cached_plan(monkeypatch, tmp_path):
    """Write a plan to a tmp cache -> tconv_layer picks it up with no
    plan= argument, including the tuned kernel-variant preference.

    Shapes here are unique within the test session: ops.tconv's jit cache
    is keyed by shapes + static args, so a shape compiled before the cache
    entry existed would (correctly) not retrace.
    """
    import jax

    from repro.core.autotune import cache_key
    from repro.core.maps import TConvProblem
    from repro.kernels import ops

    cache = _fresh_autoload(monkeypatch, tmp_path)
    ih, iw, ic, ks, oc, s = 7, 5, 2, 3, 3, 2
    p = TConvProblem(ih, iw, ic, ks, oc, s)
    plan = Plan(4, 3, "bcj", "mm2im_db")
    key = cache_key(p, dtype=jnp.float32, batch=1)
    cache.put(key, plan)

    params, _ = layers_common.init_tconv(jax.random.PRNGKey(0), ks, oc, ic)
    x = RNG.standard_normal((1, ih, iw, ic)).astype(np.float32)
    got = np.asarray(layers_common.tconv_layer(params, x, stride=s))
    want = np.asarray(
        ref.tconv_lax(x, np.asarray(params["w"]), stride=s)
        + np.asarray(params["b"]))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    consumed = ops.consumed_plans()
    from repro.core.autotune import TIER_USER_CACHE
    assert consumed and consumed[-1] == (key, plan, TIER_USER_CACHE), consumed


def test_autoload_disabled_by_env(monkeypatch, tmp_path):
    from repro.core.autotune import cache_key
    from repro.core.maps import TConvProblem
    from repro.kernels import ops
    from repro.kernels.ops import tconv

    cache = _fresh_autoload(monkeypatch, tmp_path)
    p = TConvProblem(7, 3, 2, 3, 3, 2)
    cache.put(cache_key(p, dtype=jnp.float32, batch=1), Plan(2, 3, "bcj"))
    monkeypatch.setenv(ops.AUTOLOAD_ENV, "0")

    x = RNG.standard_normal((1, 7, 3, 2)).astype(np.float32)
    w = (RNG.standard_normal((3, 3, 3, 2)) * 0.1).astype(np.float32)
    got = np.asarray(tconv(x, w, stride=2))
    np.testing.assert_allclose(
        got, np.asarray(ref.tconv_lax(x, w, stride=2)), rtol=1e-4, atol=1e-4)
    assert not ops.consumed_plans()


def test_gan_step_builders_consume_cache(monkeypatch, tmp_path):
    """runtime/steps resolves per-layer cached plans with no plans= arg;
    explicit entries take precedence over cache hits."""
    import jax

    from repro.core.autotune import cache_key
    from repro.models import gan
    from repro.runtime import steps

    cache = _fresh_autoload(monkeypatch, tmp_path)
    gp, _ = gan.init_dcgan_g(jax.random.PRNGKey(1), scale_down=64)
    probs = gan.dcgan_tconv_problems(gp)
    t1_plan = Plan(2, 4, "bcj", "mm2im_db")
    t2_plan = Plan(4, 2, "cbj")
    cache.put(cache_key(probs["t1"], dtype=jnp.float32, batch=3), t1_plan)
    cache.put(cache_key(probs["t2"], dtype=jnp.float32, batch=3), t2_plan)

    resolved = steps.resolve_gan_plans(gp, batch=3)
    assert resolved == {"t1": t1_plan, "t2": t2_plan}
    # Explicit plans= beats the cache (precedence contract).
    override = Plan(2, 2, "bcj")
    resolved = steps.resolve_gan_plans(gp, batch=3, plans={"t1": override})
    assert resolved["t1"] == override and resolved["t2"] == t2_plan
    # Builders accept the resolved mapping end-to-end (trace only).
    bundle = steps.make_gan_sample_step(gp, batch=3)
    assert bundle.kind == "gan_sample"


def test_plan_free_methods_ignore_cache(monkeypatch, tmp_path):
    """A populated cache must not break plan-incapable methods: the GAN
    builders skip resolution for them, and an unregistered plan.method in
    the cache degrades to the default kernel instead of raising."""
    import jax

    from repro.core.autotune import cache_key
    from repro.core.maps import TConvProblem
    from repro.models import gan
    from repro.runtime import steps

    cache = _fresh_autoload(monkeypatch, tmp_path)
    gp, _ = gan.init_dcgan_g(jax.random.PRNGKey(2), scale_down=64)
    for prob in gan.dcgan_tconv_problems(gp).values():
        cache.put(cache_key(prob, dtype=jnp.float32, batch=2), Plan(2, 2))
    # method='lax' cannot take plans; the builder must not hand it any.
    bundle = steps.make_gan_sample_step(gp, batch=2, method="lax")
    assert bundle.meta["plans"] == {}
    z = RNG.standard_normal((2, 100)).astype(np.float32)
    np.asarray(bundle.fn(gp, z))  # traces + runs without a dispatch error

    # Stale cache entry naming an unregistered variant: lookup_plan skips
    # the whole entry (with a warning) and dispatch runs the heuristic —
    # see test_stale_method_plan_skipped_with_warning for the tier walk.
    p = TConvProblem(3, 7, 2, 3, 3, 2)
    cache.put(cache_key(p, dtype=jnp.float32, batch=1),
              Plan(2, 3, "bcj", "not_a_registered_kernel"))
    x = RNG.standard_normal((1, 3, 7, 2)).astype(np.float32)
    w = (RNG.standard_normal((3, 3, 3, 2)) * 0.1).astype(np.float32)
    with pytest.warns(UserWarning, match="unregistered"):
        got = np.asarray(tconv(x, w, stride=2))
    np.testing.assert_allclose(
        got, np.asarray(ref.tconv_lax(x, w, stride=2)), rtol=1e-4, atol=1e-4)

    # Corrupt geometry (block_oh not a stride multiple): the auto-loaded
    # plan is discarded — heuristic dispatch instead of a ValueError.
    p_bad = TConvProblem(3, 5, 2, 3, 3, 2)
    cache.put(cache_key(p_bad, dtype=jnp.float32, batch=1), Plan(3, 3))
    from repro.kernels import ops
    ops.clear_consumed_plans()
    x = RNG.standard_normal((1, 3, 5, 2)).astype(np.float32)
    w = (RNG.standard_normal((3, 3, 3, 2)) * 0.1).astype(np.float32)
    got = np.asarray(tconv(x, w, stride=2))
    np.testing.assert_allclose(
        got, np.asarray(ref.tconv_lax(x, w, stride=2)), rtol=1e-4, atol=1e-4)
    assert not ops.consumed_plans()


def test_stale_method_plan_skipped_with_warning(monkeypatch, tmp_path):
    """An entry whose ``Plan.method`` is not in this checkout's registry —
    a cache or table written by a newer build with an extra kernel family
    — must be *skipped with a warning* at every read tier, falling through
    to the next one, and never fail dispatch (regression: lookup used to
    return the plan and dispatch raised on the unknown method)."""
    import json

    import jax

    from repro.core import autotune, plan_table

    cache = _fresh_autoload(monkeypatch, tmp_path)
    p = TConvProblem(5, 3, 2, 3, 3, 2)
    key = cache_key(p, dtype=jnp.float32, batch=1)

    # Shipped-table tier: a valid v2 table whose entry names a kernel
    # family this build does not have.
    tdir = tmp_path / "tables"
    tdir.mkdir()
    backend = jax.default_backend()
    table = {
        "version": 2,
        "provenance": {"backend": backend, "jax": jax.__version__,
                       "repeats": 1, "created": 0.0},
        "entries": {key: {"plan": Plan(2, 3, "bcj",
                                       "kernel_from_the_future").to_json()}},
    }
    (tdir / f"{backend}.json").write_text(json.dumps(table))
    monkeypatch.setenv(plan_table.TABLE_DIR_ENV, str(tdir))
    plan_table.reset_shipped_tables()

    with pytest.warns(UserWarning, match="unregistered"):
        assert autotune.lookup_plan(p, cache=cache) is None  # -> heuristic

    # User-cache tier: a stale entry there warns too and falls through to
    # the shipped tier (also stale here) -> still a clean miss.
    cache.put(key, Plan(4, 3, "bcj", "another_future_kernel"))
    with pytest.warns(UserWarning, match="unregistered"):
        assert autotune.lookup_plan(p, cache=cache) is None

    # A *valid* shipped entry underneath is reachable: the stale user
    # cache falls through TO it instead of masking the tier.
    good = Plan(2, 3, "bcj", "mm2im")
    table["entries"][key]["plan"] = good.to_json()
    (tdir / f"{backend}.json").write_text(json.dumps(table))
    plan_table.reset_shipped_tables()
    with pytest.warns(UserWarning, match="unregistered"):
        hit = autotune.lookup_plan(p, cache=cache)
    assert hit == (good, autotune.TIER_SHIPPED)

    # End-to-end: dispatch under the stale user cache computes correctly.
    x = RNG.standard_normal((1, p.ih, p.iw, p.ic)).astype(np.float32)
    w = (RNG.standard_normal((p.ks, p.ks, p.oc, p.ic)) * 0.1
         ).astype(np.float32)
    with pytest.warns(UserWarning, match="unregistered"):
        got = np.asarray(tconv(x, w, stride=p.stride))
    np.testing.assert_allclose(
        got, np.asarray(ref.tconv_lax(x, w, stride=p.stride)),
        rtol=1e-4, atol=1e-4)


def test_tuned_plan_through_layer_and_model(tmp_path):
    """Plans flow through layers.common.tconv_layer and models.gan."""
    import jax

    from repro.models import gan

    p = TConvProblem(4, 4, 4, 3, 4, 2)
    plan = Plan(2, 4, "bcj")
    params, _ = layers_common.init_tconv(jax.random.PRNGKey(0), 3, 4, 4)
    x = RNG.standard_normal((1, 4, 4, 4)).astype(np.float32)
    got = np.asarray(layers_common.tconv_layer(params, x, stride=2,
                                               plan=plan))
    want = np.asarray(tconv(x, params["w"], params["b"], stride=2))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    # DCGAN generator with per-layer plans == without (numerics unchanged).
    gp, _ = gan.init_dcgan_g(jax.random.PRNGKey(1), scale_down=64)
    probs = gan.dcgan_tconv_problems(gp)
    assert probs["t1"].ih == 4 and probs["t4"].oc == 3
    plans = {name: Plan(2 * pr.stride, min(pr.oc, 4))
             for name, pr in probs.items()}
    z = RNG.standard_normal((2, 100)).astype(np.float32)
    img_plain = np.asarray(gan.dcgan_generator(gp, z))
    img_planned = np.asarray(gan.dcgan_generator(gp, z, plans=plans))
    np.testing.assert_allclose(img_planned, img_plain, rtol=1e-4, atol=1e-4)
