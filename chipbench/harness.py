"""One benchmark run: build the cell, warm it, drive the window, check
the served outputs against the plain reference, and report.

Everything specific to a configuration, a traffic mix or a per-layer
metric is a file found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: sizes, the program's runner name, and the
  reference module ``reference/<reference>.py``;
* ``traffic/<traffic>.json``: the loop, rate or clients, precision and
  serving settings (read by ``loadgen``);
* ``limits/<config>.<precision>.json``: the reference's precision and the
  limit of each number compared;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(ctx)``
  returning a number or ``None`` when it finds nothing to read.

The program is used only through ``make_runner`` and ``TconvServer``; its
weights and inputs are made here from the seed.
"""

from __future__ import annotations

import collections
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

from chipbench import counts, loadgen

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
STATE_DIR = ROOT / "chiprun_out" / "chipbench"
CACHE_DIR = ROOT / ".jax_cache"
KEEP_BLOCK = 24        # consecutive requests kept together for the check
KEEP_BLOCKS = 16       # blocks held by the seeded reservoir
COLLECT_GRACE_S = 60.0  # how long past the window a due answer is awaited
TRACE_START_S = 1.0    # where the traced part of a window starts ...
TRACE_S = 2.0          # ... and how long it lasts
FREEZE_TICK_S = 0.005  # FreezeWatch's sleep ...
FREEZE_S = 0.05        # ... and the oversleep it keeps


_T_IMPORT = time.perf_counter()


def stamp(msg: str) -> None:
    """A progress line on stderr, with seconds since this module loaded."""
    print(f"[chipbench] {time.perf_counter() - _T_IMPORT:8.2f}s {msg}",
          file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


# ---------------------------------------------------------------------------
# Files found by name.
# ---------------------------------------------------------------------------


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, name: str, bench_dir=BENCH_DIR,
                 cfg_update=None, traffic_update=None):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(by_name)}")
        self.name = name
        self.workload = by_name[name]
        self.chips = int(self.workload["chips"])
        cfgs = {c["name"]: c for c in bench["configs"]}
        cfg_entry = cfgs[self.workload["config"]]
        self.cfg = read_json(ROOT / cfg_entry["file"]
                             if not Path(cfg_entry["file"]).is_absolute()
                             else cfg_entry["file"])
        self.cfg.update(cfg_update or {})
        self.traffic = read_json(bench_dir / "traffic"
                                 / f"{self.workload['traffic']}.json")
        self.traffic.update(traffic_update or {})
        self.precision = self.traffic["precision"]
        self.model = load_module(
            bench_dir / "reference" / f"{self.cfg['reference']}.py",
            f"chipbench_reference_{self.cfg['name'].replace('-', '_')}")
        self.limits = read_json(bench_dir / "limits"
                                / f"{self.cfg['name']}.{self.precision}.json")
        self.e2e = [m for m in bench["end_to_end"] if _in_cell(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _in_cell(m, name)]
        self.layers = self.model.layers(self.cfg)


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reference_prec(cell: Cell, **changes):
    from chipbench import plain

    ref = dict(cell.limits["reference"])
    ref.update(changes)
    return plain.Prec(**ref)


# ---------------------------------------------------------------------------
# Device and caches.
# ---------------------------------------------------------------------------


def check_device(jax, chips: int, peaks: dict) -> dict:
    """The device record; raises :class:`NoDevice` unless the default
    device is a TPU in the peak table with at least ``chips`` devices."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoDevice(f"default device is {dev.platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} devices, the cell asks for {chips}")
    if dev.device_kind not in peaks["devices"]:
        raise NoDevice(f"device kind {dev.device_kind!r} is not in the peak "
                       f"table ({sorted(peaks['devices'])})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def fix_caches(jax) -> None:
    """Compile cache at ``.jax_cache/`` in the checkout, every program
    cached; plans only from the shipped tables and the heuristic."""
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Compiles:
    """Counts of persistent-cache hits and misses and seconds of backend
    compilation, from JAX's monitoring events, since the process began.
    ``snapshot()`` is read at set-up's end and at the window's close: a
    second run of a cell on the same seed has to show no miss in set-up,
    and no run may compile inside its window."""

    _counts = {"cache_hits": 0, "cache_misses": 0, "compile_s": 0.0}
    _installed = False

    @classmethod
    def install(cls, jax_monitoring) -> None:
        if cls._installed:
            return
        cls._installed = True

        def on_event(event, **_):
            name = event.rsplit("/", 1)[-1]
            if name in ("cache_hits", "cache_misses"):
                cls._counts[name] += 1

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls._counts["compile_s"] += float(secs)

        jax_monitoring.register_event_listener(on_event)
        jax_monitoring.register_event_duration_secs_listener(on_duration)

    @classmethod
    def snapshot(cls) -> dict:
        return dict(cls._counts)

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


class GcWatch:
    """Collections of the cyclic garbage collector while it is installed:
    how many, their total and their longest pause, in seconds."""

    def __init__(self):
        self.count, self.total_s, self.longest_s = 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.count += 1
            self.total_s += d
            self.longest_s = max(self.longest_s, d)
            self._t = None

    def stop(self) -> dict:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)
        return {"collections": self.count, "total_s": self.total_s,
                "longest_s": self.longest_s}


class FreezeWatch(threading.Thread):
    """Sleeps ``FREEZE_TICK_S`` at a time through the window and keeps
    each oversleep longer than ``FREEZE_S``: a stretch in which this
    process ran no Python (the GIL held elsewhere, or the process not
    scheduled).  Times are ``time.monotonic()``, which other processes
    on the machine share."""

    def __init__(self):
        super().__init__(name="chipbench-freezes", daemon=True)
        self.gaps = []
        self._halt = threading.Event()
        self.start()

    def run(self):
        last = time.monotonic()
        while not self._halt.is_set():
            time.sleep(FREEZE_TICK_S)
            now = time.monotonic()
            if now - last > FREEZE_S:
                self.gaps.append((last, now - last))
            last = now

    def stop(self) -> dict:
        self._halt.set()
        self.join()
        top = sorted(self.gaps, key=lambda g: -g[1])[:5]
        return {"count": len(self.gaps),
                "total_s": sum(d for _, d in self.gaps),
                "longest_s": top[0][1] if top else 0.0,
                "top": [[t, d] for t, d in top]}


def longest_stall(done, t0: float, t1: float) -> float:
    """The longest time inside [t0, t1) between two consecutive batch
    completions (a batch's members share one completion time)."""
    d = np.unique(np.asarray(done, np.float64))
    d = d[np.isfinite(d) & (d >= t0) & (d < t1)]
    if d.size == 0:
        return t1 - t0
    return float(np.max(np.diff(np.concatenate([[t0], d, [t1]]))))


def isolate_plan_cache() -> None:
    STATE_DIR.mkdir(parents=True, exist_ok=True)
    user_cache = STATE_DIR / "autotune_cache.json"
    user_cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(user_cache)


# ---------------------------------------------------------------------------
# Weights and inputs, made on the device from the seed.
# ---------------------------------------------------------------------------


def make_params(cell: Cell, key):
    import jax
    import jax.numpy as jnp

    shapes = cell.model.param_shapes(cell.cfg)
    std = float(cell.cfg["init_std"])

    def init(key):
        return {name: std * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32)
                for i, (name, shape) in enumerate(sorted(shapes.items()))}

    return jax.jit(init)(key)


def make_pool(cell: Cell, key, n: int) -> np.ndarray:
    import jax

    fn = jax.jit(lambda k: cell.model.make_inputs(k, n, cell.cfg))
    return np.asarray(fn(key))


# ---------------------------------------------------------------------------
# The window.
# ---------------------------------------------------------------------------


class Keeper:
    """Which requests keep their outputs for the check: blocks of
    ``KEEP_BLOCK`` consecutive requests, held by a reservoir of
    ``KEEP_BLOCKS`` whose draws come from the seed in block order."""

    def __init__(self, seed: int):
        self._rng = np.random.default_rng([int(seed) % 2 ** 64, 7])
        self._slots = {}          # slot -> block
        self._n_blocks = 0
        self.outputs = {}         # request index -> output row
        self._lock = threading.Lock()

    def admit(self, index: int) -> None:
        """Called in index order, as each request is sent."""
        if index % KEEP_BLOCK:
            return
        b = index // KEEP_BLOCK
        with self._lock:
            self._n_blocks += 1
            if len(self._slots) < KEEP_BLOCKS:
                self._slots[len(self._slots)] = b
            else:
                j = int(self._rng.integers(0, self._n_blocks))
                if j < KEEP_BLOCKS:
                    old = self._slots[j]
                    self._slots[j] = b
                    for i in range(old * KEEP_BLOCK, (old + 1) * KEEP_BLOCK):
                        self.outputs.pop(i, None)

    def kept(self, index: int) -> bool:
        return index // KEEP_BLOCK in self._slots.values()

    def store(self, index: int, out) -> None:
        with self._lock:
            if self.kept(index):
                self.outputs[index] = np.array(out)


class Record:
    """Per-request times, filled in as the window runs."""

    def __init__(self, n: int):
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.pool_index = np.zeros(n, np.int64)

    def grow(self, n: int) -> None:
        for name in ("due", "sent", "done", "ok", "pool_index"):
            old = getattr(self, name)
            if n > old.size:
                new = np.full(n, np.nan) if old.dtype == np.float64 else \
                    np.zeros(n, old.dtype)
                new[:old.size] = old
                setattr(self, name, new)


def _settle(req, index, rec: Record, keeper: Keeper) -> None:
    rec.done[index] = req.t_done if req.t_done is not None else np.nan
    try:
        out = req.result(timeout=0)
    except Exception:  # noqa: BLE001 — failed or shed: missing
        rec.ok[index] = False
        return
    rec.ok[index] = True
    keeper.store(index, out)


def drive_open(server, model_name, pool, order, cell, offsets, t0, rec,
               keeper, annotate):
    """One generator thread sends on the schedule; one collector settles
    answers in order and drops what the check does not keep."""
    precision = cell.precision
    pending = collections.deque()
    finished = threading.Event()

    def collect():
        while True:
            while pending and pending[0][1].done():
                i, r = pending.popleft()
                _settle(r, i, rec, keeper)
            if finished.is_set() and not pending:
                return
            time.sleep(0.001)

    collector = threading.Thread(target=collect, name="chipbench-collect",
                                 daemon=True)
    collector.start()
    n = offsets.size
    rec.due[:] = t0 + offsets
    for i in range(n):
        due = rec.due[i]
        now = time.monotonic()
        if due > now:
            with annotate("chipbench.await_arrival"):
                time.sleep(due - now)
        keeper.admit(i)
        p = order[i % order.size]
        rec.pool_index[i] = p
        with annotate("chipbench.submit"):
            rec.sent[i] = time.monotonic()
            try:
                r = server.submit(model_name, pool[p], precision=precision)
            except Exception:  # noqa: BLE001 — shed at admission: missing
                continue
        pending.append((i, r))
    return finished, collector


def drive_closed(server, model_name, pool, order, cell, bounds, rec, keeper,
                 annotate):
    """``clients`` threads, each sending its next request when the last
    returned, until the window closes.  The threads wait on the returned
    barrier; ``bounds`` holds ``t0`` and ``t1`` once it is released."""
    precision = cell.precision
    lock = threading.Lock()
    counter = iter(range(10 ** 9))
    n_clients = int(cell.traffic["clients"])
    start = threading.Barrier(n_clients + 1)

    def client():
        start.wait()
        t1 = bounds["t1"]
        while True:
            with lock:
                if time.monotonic() >= t1:
                    return
                i = next(counter)
                keeper.admit(i)
                if i >= rec.due.size:
                    rec.grow(2 * rec.due.size)
                p = order[i % order.size]
                rec.pool_index[i] = p
            with annotate("chipbench.submit"):
                sent = time.monotonic()
                try:
                    r = server.submit(model_name, pool[p],
                                      precision=precision)
                except Exception:  # noqa: BLE001 — shed: missing
                    r = None
            with lock:
                rec.due[i] = rec.sent[i] = sent
            if r is None:
                continue
            with annotate("chipbench.client_wait"):
                try:
                    r.result(timeout=COLLECT_GRACE_S + t1 - sent)
                except Exception:  # noqa: BLE001 — settled below
                    pass
            with lock:
                _settle(r, i, rec, keeper)

    threads = [threading.Thread(target=client, name=f"chipbench-client{k}",
                                daemon=True) for k in range(n_clients)]
    for t in threads:
        t.start()
    return start, threads


# ---------------------------------------------------------------------------
# The check.
# ---------------------------------------------------------------------------


def served_batches(rec: Record, keeper: Keeper, n_sent: int):
    """Whole batches whose every member kept its output, as lists of
    request indices.  Requests served in one batch share its completion
    time exactly (the server stamps a batch once)."""
    groups = collections.defaultdict(list)
    for i in range(n_sent):
        if rec.ok[i]:
            groups[rec.done[i]].append(i)
    return [sorted(m) for _, m in sorted(groups.items())
            if all(i in keeper.outputs for i in m)]


def compare(cell: Cell, params, batches, pool, rec: Record, keeper: Keeper,
            precs: dict) -> dict:
    """Gaps from the reference, on the same padded batches.

    ``precs`` maps names to ``plain.Prec``; each is computed on every
    batch, and ``served`` is what the window served.  For each output
    other than ``reference``: the widest gap between its elements and the
    reference's (``max_abs_err``), and the RMS gap over the reference's
    RMS (``rel_rms_err``)."""
    import jax
    import jax.numpy as jnp

    from chipbench import plain

    target = int(cell.traffic["target_batch"])
    absmax = {}

    def forward_fn(prec):
        if cell.precision == "int8":
            if prec.xla not in absmax:
                absmax[prec.xla] = plain.calibrate(cell.model, params,
                                                   cell.cfg, prec)
            scales = plain.int_scales(absmax[prec.xla], prec.int_bits)
            fn = jax.jit(lambda p, x, sc: plain.forward_int(
                cell.model, p, x, cell.cfg, prec, sc))
            return lambda p, x: fn(p, x, scales)
        fn = jax.jit(lambda p, x: plain.forward_f32(
            cell.model, p, x, cell.cfg, prec))
        return fn

    fns = {name: forward_fn(p) for name, p in precs.items()}
    sums = {}
    shape = tuple(cell.model.input_shape(cell.cfg))
    for k, members in enumerate(batches):
        if k == 1:
            stamp("reference compiled and run on the first batch")
        xs = np.zeros((target,) + shape, np.float32)
        for j, i in enumerate(members):
            xs[j] = pool[rec.pool_index[i]]
        x_dev = jnp.asarray(xs)
        outs = {name: np.asarray(fn(params, x_dev),
                                 np.float64)[:len(members)]
                for name, fn in fns.items()}
        outs["served"] = np.stack([keeper.outputs[i] for i in members]
                                  ).astype(np.float64)
        want = outs.pop("reference")
        for name, got in outs.items():
            d = np.abs(got - want)
            s = sums.setdefault(name, [0.0, 0.0, 0.0])
            s[0] = max(s[0], float(np.max(d)) if np.isfinite(d).all()
                       else math.inf)
            s[1] += float(np.sum(d * d))
            s[2] += float(np.sum(want * want))
    return {name: {"max_abs_err": mx,
                   "rel_rms_err": math.sqrt(se / max(sr, 1e-30))}
            for name, (mx, se, sr) in sums.items()}


# ---------------------------------------------------------------------------
# Metrics context handed to the per-layer readers.
# ---------------------------------------------------------------------------


class Context:
    """What a per-layer reader may read, over the traced part of the
    window: ``trace`` (``trace.reduce_events``'s dict, empty without a
    device trace), ``stats0``/``stats1`` (the bucket's counters at its
    ends, see :func:`bucket_stats`), ``peaks`` (the device's row of
    ``peaks.json``), ``precision``, ``layers`` and ``target_batch``,
    ``per_image_ops`` (counted operations of one image) and
    ``lateness_s`` (per request, ``None`` in a closed loop)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def peak_ops(self) -> float:
        key = "int8_ops" if self.precision == "int8" else "bf16_flops"
        return float(self.peaks.get(key, 0.0))

    def stat_delta(self, field: str) -> float:
        return float(self.stats1[field]) - float(self.stats0[field])

    def kernel_roofline(self):
        """Roofline share of the TCONV kernels, or ``None`` without
        kernel events in the trace."""
        tr = self.trace
        if not tr or tr.get("kernel_s", 0.0) <= 0.0:
            return None
        batches = self.stat_delta("batches")
        if self.peak_ops() <= 0:
            return None
        t_min = counts.tconv_min_seconds(
            self.layers, self.target_batch, self.precision, self.peak_ops(),
            float(self.peaks["hbm_bytes_per_s"]))
        return 100.0 * batches * t_min / tr["kernel_s"]


def bucket_stats(server) -> dict:
    """The one bucket's cumulative counters, with the sums the means
    were taken from."""
    (b,) = server.stats()["buckets"].values()
    out = dict(b)
    out["fill_sum"] = b["batch_fill_ratio"] * b["batches"]
    out["wait_sum"] = b["queue_wait_mean_s"] * b["completed"]
    return out


def read_per_layer(cell: Cell, ctx: Context, bench_dir=BENCH_DIR) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                          "chipbench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# One run: set-up, the window, the check.
# ---------------------------------------------------------------------------


class Setup:
    """The built cell: device, runner behind a warmed server, weights and
    the input pool."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def setup(cell: Cell, seed: int, *, require_device: bool = True,
          compile_cache: bool = True, server_hook=None) -> Setup:
    isolate_plan_cache()
    import jax
    from jax import monitoring

    Compiles.install(monitoring)

    from chipbench import plain

    peaks_all = read_json(BENCH_DIR / "peaks.json")
    if require_device:
        device = check_device(jax, cell.chips, peaks_all)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    if compile_cache:
        fix_caches(jax)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.models.runner import make_runner
    from repro.serve.server import TconvServer

    stamp(f"device {device}")
    traffic = cell.traffic
    target = int(traffic["target_batch"])
    key = plain.key_from_seed(seed)
    params = make_params(cell, jax.random.fold_in(key, 0))
    stamp("weights made")
    runner = make_runner(cell.cfg["runner"], params=params,
                         **cell.cfg.get("runner_options", {}))
    model_name = cell.cfg["runner"]
    server = TconvServer({model_name: runner},
                         max_wait_s=float(traffic["max_wait_s"]),
                         default_batch=target)
    for r in server.warmup(precisions=(cell.precision,)):
        stamp(f"warm {r.model}:b{r.batch}:{r.precision} {r.seconds:.2f} s, "
            f"plan tiers {dict(r.tiers)}")
        if r.batch != target:
            raise RuntimeError(f"bucket warmed at batch {r.batch}, the "
                               f"traffic asks for {target}")
    if server_hook is not None:
        server_hook(server)
    pool = make_pool(cell, jax.random.fold_in(key, 1), int(traffic["pool"]))
    stamp(f"input pool {pool.shape}")
    return Setup(jax=jax, device=device,
                 peaks=peaks_all["devices"].get(device["kind"], {}),
                 params=params, runner=runner, server=server,
                 model_name=model_name, target=target, pool=pool)


class Window:
    def __init__(self, **kw):
        self.__dict__.update(kw)


class _Tracer(threading.Thread):
    """Traces a part of the window from a thread of its own: from
    ``TRACE_START_S`` after the window opens, for ``TRACE_S`` (the whole
    window when it is shorter than their sum), with the server's counters
    read at both ends.  A trace's size, and its cost to the host, grow
    with its length; a fixed part keeps both bounded."""

    def __init__(self, jax, server, trace_dir, t0, seconds):
        super().__init__(name="chipbench-tracer", daemon=True)
        self.jax, self.server, self.dir = jax, server, trace_dir
        if seconds >= TRACE_START_S + TRACE_S:
            self.ts = t0 + TRACE_START_S
            self.te = self.ts + TRACE_S
        else:
            self.ts, self.te = t0, t0 + seconds
        self.seconds = self.te - self.ts
        shutil.rmtree(trace_dir, ignore_errors=True)
        self.start()

    def run(self):
        jax = self.jax
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        time.sleep(max(self.ts - time.monotonic(), 0.0))
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.stats0 = bucket_stats(self.server)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            time.sleep(max(self.te - time.monotonic(), 0.0))
        self.stats1 = bucket_stats(self.server)
        jax.profiler.stop_trace()


def run_window(s: Setup, cell: Cell, seed: int, seconds: float,
               trace: bool, t_start: float) -> Window:
    """Warm the running server with one batch, then measure for
    ``seconds``; answers due in the window are awaited past its close."""
    jax, server, pool = s.jax, s.server, s.pool
    traffic = cell.traffic
    rng = np.random.default_rng([int(seed) % 2 ** 64, 3])
    order = rng.permutation(pool.shape[0])
    closed = traffic["loop"] == "closed"
    offsets = None if closed else loadgen.open_schedule(traffic, seconds,
                                                        seed)
    rec = Record(4096 if closed else offsets.size)
    keeper = Keeper(seed)
    if trace:
        annotate = jax.profiler.TraceAnnotation
    else:
        import contextlib
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    bounds = {}

    with server:
        warm = [server.submit(s.model_name, pool[i % pool.shape[0]],
                              precision=cell.precision)
                for i in range(s.target)]
        for r in warm:
            r.result(timeout=600)
        del warm
        stamp("served one batch through the running server")
        if closed:
            start, threads = drive_closed(server, s.model_name, pool, order,
                                          cell, bounds, rec, keeper,
                                          annotate)
        # What set-up left behind is not collected inside the window.
        gc.collect()
        gc.freeze()
        gc_watch = GcWatch()
        freeze_watch = FreezeWatch()
        stats0 = bucket_stats(server)
        t0 = time.monotonic()
        t1 = t0 + seconds
        bounds.update(t0=t0, t1=t1)
        setup_s = time.perf_counter() - t_start
        compiles0 = Compiles.snapshot()
        tracer = None
        if trace:
            tracer = _Tracer(jax, server,
                             STATE_DIR / "trace" / f"{cell.name}.{seed}",
                             t0, seconds)
        if closed:
            start.wait()
        else:
            finished, collector = drive_open(
                server, s.model_name, pool, order, cell, offsets, t0, rec,
                keeper, annotate)
        time.sleep(max(t1 - time.monotonic(), 0.0))
        stats1 = bucket_stats(server)
        t_close = time.monotonic()
        compiles1 = Compiles.snapshot()
        gc_window = gc_watch.stop()
        freezes = freeze_watch.stop()
        stamp(f"window closed after {t_close - t0:.3f} s")
        if tracer is not None:
            tracer.join()
            stamp("trace written")
        if closed:
            for t in threads:
                t.join(timeout=COLLECT_GRACE_S + seconds)
        else:
            finished.set()
            collector.join(timeout=COLLECT_GRACE_S)
        give_up = time.monotonic()
        gc.unfreeze()
    stats2 = bucket_stats(server)
    stamp(f"answers settled {give_up - t_close:.3f} s after the close")
    n_sent = (int(np.sum(np.isfinite(rec.sent))) if closed
              else offsets.size)
    return Window(rec=rec, keeper=keeper, closed=closed, n_sent=n_sent,
                  t0=t0, t1=t1, t_close=t_close, give_up=give_up,
                  stats0=stats0, stats1=stats1, stats2=stats2,
                  setup_s=setup_s, tracer=tracer, compiles0=compiles0,
                  compiles1=compiles1, gc=gc_window, freezes=freezes)


def end_to_end(w: Window) -> dict:
    n = w.n_sent
    rec = w.rec
    due, done, ok = rec.due[:n], rec.done[:n], rec.ok[:n]
    lat = loadgen.latencies(due, done, ok, w.give_up)
    return {
        "images_per_s": loadgen.window_rate(done, ok, w.t0, w.t1),
        "latency_p50_ms": 1e3 * loadgen.quantile(lat, 0.50),
        "latency_p95_ms": 1e3 * loadgen.quantile(lat, 0.95),
        "setup_s": w.setup_s,
    }


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_device: bool = True,
             compile_cache: bool = True, controls=None,
             keep_trace: bool = False, server_hook=None):
    """One run: ``(result, extra)``.  ``result`` is the object the
    benchmark prints (the driver's keys, then ``checks``); ``extra``
    holds diagnostics.  ``controls`` maps names to ``plain.Prec`` changes
    whose readings are returned in ``extra`` (the control runs only);
    ``server_hook(server)`` lets a test break the timed path."""
    s = setup(cell, seed, require_device=require_device,
              compile_cache=compile_cache, server_hook=server_hook)
    w = run_window(s, cell, seed, seconds, trace, t_start)
    target = s.target

    memory_peak = None
    try:
        memory_peak = int(s.jax.devices()[0].memory_stats()
                          ["peak_bytes_in_use"])
    except Exception:  # noqa: BLE001 — not every backend reports it
        pass

    n = w.n_sent
    e2e = end_to_end(w)
    ok, done = w.rec.ok[:n], w.rec.done[:n]
    failed = int(np.sum(~(ok & np.isfinite(done))))
    degraded = int(w.stats2["degraded"] - w.stats0["degraded"])

    # Free the program's state before the reference runs.
    s.server = s.runner = None
    gc.collect()
    batches = served_batches(w.rec, w.keeper, n)
    pick = np.random.default_rng([int(seed) % 2 ** 64, 11])
    if len(batches) > 2 * KEEP_BLOCKS:
        idx = np.sort(pick.choice(len(batches), 2 * KEEP_BLOCKS,
                                  replace=False))
        batches = [batches[i] for i in idx]
    precs = {"reference": reference_prec(cell)}
    for name, changes in (controls or {}).items():
        precs[name] = reference_prec(cell, **changes)
    t_check = time.perf_counter()
    readings = compare(cell, s.params, batches, s.pool, w.rec, w.keeper,
                       precs) if batches else {}
    check_s = time.perf_counter() - t_check
    stamp(f"check of {len(batches)} batches took {check_s:.2f} s")
    served = readings.get("served", {})
    checks = {name: {"value": served.get(name, math.inf),
                     "limit": float(limit)}
              for name, limit in cell.limits["limits"].items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["degraded_batches"] = {"value": degraded, "limit": 0}
    min_batches = int(cell.limits["min_batches"])
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and len(batches) >= min_batches)
    checks["batches_compared"] = {"value": len(batches),
                                  "limit": f">= {min_batches}"}

    tr = None
    if trace:
        from chipbench import trace as trace_mod

        tw = w.tracer
        size = sum(f.stat().st_size for f in tw.dir.rglob("*")
                   if f.is_file()) if tw.dir.exists() else 0
        tr = trace_mod.reduce_dir(tw.dir) if size else {}
        stamp(f"trace of {size / 2 ** 20:.1f} MiB reduced")
        if not keep_trace:
            shutil.rmtree(tw.dir, ignore_errors=True)
        ctx = Context(
            trace=tr, stats0=tw.stats0, stats1=tw.stats1, peaks=s.peaks,
            precision=cell.precision, layers=cell.layers,
            target_batch=target,
            per_image_ops=2.0 * counts.model_counts(cell.layers)["total"],
            lateness_s=None if w.closed else loadgen.lateness(
                w.rec.due[:n], w.rec.sent[:n]))
        metrics = read_per_layer(cell, ctx)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.e2e}

    device = dict(s.device)
    device["memory_peak_bytes"] = memory_peak
    if tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    result = {"correct": bool(correct), "attempted": n,
              "failed": failed + degraded * target, "metrics": metrics,
              "device": device}
    if tr:
        result["breakdown"] = tr["breakdown"]
    result["checks"] = checks
    extra = {"end_to_end": e2e, "window_s": w.t1 - w.t0,
             "close_late_s": w.t_close - w.t1,
             "settle_s": w.give_up - w.t_close, "check_s": check_s,
             "batches": int(w.stats1["batches"] - w.stats0["batches"]),
             "setup_compiles": w.compiles0,
             "window_compiles": Compiles.delta(w.compiles0, w.compiles1),
             "gc": w.gc,
             "freezes": w.freezes,
             "stall_s": longest_stall(done, w.t0, w.t1),
             "readings": readings}
    if tr:
        extra["trace"] = {k: v for k, v in tr.items() if k != "breakdown"}
    return result, extra
