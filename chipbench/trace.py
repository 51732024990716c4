"""Reduce one profiler trace (``*.xplane.pb``) to device busy time,
TCONV kernel time and the ``breakdown`` of a run.

Only what lies inside the benchmark's window annotation counts.  Device
operations are the events of the ops line (``kernels.json``:
``ops_line``) on each device plane (``device_plane_prefix``); busy time
is the union of their intervals, averaged over the devices.  A TCONV
kernel event is one whose name, or ``long_name`` stat, contains every
one of ``tconv_kernel_patterns``.  Idle gaps are the holes in the busy union of
the first device; each is put down to the host event that overlaps it
most, on any host thread, the clients' waits only where nothing else
does.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "chipbench.window"
# Host spans that cover whole waits rather than work; a gap is put down to
# them only when nothing more specific overlaps it.
BACKGROUND_SPANS = ("chipbench.client_wait",)
KERNELS_FILE = Path(__file__).resolve().parent / "kernels.json"


def kernel_spec(path=KERNELS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def find_xplane(log_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _stat(event, name):
    try:
        for k, v in event.stats:
            if k == name:
                return v
    except Exception:  # noqa: BLE001 — stats are optional per event
        return None
    return None


class _Spans:
    """Host spans sorted by start, to find the one that overlaps an
    interval most without scanning them all."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [h[0] for h in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0.0)

    def most_overlap(self, g0, g1):
        best = None
        lo = bisect.bisect_left(self.starts, g0 - self.longest)
        hi = bisect.bisect_right(self.starts, g1)
        for s, e, n in self.spans[lo:hi]:
            ov = min(e, g1) - max(s, g0)
            if ov > 0 and (best is None or ov > best[0]):
                best = (ov, n)
        return best[1] if best else None


def load_events(path):
    """(host_events, device_events_by_plane) from an xplane file, each
    event a ``(name, long_name, start_ns, end_ns)`` tuple; host events
    carry their thread name in place of ``long_name``."""
    from jax.profiler import ProfileData

    spec = kernel_spec()
    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith(spec["device_plane_prefix"]):
            evs = []
            for line in plane.lines:
                if line.name != spec["ops_line"]:
                    continue
                for e in line.events:
                    start = float(e.start_ns)
                    evs.append((e.name, _stat(e, "long_name") or "", start,
                                start + float(e.duration_ns)))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    start = float(e.start_ns)
                    host.append((e.name, line.name, start,
                                 start + float(e.duration_ns)))
    return host, devices


def reduce_events(host, devices, patterns, top: int = 10) -> dict:
    """The numbers a run reports from its trace; see the module doc."""
    win = [e for e in host if e[0] == WINDOW_SPAN]
    if not win or not devices:
        return {}
    w0, w1 = win[0][2], win[0][3]
    window_s = (w1 - w0) * 1e-9

    busy, kernel_s, kernel_n, ops = [], 0.0, 0, defaultdict(float)
    first = None
    for plane in sorted(devices):
        clipped = [(n, ln, max(s, w0), min(e, w1))
                   for n, ln, s, e in devices[plane] if e > w0 and s < w1]
        merged = _union([(s, e) for _, _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if first is None:
            first = merged
        for name, long_name, s, e in clipped:
            ops[name] += (e - s) * 1e-9
            if all(p in name or p in long_name for p in patterns):
                kernel_s += (e - s) * 1e-9
                kernel_n += 1
    busy_s = sum(busy) / len(busy)
    kernel_s /= len(busy)

    gaps = []
    edge = w0
    for s, e in first + [[w1, w1]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    inside = [(s, e, n) for n, _, s, e in host
              if e > w0 and s < w1 and n != WINDOW_SPAN]
    specific = _Spans(h for h in inside if h[2] not in BACKGROUND_SPANS)
    background = _Spans(h for h in inside if h[2] in BACKGROUND_SPANS)
    idle = defaultdict(float)
    for g0, g1 in gaps:
        name = specific.most_overlap(g0, g1) or \
            background.most_overlap(g0, g1) or "host idle"
        idle[name] += (g1 - g0) * 1e-9

    def top_items(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {"window_s": window_s, "busy_s": busy_s, "kernel_s": kernel_s,
            "kernel_events": kernel_n, "n_devices": len(busy),
            "breakdown": {"device_ops": top_items(ops),
                          "idle_gaps": top_items(idle)}}


def reduce_dir(log_dir, patterns=None) -> dict:
    path = find_xplane(log_dir)
    host, devices = load_events(path)
    if patterns is None:
        patterns = kernel_spec()["tconv_kernel_patterns"]
    return reduce_events(host, devices, patterns)
