"""Reduction of a profiler trace to device busy time, module times and gaps.

A trace is a list of planes ``{"name", "lines": [{"name", "events": [(name,
start_ns, duration_ns), ...]}]}``, as ``load_xplane`` reads it from the
``.xplane.pb`` that ``jax.profiler`` writes.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation and
their ``XLA Modules`` line one event per executed program (``jit_<fn>(<id>)``).
Host planes carry the benchmark's ``TraceAnnotation`` spans on the same
clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_LABELS = ("bench.save", "bench.failure", "bench.step", "bench.batch",
               "bench.eval")


def find_xplane(directory):
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load_xplane(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [{"name": pl.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]}
                       for ln in pl.lines]}
            for pl in data.planes]


def is_device_plane(name):
    return re.match(r"^/device:TPU:\d+$", name) is not None


def op_name(name):
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def module_base(name):
    """``jit_step(123)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def merge(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def host_spans(planes):
    """[(name, start, end)] of every event on host planes."""
    out = []
    for pl in planes:
        if is_device_plane(pl["name"]):
            continue
        for ln in pl["lines"]:
            out.extend((n, s, s + d) for n, s, d in ln["events"])
    return out


def reduce_trace(planes, window=WINDOW, labels=HOST_LABELS, top=10):
    """Window length, device busy seconds (mean over devices), per-module
    device time and count, the top device operations, and device idle time
    attributed to what the host was doing (the label span holding the gap's
    midpoint)."""
    spans = host_spans(planes)
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins:
        raise ValueError(f"no {window!r} span in the trace")
    lo, hi = max(wins, key=lambda w: w[1] - w[0])
    lab = sorted((s, e, n) for n, s, e in spans if n in labels)
    lab_starts = [s for s, _, _ in lab]
    devices = [pl for pl in planes if is_device_plane(pl["name"])]
    busy_total, modules, op_time, idle = 0.0, {}, {}, {}
    for pl in devices:
        ivs = []
        for n, s, d in _line(pl, OPS_LINE):
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                ivs.append((a, b))
                n = op_name(n)
                op_time[n] = op_time.get(n, 0.0) + (b - a)
        busy = merge(ivs)
        busy_total += sum(e - s for s, e in busy)
        for n, s, d in _line(pl, MODULES_LINE):
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                m = modules.setdefault(module_base(n), {"s": 0.0, "count": 0})
                m["s"] += (b - a) * 1e-9
                m["count"] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            i = bisect.bisect_right(lab_starts, mid) - 1
            name = "host:other"
            while i >= 0 and lab[i][0] <= mid:
                if lab[i][1] >= mid:
                    name = lab[i][2]
                    break
                i -= 1
                if i >= 0 and mid - lab[i][0] > 60e9:
                    break
            idle[name] = idle.get(name, 0.0) + (g1 - g0)
    n_dev = max(len(devices), 1)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "n_devices": len(devices),
        "modules": modules,
        "device_ops": [[n, t * 1e-9 / n_dev] for n, t in ops],
        "idle_gaps": [[n, t * 1e-9 / n_dev] for n, t in gaps],
    }
