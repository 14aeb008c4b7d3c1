"""Reduce a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: the traced window, the device's busy time inside it (the union of
its operations' intervals, averaged over the chips), device time per program
and per operation, and the idle gaps, each labelled by the host annotation
it falls in.

The traced window is the host annotation ``bench.window`` that the harness
places around the first ``trace_seconds`` of the measured window;
``bench.batch`` marks the service's batch entry.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os

WINDOW = "bench.window"
BATCH = "bench.batch"


def _device_planes(planes):
    return [lines for name, lines in planes
            if name.startswith("/device:TPU:")
            and name[len("/device:TPU:"):].isdigit()]


def _line(lines, name):
    for ln in lines:
        if ln.name == name:
            return ln
    return None


def short_name(op: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the part
    before `` = `` (``%fusion.744``)."""
    return op.split(" = ", 1)[0]


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _label(a, b, batches, starts):
    """Split the idle interval [a, b) at the edges of the batch annotations
    (merged, sorted; ``starts`` their starts): ``(label, seconds)`` for each
    part."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    x = a
    while x < b:
        while i < len(batches) and batches[i][1] <= x:
            i += 1
        inside = i < len(batches) and batches[i][0] <= x
        y = min(b, batches[i][1] if inside else
                batches[i][0] if i < len(batches) else b)
        yield ("host in service batch" if inside
               else "host outside any batch", (y - x) * 1e-9)
        x = y


def reduce_planes(planes, top: int = 10) -> dict:
    """The reduction itself, over already-parsed planes (anything with
    ``name``/``lines``/``events``/``start_ns``/``duration_ns``). The
    profiler's ``planes`` can be walked only once, so each plane's lines are
    read into a list here."""
    planes = [(p.name, list(p.lines)) for p in planes]
    host_events = [e for name, lines in planes if name.startswith("/host:")
                   for ln in lines for e in ln.events]
    windows = [(e.start_ns, e.start_ns + e.duration_ns) for e in host_events
               if e.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = windows[0]
    batches = _union(_clip([(e.start_ns, e.start_ns + e.duration_ns)
                            for e in host_events if e.name == BATCH], w0, w1))
    starts = [p for p, _ in batches]
    devices = _device_planes(planes)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_ns = 0.0
    ops: dict = {}
    modules: dict = {}
    whole: dict = {}
    gaps: list = []
    for lines in devices:
        op_line = _line(lines, "XLA Ops")
        spans = []
        # one pass over each event: a scanned search puts every step's
        # operations in the trace, millions of events in a window
        for ln in ([op_line] if op_line is not None else lines):
            for e in ln.events:
                a = e.start_ns
                b = min(a + e.duration_ns, w1)
                a = max(a, w0)
                if b > a:
                    spans.append((a, b))
                    k = short_name(e.name)
                    ops[k] = ops.get(k, 0.0) + (b - a)
        busy = _union(spans)
        busy_ns += sum(b - a for a, b in busy)
        mod_line = _line(lines, "XLA Modules")
        for e in (mod_line.events if mod_line is not None else ()):
            a, b = e.start_ns, e.start_ns + e.duration_ns
            d = min(b, w1) - max(a, w0)
            if d > 0:
                modules[e.name] = modules.get(e.name, 0.0) + d
            if a >= w0 and b <= w1:
                n, t = whole.get(e.name, (0, 0.0))
                whole[e.name] = (n + 1, t + (b - a))
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        parts = (part for a, b in zip(edges[::2], edges[1::2])
                 for part in _label(a, b, batches, starts))
        gaps = heapq.nlargest(top, gaps + list(heapq.nlargest(
            top, parts, key=lambda g: g[1])), key=lambda g: g[1])
    n = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "chips": n,
        "modules_s": {k: v * 1e-9 / n for k, v in modules.items()},
        # executions that ran wholly inside the window: count, seconds
        "modules_whole": {k: (c, t * 1e-9 / n)
                          for k, (c, t) in whole.items()},
        "device_ops": [[k, v * 1e-9 / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, s] for label, s in gaps[:top]],
    }


def reduce_file(path: str, top: int = 10) -> dict:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         top)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]
