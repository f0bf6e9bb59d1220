"""Reduce a profiler trace of one run to the numbers the benchmark reads.

The window is the benchmark's own ``bench.window`` host span.  Device
busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), clipped
to the window and averaged over the devices; the idle share is 1 minus
busy over the window.  Program time is the sum of a device's ``XLA
Modules`` events by module name.  Each idle gap is labelled by the
innermost ``bench.`` host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(log_dir: str) -> None:
    """Start the profiler without its Python tracer: the benchmark's
    own ``TraceAnnotation`` spans come through the host tracer, and
    tracing every Python call would slow the host it measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals) -> list:
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(merged, lo, hi) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in merged
            if b > lo and a < hi]


def module_name(name: str) -> str:
    """A module event's name without the program id the trace appends
    (``jit_serve_cross(42)`` -> ``jit_serve_cross``)."""
    return name.split("(")[0].strip()


def reduce_events(device_lines: dict, host_spans: list) -> dict:
    """The reduction on plain data, so that it can be tested without a
    trace file.

    ``device_lines``: {device: {line name: [(name, start_ns, dur_ns)]}};
    ``host_spans``: [(name, start_ns, dur_ns)] of the host threads.
    Returns busy_s and window_s (busy averaged over the devices),
    per-module and per-op device seconds, and the idle gaps longest
    first as (label, seconds)."""
    windows = [(s, s + d) for n, s, d in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo = min(a for a, _ in windows)
    hi = max(b for _, b in windows)
    bench_spans = [(s, s + d, n) for n, s, d in host_spans
                   if n.startswith("bench.") and n != WINDOW]
    busy = 0.0
    modules: dict = defaultdict(float)
    ops: dict = defaultdict(float)
    gaps: list = []
    for lines in device_lines.values():
        op_events = lines.get(OPS_LINE, [])
        merged = _clip(_union((s, s + d) for _, s, d in op_events), lo, hi)
        busy += sum(b - a for a, b in merged)
        for name, s, d in op_events:
            if s >= lo and s + d <= hi:
                ops[name] += d * 1e-9
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= lo and s + d <= hi:
                modules[module_name(name)] += d * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                cover = [(e - s, n) for s, e, n in bench_spans
                         if s <= mid <= e]
                label = min(cover)[1] if cover else "outside bench spans"
                gaps.append((label, (b - a) * 1e-9))
    ndev = max(1, len(device_lines))
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy * 1e-9 / ndev, "window_s": (hi - lo) * 1e-9,
            "devices": len(device_lines), "modules": dict(modules),
            "ops": dict(ops), "idle_gaps": gaps}


def load(xplane_path: str) -> tuple[dict, list]:
    """(device_lines, host_spans) of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    device_lines: dict = {}
    host_spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_lines[plane.name] = {
                line.name: [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events
                               if e.name.startswith("bench.")]
    return device_lines, host_spans


def reduce_trace_file(xplane_path: str) -> dict:
    return reduce_events(*load(xplane_path))


def reduce_trace(log_dir: str) -> dict:
    return reduce_trace_file(find_xplane(log_dir))


#: characters kept of an op's name: its HLO instruction and result shape
NAME_CHARS = 120


def breakdown(red: dict, n: int = 10) -> dict:
    """The result line's ``breakdown``: the programs and then the ops
    that took the most device time (at most ``n`` entries in all), and
    the ``n`` longest idle gaps by what the host was doing."""
    mods = sorted(red["modules"].items(), key=lambda kv: -kv[1])[:n // 2]
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k[:NAME_CHARS], v] for k, v in
                           (mods + ops)[:n]],
            "idle_gaps": [[k, v] for k, v in red["idle_gaps"][:n]]}
