"""From a profiler trace to numbers: device busy time, time by operation
and by program, and the idle gaps.

``load`` turns jax's ``ProfileData`` into plain tuples; ``reduce`` works on
those alone, so a test can hand it a synthetic trace.  Times are
nanoseconds on the profiler's own clock.  The harness brackets the steady
part of the trace with two ``TraceAnnotation`` markers; only what lies
between them counts, so that starting and stopping the profiler (which
takes seconds and stalls the host) is outside the window.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MARK_BEGIN, MARK_END = "bench_window_begin", "bench_window_end"


def load(trace_dir: str) -> list:
    """[(plane name, [(line name, [(event name, start_ns, duration_ns)])])]
    of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    return [(plane.name,
             [(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events])
              for line in plane.lines])
            for plane in data.planes]


def merge(intervals: list) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(events: list, t0: float, t1: float) -> list:
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b))
    return out


def markers(planes: list) -> tuple:
    """(begin, end) of the bracketed window, or None where a marker is
    missing."""
    found = {}
    for name, lines in planes:
        if name.startswith("/device:"):
            continue
        for _line, events in lines:
            for ename, start, _dur in events:
                if ename in (MARK_BEGIN, MARK_END):
                    found[ename] = start
    if MARK_BEGIN in found and MARK_END in found:
        return found[MARK_BEGIN], found[MARK_END]
    return None


def reduce(planes: list) -> dict:
    """{"window_s", "busy_s", "busy_any_s", "devices", "ops": {name: s},
    "modules": {name: s}, "gaps": [(start_ns, end_ns)], "t0_ns"}: a
    device's busy time is the union of its operation intervals; ``busy_s``
    is their mean over the device planes, ``busy_any_s`` the union over
    all of them.  Operations and programs are summed over the planes.  A
    gap is a stretch of the window in which no device runs an operation.
    Returns {} when no device plane holds an operation: there is then
    nothing to read."""
    window = markers(planes)
    devices = []                         # (lines by name, events)
    for name, lines in planes:
        if not name.startswith(DEVICE_PREFIX):
            continue
        by_line = dict(lines)
        events = by_line.get(OPS_LINE) or by_line.get(MODULES_LINE) or []
        if events:
            devices.append((by_line, events))
    if window is None and devices:
        window = (min(s for _, ev in devices for _, s, _ in ev),
                  max(s + d for _, ev in devices for _, s, d in ev))
    per_device, ops, modules, every = [], {}, {}, []
    for by_line, events in devices:
        t0, t1 = window
        clipped = _clip(events, t0, t1)
        busy = merge([(a, b) for _, a, b in clipped])
        per_device.append(sum(b - a for a, b in busy))
        every.extend(busy)
        for ename, a, b in clipped:
            ops[ename] = ops.get(ename, 0.0) + (b - a) / 1e9
        for ename, a, b in _clip(by_line.get(MODULES_LINE, []), t0, t1):
            modules[ename] = modules.get(ename, 0.0) + (b - a) / 1e9
    if not per_device or sum(per_device) <= 0:
        return {}
    union = merge(every)
    edges = [window[0]] + [x for ab in union for x in ab] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return {"window_s": (window[1] - window[0]) / 1e9,
            "busy_s": sum(per_device) / len(per_device) / 1e9,
            "busy_any_s": sum(b - a for a, b in union) / 1e9,
            "devices": len(per_device), "ops": ops, "modules": modules,
            "gaps": gaps, "t0_ns": window[0]}


def kernel_seconds(summary: dict, match: str = "") -> float:
    """Seconds of device operations inside programs whose name contains
    ``match`` (every operation when it is empty), summed over the devices.
    Where the trace has no line of programs, the operations' own names are
    matched."""
    if not match:                    # each device's union: nested ops once
        return summary["busy_s"] * summary["devices"]
    table = summary["modules"] or summary["ops"]
    return sum(s for name, s in table.items() if match in name)


def top(table: dict, n: int = 10, width: int = 120) -> list:
    """The ``n`` largest entries; a name is cut to ``width`` characters
    (the trace names an operation by its whole HLO line)."""
    return [[name[:width], s] for name, s in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


SHORT_GAP_NS = 20_000.0
SHORT_GAPS = "gaps under 20 us, between operations of one program"


def gap_breakdown(summary: dict, label) -> list:
    """The idle gaps summed by ``label(start_ns, end_ns)`` (what the host
    was doing meanwhile), the ten largest.  A trace holds tens of
    thousands of gaps of a microsecond between a program's operations:
    those are summed under one name and not looked up."""
    table = {}
    for a, b in summary["gaps"]:
        key = label(a, b) if b - a >= SHORT_GAP_NS else SHORT_GAPS
        table[key] = table.get(key, 0.0) + (b - a) / 1e9
    return top(table)
