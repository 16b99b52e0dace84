"""From a profiler trace (`.xplane.pb`) to what the per-layer readers use.

Two steps, so that the arithmetic is testable on a small recorded file:

`program_trace.load(path)` reads the trace ONCE into plain data:
    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns, ...], ...]}]}]}
(a fourth element, a span's attributes or an operation's `op_name`, is
`program_trace`'s to read and is stepped over here);
`reduce(raw)` turns that into the reduced trace:
    window_s      length of the traced part (the `bench.traced_window`
                  annotation; without it, the extent of the device events)
    devices       per device plane: busy_s (union of the intervals in which
                  an operation ran, inside the window), modules {name:
                  {"seconds", "count"}} from the XLA module line, ops
                  [[name, seconds], ...] and gaps [[start_s, length_s], ...]
                  (idle stretches, longest first)
    host_spans    [[name, start_s, end_s], ...] of the harness's own
                  `bench.*` annotations, on the same clock, window-relative
On a TPU the device planes are `/device:TPU:<n>`; their `XLA Ops` line
holds one event per executed operation and `XLA Modules` one per executed
program (`jit_<function>(<fingerprint>)`).
"""
from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
WINDOW_SPAN = "bench.traced_window"


def short_name(event_name: str) -> str:
    """An XLA op's event is named by its whole HLO line (`%fusion.218 =
    bf16[50304,2048]{...} fusion(...), kind=kOutput, calls=...`): keep the
    op's own name and the fusion kind."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name
    kind = re.search(r"kind=(k\w+)", rest)
    return head.lstrip("%") + (f" ({kind.group(1)})" if kind else "")


def module_name(event_name: str) -> str:
    """`jit_pure_burst(123456789)` -> `jit_pure_burst`."""
    return event_name.split("(")[0]


def _union(intervals):
    """Merged [start, end] intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(events, lo, hi):
    for name, start, dur, *_ in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def reduce(raw: dict) -> dict:
    device_planes = [p for p in raw["planes"]
                     if DEVICE_PLANE.match(p["name"])]
    host_events = [ev for p in raw["planes"] if p not in device_planes
                   for line in p["lines"] for ev in line["events"]]
    window = [ev for ev in host_events if ev[0] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][1], window[0][1] + window[0][2]
    else:
        spans = [(ev[1], ev[1] + ev[2]) for p in device_planes
                 for line in p["lines"] for ev in line["events"]]
        if not spans:
            return {"window_s": 0.0, "devices": [], "host_spans": []}
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    devices = []
    for plane in device_planes:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = list(_clip(lines.get(OP_LINE) or lines.get(MODULE_LINE, []),
                         lo, hi))
        busy = _union([a, b] for _, a, b in ops)
        by_op, modules = {}, {}
        for name, a, b in ops:
            by_op[name] = by_op.get(name, 0) + (b - a)
        for name, a, b in _clip(lines.get(MODULE_LINE, []), lo, hi):
            m = modules.setdefault(module_name(name),
                                   {"seconds": 0.0, "count": 0})
            m["seconds"] += (b - a) / 1e9
            m["count"] += 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = sorted(([(edges[i] - lo) / 1e9,
                        (edges[i + 1] - edges[i]) / 1e9]
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]),
                      key=lambda g: -g[1])
        devices.append({
            "name": plane["name"],
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "modules": modules,
            "ops": sorted(([n, s / 1e9] for n, s in by_op.items()),
                          key=lambda o: -o[1])[:40],
            "gaps": gaps,
        })
    host_spans = sorted(
        [name[len("bench."):], (a - lo) / 1e9, (b - lo) / 1e9]
        for name, a, b in _clip(host_events, lo, hi)
        if name.startswith("bench.") and name != WINDOW_SPAN)
    return {"window_s": (hi - lo) / 1e9, "devices": devices,
            "host_spans": host_spans}


def busy_seconds(reduced: dict) -> float:
    """Device-busy seconds averaged over the device planes."""
    devices = reduced["devices"]
    return sum(d["busy_s"] for d in devices) / len(devices) if devices \
        else 0.0


def module_seconds(reduced: dict, pattern: str) -> tuple:
    """(seconds, executions) of the modules whose name matches `pattern`,
    on the busiest device."""
    best = (0.0, 0)
    for d in reduced["devices"]:
        secs = sum(m["seconds"] for n, m in d["modules"].items()
                   if re.search(pattern, n))
        count = sum(m["count"] for n, m in d["modules"].items()
                    if re.search(pattern, n))
        best = max(best, (secs, count))
    return best


def attribute_gaps(reduced: dict, charged=None, stretches=None,
                   top=10) -> list:
    """[[what the host was doing, idle seconds], ...], longest first.
    `charged` is idle time that the caller has a name for already ({name:
    seconds}; none by default); `stretches` are the idle stretches
    [[start_s, length_s], ...] left to name here (by default every gap of
    the first device). Each stretch goes whole to the harness span that
    covers its middle: the latest-starting one, `(none)` where no span
    does."""
    if not reduced["devices"]:
        return []
    charged = dict(charged or {})
    if stretches is None:
        stretches = reduced["devices"][0]["gaps"]
    spans = sorted(reduced["host_spans"], key=lambda s: s[1])
    starts = [s[1] for s in spans]
    for start, length in stretches:
        mid = start + length / 2
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and spans[i][2] <= mid:
            i -= 1
        name = spans[i][0] if i >= 0 else "(none)"
        charged[name] = charged.get(name, 0.0) + length
    return sorted(([n, s] for n, s in charged.items()),
                  key=lambda g: -g[1])[:top]


def breakdown(reduced: dict, charged=None, stretches=None) -> dict:
    """The result line's `breakdown`; `charged` and `stretches` as
    `attribute_gaps` takes them."""
    ops = reduced["devices"][0]["ops"][:10] if reduced["devices"] else []
    return {"device_ops": ops,
            "idle_gaps": attribute_gaps(reduced, charged, stretches)}
