"""Device time under a model's FINER scopes (`mlp/experts`, `attn/latent`)
and the counts a burst hands to `serving.emit`.

`program_trace.scope_seconds` sums an operation's self time by the
top-level scope in its `op_name` (`tracing.SCOPES`). A model that names
parts of a scope (`attn/kv_write`, `attn/latent`, `mlp/router`,
`mlp/experts`, `mlp/shared`) puts the finer name right behind the
top-level one, and this module sums by that pair, the same way: the same
file, window, nesting (`program_trace.nest`) and module line. A trace
whose operations carry no such name (another model's, an older commit's)
gives no value: every reader then returns None.
"""
from __future__ import annotations

import bisect
import functools
import os
import re

from benchmark import program_trace, trace_reduce


def path_of(op_name: str):
    """`.../mlp/experts/dot_general` -> `mlp/experts`: the top-level scope
    and the name right behind it; None where there is no top-level scope
    or nothing named follows it."""
    parts = op_name.split(";")[0].split("/")
    for i, part in enumerate(parts[:-1]):
        m = program_trace._WRAPPED.match(part)
        if m and m.group(1) in program_trace.SCOPES:
            nxt = program_trace._WRAPPED.match(parts[i + 1])
            return f"{m.group(1)}/{nxt.group(1)}" if nxt else None
    return None


def path_seconds(raw: dict) -> dict:
    """{module: {path: seconds of self time}} inside the traced window, of
    the first device, from `program_trace.load`'s data."""
    device_planes = [p for p in raw["planes"]
                     if trace_reduce.DEVICE_PLANE.match(p["name"])]
    window = [ev for p in raw["planes"] if p not in device_planes
              for ln in p["lines"] for ev in ln["events"]
              if ev[0] == trace_reduce.WINDOW_SPAN]
    if not window or not device_planes:
        return {}
    lo, hi = window[0][1], window[0][1] + window[0][2]
    lines = {ln["name"]: ln["events"] for ln in device_planes[0]["lines"]}
    modules = sorted((ev[1], ev[1] + ev[2], trace_reduce.module_name(ev[0]))
                     for ev in lines.get(trace_reduce.MODULE_LINE, []))
    starts = [m[0] for m in modules]
    clipped = [(max(ev[1], lo), min(ev[1] + ev[2], hi), ev)
               for ev in lines.get(trace_reduce.OP_LINE, [])
               if min(ev[1] + ev[2], hi) > max(ev[1], lo)]
    seconds = {}
    for start, _end, ev, _depth, self_ns in program_trace.nest(clipped):
        i = bisect.bisect_right(starts, start) - 1
        path = path_of(ev[3]) if len(ev) > 3 and ev[3] else None
        if path is None or i < 0 or modules[i][1] <= start:
            continue
        per = seconds.setdefault(modules[i][2], {})
        per[path] = per.get(path, 0.0) + self_ns / 1e9
    return seconds


@functools.lru_cache(maxsize=2)
def _paths_of(path, _mtime):
    return path_seconds(program_trace.load(path))


def path_ms(reduced, module_pattern, path, steps=1, directory=None):
    """Milliseconds of `path` per step inside the modules that match
    (`steps` a module execution) in the newest traced run; None where the
    reader was handed no trace, no run left a file, or no operation of
    those modules carries the path."""
    if not reduced or not reduced.get("devices"):
        return None
    file = program_trace.newest(directory)
    if not file:
        return None
    found = [v for m, v in _paths_of(file, os.path.getmtime(file)).items()
             if re.search(module_pattern, m)]
    _, runs = trace_reduce.module_seconds(reduced, module_pattern)
    if not runs or not any(path in v for v in found):
        return None
    return 1e3 * sum(v.get(path, 0.0) for v in found) / (runs * steps)


def emit_ratio(reduced, over, under):
    """sum(`over`) / sum(`under`) of the attributes a model's burst put on
    its `serving.emit` phases in the newest traced run (the program's own
    counts of its routing); None where no phase carries `under`."""
    emits = [a for a in program_trace.marks(program_trace.current(reduced),
                                            "serving.emit") if a.get(under)]
    if not emits:
        return None
    return sum(a[over] for a in emits) / sum(a[under] for a in emits)
