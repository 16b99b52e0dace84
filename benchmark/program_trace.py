"""The program's own spans and scopes, read from the profiler's trace.

`trace_reduce.py` reads what the HARNESS wrote (`bench.*` annotations) and
the device's operations by name. This module reads what the PROGRAM says
of itself in the same `.xplane.pb`:

- its host phases: `jax.profiler.TraceAnnotation`s whose names start
  `serving.`, `train.` or `jit.` (paddle_tpu/observability/tracing.py
  `phase` / `mark`), with their attributes, on the device trace's clock;
- its device scopes: the `jax.named_scope` names (`SCOPES`) that the
  models put into each operation's `op_name`.

and gives the per-layer readers four things, all inside the
`bench.traced_window` annotation (`run.read_trace` parses the file once
and puts them beside the reduced trace, under `program`; a reader asks
`current(reduced)` for them):

`spans`          the program's spans and marks, nested by containment on
                 their thread: {"name", "start_s", "end_s", "attrs",
                 "depth", "line", "mark"}
`idle_by_span`   the first device's idle stretches charged to what the
                 host was doing: at every instant of a gap, the innermost
                 program span that covers it; `(outside)` where only a
                 harness span or nothing does. A gap that crosses several
                 phases is divided among them by overlap, so the values sum
                 to the device's idle time.
`scope_seconds`  per XLA module, the SELF time of each operation (its
                 duration less the operations that ran inside it, as a
                 scan's body does inside its `while`) summed by the
                 top-level scope in its `op_name`; a fusion is named by its
                 root's `op_name`, an operation the compiler made without
                 metadata has none and is booked to no scope.
`path_seconds`   the same self time by the scope and the name right behind
                 it (`mlp/experts`, `attn/latent`: `program_subscopes`),
                 from the same pass over the operations.

A trace of a program without phases or scopes (an older commit's) gives
empty spans and no scope: every reader then returns None. Nothing here
imports `paddle_tpu`; the names it shares with the program are the
constants below, pinned by tests on both sides.
"""
from __future__ import annotations

import bisect
import re

from benchmark import trace_reduce

PROGRAM_PREFIXES = ("serving.", "train.", "jit.")
HARNESS_PREFIX = "bench."
SCOPES = ("embed", "attn", "mlp", "head", "optimizer")
OUTSIDE = "(outside)"   # idle time inside no span of the program
_WRAPPED = re.compile(r"^(?:\w+\()*([\w.\-]+)\)*$")


def split_attrs(event_name: str, stats: dict):
    """(name, attrs): attributes arrive as the event's stats, or — when a
    tracer leaves TraceMe's encoding in place — as a `#k=v,k=v#` suffix of
    its name."""
    attrs = dict(stats)
    if event_name.endswith("#") and "#" in event_name[:-1]:
        event_name, _, encoded = event_name[:-1].partition("#")
        for pair in encoded.split(","):
            key, sep, value = pair.partition("=")
            if sep:
                attrs.setdefault(key, _number(value))
    return event_name, attrs


def _number(text):
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            pass
    return text


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of one protobuf message in buf[start:end]; a
    length-delimited value is its (start, end), never copied."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = (i, i + size), i + size
        else:  # fixed64 / fixed32
            value, i = None, i + (8 if wire == 1 else 4)
        yield key >> 3, value


def op_names(path: str) -> dict:
    """{(program id, event name): op_name} of the first device plane.

    An operation's `op_name` is in the trace, as the `tf_op` stat
    (`<op_name>:`) of the event's METADATA, which jaxlib 0.9.0's
    `ProfileData` does not hand out (its `event.stats` are the event's
    own: offset, duration, time scale). So this reads just that table
    from the file's protobuf wire format (xplane.proto: XSpace.planes=1;
    XPlane.name=2, event_metadata=4, stat_metadata=5; XEventMetadata
    .name=2, stats=5; XStat.metadata_id=1, uint64=3, int64=4, str=5;
    XStatMetadata.id=1, name=2), skipping the lines unread. The program
    id is the number in the module event's name.

    Those numbers and the two stat names are libtpu 0.0.34's. A device
    plane whose operations have metadata but no `tf_op` among it means
    they have moved: that raises, so that the scope metrics are not
    silently lost or booked to the wrong scope."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())

    def text(span):
        return bytes(buf[span[0]:span[1]]).decode()

    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for number, value in _fields(buf, *plane):
            if number == 2:
                name = text(value)
            elif number == 4:
                metadata.append(value)
            elif number == 5:
                entry = dict(_fields(buf, *value))
                stat = dict(_fields(buf, *entry[2]))
                stat_names[stat.get(1)] = text(stat[2]) if 2 in stat else ""
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        table = {}
        for entry in metadata:
            event = dict(_fields(buf, *entry)).get(2)
            if event is None:
                continue
            event_name, program, op_name = "", None, ""
            for number, value in _fields(buf, *event):
                if number == 2:
                    event_name = text(value)
                elif number == 5:
                    stat = dict(_fields(buf, *value))
                    kind = stat_names.get(stat.get(1))
                    if kind == "program_id":
                        program = stat.get(3, stat.get(4))
                    elif kind == "tf_op" and 5 in stat:
                        op_name = text(stat[5])
            if op_name:
                table[(program, event_name)] = op_name.rstrip(":")
        if metadata and not table:
            raise RuntimeError(
                f"{path}: none of the {len(metadata)} event metadata of "
                f"{name} carries a `tf_op` string: where this libtpu keeps "
                "an operation's op_name has to be read off a raw trace "
                "again (PERF.md section 3)")
        return table
    return {}


def _program_id(module_event_name: str):
    """`jit_pure_burst(11937236725742203718)` -> 11937236725742203718."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return int(m.group(1)) if m else None


def load(path: str) -> dict:
    """The trace as plain data, read with nothing but jax
    (`jax.profiler.ProfileData`) and `op_names`: the shape
    `trace_reduce.reduce` takes, with a fourth element per event: the
    attributes of a host span (the harness's `bench.*` and the program's
    own), or the `op_name` of a device operation ("" where the compiler
    gave it none)."""
    from jax.profiler import ProfileData

    names = op_names(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(trace_reduce.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (trace_reduce.MODULE_LINE,
                                            trace_reduce.OP_LINE):
                continue
            events = []
            for ev in line.events:
                if device:
                    events.append([ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)])
                elif ev.name.startswith(PROGRAM_PREFIXES
                                        + (HARNESS_PREFIX,)):
                    name, attrs = split_attrs(ev.name, dict(ev.stats))
                    events.append([name, int(ev.start_ns),
                                   int(ev.duration_ns), attrs])
            if events:
                lines.append({"name": line.name, "events": events})
        if device:
            _name_operations(lines, names)
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _name_operations(lines, names):
    """Give each operation its `op_name` (found by the program it ran in:
    the module event that covers its start) and shorten its name."""
    by_line = {ln["name"]: ln["events"] for ln in lines}
    modules = sorted((ev[1], ev[1] + ev[2], _program_id(ev[0]))
                     for ev in by_line.get(trace_reduce.MODULE_LINE, []))
    starts = [m[0] for m in modules]
    for ev in by_line.get(trace_reduce.OP_LINE, []):
        i = bisect.bisect_right(starts, ev[1]) - 1
        program = modules[i][2] if i >= 0 and ev[1] < modules[i][1] \
            else None
        ev.append(names.get((program, ev[0]), ""))
        ev[0] = trace_reduce.short_name(ev[0])


def scope_and_path(op_name: str):
    """(top-level scope, finer path) of an `op_name`. The scope is the
    first path component that is one of SCOPES, bare or inside a
    transform's name: `jit(pure_step)/transpose(jvp(attn))/dot_general` is
    `attn`, `.../while/body/closed_call/attn/kv_write/scatter` too. The
    path is the scope and the name right behind it
    (`.../mlp/experts/dot_general` -> `mlp/experts`); None where nothing
    named follows the scope."""
    parts = op_name.split(";")[0].split("/")
    for i, part in enumerate(parts):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            nxt = _WRAPPED.match(parts[i + 1]) if i + 1 < len(parts) \
                else None
            return m.group(1), \
                f"{m.group(1)}/{nxt.group(1)}" if nxt else None
    return None, None


def scope_of(op_name: str):
    return scope_and_path(op_name)[0]


def path_of(op_name: str):
    return scope_and_path(op_name)[1]


def nest(events):
    """[(start, end, payload)] -> [(start, end, payload, depth, self)],
    nested by containment: an event is the child of the latest-starting
    earlier event that has not ended when it starts."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out = [None] * len(events)
    stack = []  # indices into events
    child_time = [0] * len(events)
    for i in order:
        start, end, payload = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            child_time[parent] += min(end, events[parent][1]) - start
        out[i] = [start, end, payload, len(stack)]
        stack.append(i)
    return [tuple(row) + (max(0, row[1] - row[0] - child_time[i]),)
            for i, row in enumerate(out)]


def _innermost_segments(spans):
    """Disjoint (start, end, name) stretches covering the union of
    `spans` [(start, end, name)]: in each, the covering span that started
    last (the innermost, for spans nested on one thread)."""
    edges = sorted({t for s in spans for t in s[:2]})
    by_start = sorted(spans)
    segments, live, j = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while j < len(by_start) and by_start[j][0] <= a:
            live.append(by_start[j])
            j += 1
        live = [s for s in live if s[1] > a]
        if live:
            name = max(live, key=lambda s: (s[0], -s[1]))[2]
            if segments and segments[-1][2] == name \
                    and segments[-1][1] == a:
                segments[-1][1] = b
            else:
                segments.append([a, b, name])
    return segments


# a mark is a moment, though entering and leaving its annotation takes a
# microsecond: a span shorter than this charges no idle time of its own
MARK_NS = 5_000


def _host_spans(host, lo, hi):
    """The program's spans that overlap [lo, hi), clipped to it and nested
    per thread, in time order."""
    by_line = {}
    for line, ev in host:
        if ev[0].startswith(PROGRAM_PREFIXES) and ev[1] < hi \
                and ev[1] + ev[2] >= lo:
            by_line.setdefault(line, []).append(ev)
    spans = []
    for line, events in sorted(by_line.items()):
        for start, end, ev, depth, _ in nest(
                [(max(ev[1], lo), min(ev[1] + ev[2], hi), ev)
                 for ev in events]):
            spans.append({"name": ev[0], "start_s": (start - lo) / 1e9,
                          "end_s": (end - lo) / 1e9,
                          "attrs": ev[3] if len(ev) > 3 else {},
                          "depth": depth, "line": line,
                          "mark": ev[2] < MARK_NS})
    return sorted(spans, key=lambda s: (s["start_s"], s["depth"]))


def _charge(gaps, spans):
    """({span name: idle seconds}, [[start_s, length_s], ...]): each gap
    [start_s, length_s] divided among the innermost spans by overlap; the
    stretches no span covers are summed under OUTSIDE and returned."""
    segments = _innermost_segments(
        [(s["start_s"], s["end_s"], s["name"]) for s in spans
         if not s["mark"]])
    starts = [seg[0] for seg in segments]
    idle, outside = {}, []
    for g0, length in sorted(gaps):
        g1, at = g0 + length, g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            a, b, name = segments[i]
            lo, hi = max(a, g0), min(b, g1)
            if hi > lo:
                if lo > at:
                    outside.append([at, lo - at])
                idle[name] = idle.get(name, 0.0) + hi - lo
                at = hi
            i += 1
        if g1 > at:
            outside.append([at, g1 - at])
    if outside:
        idle[OUTSIDE] = sum(length for _, length in outside)
    return idle, outside


def self_seconds(lines, lo, hi, key):
    """{module: {key(op_name): seconds}} from the operations' self time
    inside [lo, hi), by the module each ran in; an operation without an
    `op_name` is booked under None."""
    modules = sorted((ev[1], ev[1] + ev[2], trace_reduce.module_name(ev[0]))
                     for ev in lines.get(trace_reduce.MODULE_LINE, []))
    module_starts = [m[0] for m in modules]
    clipped = [(max(ev[1], lo), min(ev[1] + ev[2], hi), ev)
               for ev in lines.get(trace_reduce.OP_LINE, [])
               if min(ev[1] + ev[2], hi) > max(ev[1], lo)]
    seconds = {}
    for start, _end, ev, _depth, self_ns in nest(clipped):
        i = bisect.bisect_right(module_starts, start) - 1
        if i < 0 or modules[i][1] <= start:
            continue
        per = seconds.setdefault(modules[i][2], {})
        name = key(ev[3]) if len(ev) > 3 and ev[3] else None
        per[name] = per.get(name, 0.0) + self_ns / 1e9
    return seconds


def traced_part(raw: dict):
    """(the first device's lines by name, the host's (line, event)s, the
    window's bounds in ns) of `load`'s data; None without a
    `bench.traced_window` annotation or a device plane."""
    device_planes = [p for p in raw["planes"]
                     if trace_reduce.DEVICE_PLANE.match(p["name"])]
    host = [(ln["name"], ev) for p in raw["planes"]
            if p not in device_planes for ln in p["lines"]
            for ev in ln["events"]]
    window = [ev for _, ev in host if ev[0] == trace_reduce.WINDOW_SPAN]
    if not window or not device_planes:
        return None
    lines = {ln["name"]: ln["events"] for ln in device_planes[0]["lines"]}
    return lines, host, window[0][1], window[0][1] + window[0][2]


def program_side(raw: dict) -> dict:
    """The part of `parse` that needs only the file, inside the
    `bench.traced_window` annotation: the program's spans, and from ONE
    pass over the operations (`self_seconds` keyed by `scope_and_path`)
    the seconds by top-level scope (`scope_seconds`; modules none of whose
    operations carries a scope left out) and by finer path
    (`path_seconds`: {module: {path: seconds}}, operations without a path
    and modules without any left out)."""
    part = traced_part(raw)
    if part is None:
        return {"spans": [], "scope_seconds": {}, "path_seconds": {}}
    lines, host, lo, hi = part
    by_scope, by_path = {}, {}
    for module, per in self_seconds(lines, lo, hi, scope_and_path).items():
        scopes, paths = {}, {}
        for key, seconds in per.items():
            scope, path = key or (None, None)
            scopes[scope] = scopes.get(scope, 0.0) + seconds
            if path is not None:
                paths[path] = paths.get(path, 0.0) + seconds
        if any(k is not None for k in scopes):
            by_scope[module] = scopes
        if paths:
            by_path[module] = paths
    return {"spans": _host_spans(host, lo, hi), "scope_seconds": by_scope,
            "path_seconds": by_path}


def parse(raw: dict, reduced: dict) -> dict:
    """What the readers use (module docstring), from `load`'s data and
    `trace_reduce.reduce`'s view of the same data: the window, the idle
    stretches and the modules' time are that view's own, so that what is
    charged here sums to what it reports. `idle_outside` holds the
    stretches charged to OUTSIDE (`idle_charge`)."""
    return _join(program_side(raw), reduced)


def _join(side, reduced):
    device = reduced["devices"][0] if reduced["devices"] else None
    idle, outside = _charge(device["gaps"], side["spans"]) if device \
        else ({}, [])
    return dict(side, window_s=reduced["window_s"], idle_by_span=idle,
                idle_outside=outside,
                modules=device["modules"] if device else {})


def idle_charge(parsed):
    """(charged, stretches) as `trace_reduce.attribute_gaps` takes them:
    the idle seconds by the program's span names, and the stretches that no
    span of the program covers, which are the harness's spans' to name."""
    return ({n: s for n, s in parsed["idle_by_span"].items()
             if n != OUTSIDE}, parsed["idle_outside"])


def current(reduced):
    """The program's side of the traced run whose reduced trace a reader
    was handed, as `run.read_trace` put it there; None where the reader was
    handed no trace or one without it."""
    return reduced.get("program") if reduced else None


# -- what the readers in benchmark/metrics/ ask for --------------------------

def marks(parsed, name):
    """Attributes of every span or mark called `name`, in time order."""
    return [s["attrs"] for s in (parsed or {"spans": []})["spans"]
            if s["name"] == name]


def has_program_spans(parsed) -> bool:
    return bool(parsed and parsed["spans"])


def idle_pct(parsed, names):
    """Idle time of the device charged to the spans `names`, as a share of
    the traced part; None for a program that has no phases."""
    if not has_program_spans(parsed) or parsed["window_s"] <= 0:
        return None
    return 100.0 * sum(parsed["idle_by_span"].get(n, 0.0) for n in names) \
        / parsed["window_s"]


def _module_runs(parsed, module_pattern):
    """(seconds, executions) of the matching modules inside the traced
    part, as `trace_reduce.module_seconds` counts them."""
    rows = [v for m, v in parsed["modules"].items()
            if re.search(module_pattern, m)]
    return sum(r["seconds"] for r in rows), sum(r["count"] for r in rows)


def scope_ms(parsed, module_pattern, scope, steps=1):
    """Milliseconds of `scope` per step inside the modules that match
    (`steps` a module execution); None where no operation of those modules
    carries a scope."""
    if not parsed:
        return None
    found = [v for m, v in parsed["scope_seconds"].items()
             if re.search(module_pattern, m)]
    _, runs = _module_runs(parsed, module_pattern)
    if not found or not runs:
        return None
    return 1e3 * sum(v.get(scope, 0.0) for v in found) / (runs * steps)


def rest_ms(parsed, module_pattern, scopes, steps=1):
    """The modules' own time per step less what `scopes` hold: operations
    under another scope or under none, and the time between operations."""
    named = [scope_ms(parsed, module_pattern, s, steps) for s in scopes]
    if None in named:
        return None
    seconds, runs = _module_runs(parsed, module_pattern)
    return 1e3 * seconds / (runs * steps) - sum(named)
