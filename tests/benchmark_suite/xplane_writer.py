"""A trace as plain data -> the `.xplane.pb` a profiler session would have
left, so that the benchmark's tests can drive the readers from a FILE, as
a traced run does (`trace_reduce.load`, `program_trace.current`).

`raw` has `program_trace.load`'s shape: planes of lines of events `[name,
start_ns, duration_ns, fourth]`, where the fourth element (optional) is a
host span's attributes or a device operation's `op_name`. What is written
follows what libtpu 0.0.34 wrote on the chip (PERF.md section 3): a host
span's attributes are the event's own stats (or stay in its name where it
ends `#k=v#`); a device operation is named by its whole HLO line, and its
`op_name` is the `tf_op` stat (`<op_name>:`, a string) of the event's
METADATA, beside `program_id` (a uint64: the number in the name of the
module it ran in) and a stat with a fixed64 value that a reader has to
step over.

The encoder is written from tensorflow/tsl's xplane.proto, apart from the
decoder in benchmark/program_trace.py, which it pins.
"""
import os
import re
import struct

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def _varint(n):
    n &= (1 << 64) - 1          # int64 fields: two's complement
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _bytes(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _double(field, x):
    return _varint(field << 3 | 1) + struct.pack("<d", x)


class _Ids(dict):
    """name -> id, handed out in order of first use, from 1."""

    def __missing__(self, key):
        self[key] = len(self) + 1
        return self[key]


def _stat(stat_ids, name, value, unsigned=False):
    """XStat: metadata_id=1, double=2, uint64=3, int64=4, str=5."""
    head = _int(1, stat_ids[name])
    if isinstance(value, float):
        return head + _double(2, value)
    if isinstance(value, int):
        return head + _int(3 if unsigned else 4, value)
    return head + _bytes(5, value)


def _hlo_line(short):
    """`fusion.1 (kOutput)` -> `%fusion.1 = f32[1]{0} fusion(), kind=kOutput`:
    what `trace_reduce.short_name` shortens again."""
    if " = " in short:
        return short
    name, _, kind = short.partition(" (")
    return f"%{name} = f32[1]{{0}} fusion()" + \
        (f", kind={kind[:-1]}" if kind else "")


def _program_of(modules, start):
    for name, m_start, m_len in modules:
        if m_start <= start < m_start + m_len:
            return int(re.search(r"\((\d+)\)$", name).group(1))
    return None


def _plane(plane, tf_op=True):
    device = bool(DEVICE_PLANE.match(plane["name"]))
    modules = [ev for ln in plane["lines"] if ln["name"] == "XLA Modules"
               for ev in ln["events"]] if device else []
    stat_ids, event_ids, event_stats = _Ids(), _Ids(), {}
    out = _bytes(2, plane["name"])
    for number, line in enumerate(plane["lines"], 1):
        base = min(ev[1] for ev in line["events"])
        body = _int(1, number) + _bytes(2, line["name"]) + _int(3, base)
        for ev in line["events"]:
            name, start, length = ev[:3]
            fourth = ev[3] if len(ev) > 3 else None
            own, of_metadata = b"", ()
            if device and line["name"] == "XLA Ops":
                name = _hlo_line(name)
                program = _program_of(modules, start)
                of_metadata = (("flops_per_s", 1.5, False),)
                if program is not None:
                    of_metadata += (("program_id", program, True),)
                if fourth and tf_op:
                    of_metadata += (("tf_op", fourth + ":", False),)
                key = (name, program)
            else:
                key = name
                for k, v in (fourth or {}).items():
                    own += _bytes(4, _stat(stat_ids, k, v))
            event_stats[event_ids[key]] = (name, of_metadata)
            # XEvent: metadata_id=1, offset_ps=2, duration_ps=3, stats=4
            body += _bytes(4, _int(1, event_ids[key])
                           + _int(2, 1000 * (start - base))
                           + _int(3, 1000 * length) + own)
        out += _bytes(3, body)
    for number, (name, stats) in event_stats.items():
        # XEventMetadata: id=1, name=2, stats=5; a map entry: key=1, value=2
        meta = _int(1, number) + _bytes(2, name)
        for k, v, unsigned in stats:
            meta += _bytes(5, _stat(stat_ids, k, v, unsigned))
        out += _bytes(4, _int(1, number) + _bytes(2, meta))
    for name, number in stat_ids.items():
        # XStatMetadata: id=1, name=2
        out += _bytes(5, _int(1, number)
                      + _bytes(2, _int(1, number) + _bytes(2, name)))
    return out


def write(raw, directory, stamp="2026_01_01_00_00_00", tf_op=True):
    """Write `raw` under `directory` where a profiler session puts its
    file; returns the path. `tf_op=False` leaves every operation's
    `op_name` out, as a libtpu that moved it elsewhere would."""
    where = os.path.join(str(directory), "plugins", "profile", stamp)
    os.makedirs(where, exist_ok=True)
    path = os.path.join(where, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(b"".join(_bytes(1, _plane(p, tf_op))
                         for p in raw["planes"]))
    return path
