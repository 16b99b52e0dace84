"""The decode (burst) program's share of its roofline: the least time the
chip needs for the steps it ran — every weight read once a step plus the
live keys and values, or the steps' operations, whichever bounds — over the
program's device time. HBM-bound at these batch sizes."""
from benchmark import flops, trace_reduce

MODULE = r"pure_burst"


def read(trace, host, cell):
    if trace is None:
        return None
    seconds, runs = trace_reduce.module_seconds(trace, MODULE)
    steps = host.samples.get("decode", [])
    if not runs or not steps or seconds <= 0:
        return None
    cfg, burst = cell.config, cell.config["engine"]["decode_burst"]
    rows = sum(v[2] for v in steps) / len(steps)
    kv_tokens = sum(v[3] for v in steps) / len(steps)
    need = flops.roofline_seconds(
        rows * flops.decode_flops(cfg, kv_tokens / max(rows, 1)),
        flops.decode_bytes(cfg, kv_tokens), cell.peaks)
    return 100.0 * runs * burst * need / seconds
