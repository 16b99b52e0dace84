"""The decode (burst) program's share of its roofline, by the table of the
configuration's family: the least time the chip needs for the steps it ran
(GPT: every weight read once a step plus the live keys and values; an
expert family: every matrix outside the routed experts once a step, the
held experts that the step's rows are expected to HIT, one cache row a
live token a layer; or the steps' operations, whichever bounds) over the
program's device time. HBM-bound at these batch sizes."""
from benchmark import families, flops, trace_reduce

MODULE = r"pure_burst"


def read(trace, host, cell):
    if trace is None:
        return None
    seconds, runs = trace_reduce.module_seconds(trace, MODULE)
    steps = host.samples.get("decode", [])
    if not runs or not steps or seconds <= 0:
        return None
    cfg, burst = cell.config, cell.config["engine"]["decode_burst"]
    need = families.needs(cfg)
    rows = sum(v[2] for v in steps) / len(steps)
    kv_tokens = sum(v[3] for v in steps) / len(steps)
    least = flops.roofline_seconds(
        rows * need.decode_flops(cfg, kv_tokens / max(rows, 1)),
        need.decode_bytes(cfg, kv_tokens, rows), cell.peaks)
    return 100.0 * runs * burst * least / seconds
