"""The PolyNorm expert kernel of the decode step (`expert_hit.py`'s
`_hit_poly_kernel`, two passes over each hit expert's width) against its
roofline BY BYTES: the weights of the experts a burst's calls stream
(`experts_read`, the burst's own count on `serving.emit`: the held experts
some live token picked, a layer a step), at the HBM peak, against the
device time under `mlp/experts` in the burst, a burst at a time. That
scope holds the kernel with the table of hit experts built beside it. A
decode step of a few rows reads each hit expert's weights for them: bound
by memory. None where no burst counts `experts_read`."""
from benchmark import families, program_subscopes, program_trace

MODULE = r"pure_burst"


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "mlp/experts")
    bursts = [a["experts_read"] for a in program_trace.marks(
        program_trace.current(trace), "serving.emit")
        if a.get("experts_held")]
    if per_run is None or not bursts or per_run <= 0:
        return None
    need = families.needs(cell.config)
    least = need.expert_read_bytes(cell.config, sum(bursts) / len(bursts)) \
        / cell.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (per_run / 1e3)
