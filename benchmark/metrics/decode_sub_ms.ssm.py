"""Device time of one decode step under `attn/ssm`: the Mamba layers' conv
and scan with their state's reads and writes (the conv tail's shift, the
decay, the state's update and its product with C, the write of the live
rows' states back into the slots' pools; not the four projections, the
norms on dt, B and C, or the gate either side of it). Part of
`decode_ms.attn`."""
from benchmark import program_subscopes

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_subscopes.path_ms(
        trace, MODULE, "attn/ssm", cell.config["engine"]["decode_burst"])
