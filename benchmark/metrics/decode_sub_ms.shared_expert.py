"""Device time of one decode step under `mlp/shared`: the shared expert
(every chip of the deployment computes it alike) and its sum with the
routed share. Part of `decode_ms.mlp`."""
from benchmark import program_subscopes

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_subscopes.path_ms(
        trace, MODULE, "mlp/shared", cell.config["engine"]["decode_burst"])
