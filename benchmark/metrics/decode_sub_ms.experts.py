"""Device time of one decode step under `mlp/experts`: the held routed
experts' three products, weighted by the router's weights (every held
expert's product is taken every step: 6.0 GB of weights at 16 held in 4
layers). Part of `decode_ms.mlp`."""
from benchmark import program_subscopes

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_subscopes.path_ms(
        trace, MODULE, "mlp/experts", cell.config["engine"]["decode_burst"])
