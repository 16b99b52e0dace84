"""Device time of one decode step under `mlp/router`: the router's product
over ALL the deployment's experts, the sigmoid, the top-k and the weights
of the experts held here. Part of `decode_ms.mlp`."""
from benchmark import program_subscopes

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_subscopes.path_ms(
        trace, MODULE, "mlp/router", cell.config["engine"]["decode_burst"])
