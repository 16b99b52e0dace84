"""Device time of one decode step under `attn/mhc` and `mlp/mhc`: the
hyper-connected residual's reads and writes of the streams around every
sublayer (the norm over the streams, the product with phi, the sigmoids,
the Sinkhorn iterations, the mix that feeds the sublayer and the one that
writes its output back), not the sublayers themselves. Part of
`decode_ms.attn` and `decode_ms.mlp`; None for a program whose layers have
one residual stream."""
from benchmark import program_subscopes

MODULE = r"pure_burst"
PATHS = ("attn/mhc", "mlp/mhc")


def read(trace, host, cell):
    steps = cell.config["engine"]["decode_burst"]
    parts = [program_subscopes.path_ms(trace, MODULE, p, steps)
             for p in PATHS]
    return None if None in parts else sum(parts)
