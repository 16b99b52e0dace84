"""Device time of one decode step under `attn/window`: the window layers'
attention itself (the read of the rings' live pages, scores, softmax and
the weighted sum; not the projections, norms, rope or gate either side of
it, nor the token write). Part of `decode_ms.attn`."""
from benchmark import program_subscopes

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_subscopes.path_ms(
        trace, MODULE, "attn/window", cell.config["engine"]["decode_burst"])
