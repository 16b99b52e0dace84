"""Device time of one decode step under `attn/latent`: the gather of the
rows' latent pages, scores, softmax and the weighted sum, in absorbed form
(not the projections either side of it). Part of `decode_ms.attn`."""
from benchmark import program_subscopes

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_subscopes.path_ms(
        trace, MODULE, "attn/latent", cell.config["engine"]["decode_burst"])
