"""Device time of one train step under `attn/flash`: the flash-attention
kernels themselves (`flash_fwd` in the forward and in the recomputed
forward, `flash_bwd_dkv`, `flash_bwd_dq`, the backward's row sums), not
the projections or the layout copies either side of them. Part of
`train_ms.attn`; None for a step whose attention runs in XLA's fusions."""
from benchmark import program_subscopes

MODULE = r"pure_step"


def read(trace, host, cell):
    return program_subscopes.path_ms(trace, MODULE, "attn/flash")
