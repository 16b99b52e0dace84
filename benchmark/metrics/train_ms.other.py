"""Device time of one train step outside `attn`, `mlp`, `head` and `optimizer`:
the step program's time per execution less the four. It holds the embedding
and its gradient scatter, and every operation the compiler made without
metadata (the asynchronous copies)."""
from benchmark import program_trace

MODULE = r"pure_step"


def read(trace, host, cell):
    return program_trace.rest_ms(program_trace.current(trace), MODULE,
                                 ("attn", "mlp", "head", "optimizer"))
