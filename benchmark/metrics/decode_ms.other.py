"""Device time of one decode step outside `attn`, `mlp` and `head`:
`decode_step_ms` less the three. It holds the embedding, the scan's own
bookkeeping, and every operation the compiler made without metadata or named
after the `while` alone."""
from benchmark import program_trace

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_trace.rest_ms(
        program_trace.current(trace), MODULE, ("attn", "mlp", "head"),
        cell.config["engine"]["decode_burst"])
