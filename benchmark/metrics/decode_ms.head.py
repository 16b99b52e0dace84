"""Device time of one decode step under the scope `head` (the head: the final
norm, the logits, the sampler or the loss): self time of the burst program's
operations whose `op_name` carries it, over executions x the steps in a
burst. The four `decode_ms.*` sum to `decode_step_ms`."""
from benchmark import program_trace

MODULE = r"pure_burst"


def read(trace, host, cell):
    return program_trace.scope_ms(
        program_trace.current(trace), MODULE, "head",
        cell.config["engine"]["decode_burst"])
