"""Device time of one train step under the scope `head` (the head: the final
norm, the logits, the sampler or the loss), forward, recompute and backward
alike: self time of the step program's operations whose `op_name` carries
it, per execution."""
from benchmark import program_trace

MODULE = r"pure_step"


def read(trace, host, cell):
    return program_trace.scope_ms(program_trace.current(trace), MODULE,
                                  "head")
