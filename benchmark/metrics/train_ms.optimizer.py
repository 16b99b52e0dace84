"""Device time of one train step under the scope `optimizer` (the optimizer's
update where XLA left it a fusion of its own; an update fused into a weight-
gradient matmul is booked to that matmul's scope), forward, recompute and
backward alike: self time of the step program's operations whose `op_name`
carries it, per execution."""
from benchmark import program_trace

MODULE = r"pure_step"


def read(trace, host, cell):
    return program_trace.scope_ms(program_trace.current(trace), MODULE,
                                  "optimizer")
