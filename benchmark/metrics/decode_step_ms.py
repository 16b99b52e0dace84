"""Device time of one decode step: the burst program's time in the trace
over its executions x the steps in a burst."""
from benchmark import trace_reduce

MODULE = r"pure_burst"


def read(trace, host, cell):
    if trace is None:
        return None
    seconds, runs = trace_reduce.module_seconds(trace, MODULE)
    if not runs:
        return None
    return 1e3 * seconds / (runs * cell.config["engine"]["decode_burst"])
