"""Share of the traced part in which the device was idle while the host was
fetching a burst's tokens (`serving.decode.sync`), replaying them through
the callbacks (`serving.emit`) or closing the step's telemetry
(`serving.close`). The five `idle_pct.*` sum to `device_idle_pct.serve`."""
from benchmark import program_trace

SPANS = ("serving.decode.sync", "serving.emit", "serving.close")


def read(trace, host, cell):
    return program_trace.idle_pct(program_trace.current(trace), SPANS)
