"""Share of the traced part in which the device was idle while the host was
inside no span of the program: the harness's loop, `add_request`, sleeping
until a request is due. The five `idle_pct.*` sum to
`device_idle_pct.serve`."""
from benchmark import program_trace

SPANS = ("(outside)",)


def read(trace, host, cell):
    return program_trace.idle_pct(program_trace.current(trace), SPANS)
