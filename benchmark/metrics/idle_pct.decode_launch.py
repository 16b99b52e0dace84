"""Share of the traced part in which the device was idle while the host was
admitting requests (`serving.admit`) or growing pages, building the launch
state, copying the arguments and calling the burst or step program
(`serving.decode.launch`). The five `idle_pct.*` sum to
`device_idle_pct.serve`."""
from benchmark import program_trace

SPANS = ("serving.admit", "serving.decode.launch")


def read(trace, host, cell):
    return program_trace.idle_pct(program_trace.current(trace), SPANS)
