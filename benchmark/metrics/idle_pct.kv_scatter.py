"""Share of the traced part in which the device was idle while the host was
dispatching the page write after a prefill, one compiled call a layer with
that layer's pools donated (`serving.kv_scatter`). The five `idle_pct.*`
sum to `device_idle_pct.serve`."""
from benchmark import program_trace

SPANS = ("serving.kv_scatter",)


def read(trace, host, cell):
    return program_trace.idle_pct(program_trace.current(trace), SPANS)
