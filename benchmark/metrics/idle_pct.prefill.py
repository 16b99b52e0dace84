"""Share of the traced part in which the device was idle while the host was
inside `serving.prefill_batch`: preparing the padded batch and calling the
compiled prefill (`.launch`), waiting for the first tokens (`.sync`), or in
the rest of it. The page scatter is `idle_pct.kv_scatter`. The five
`idle_pct.*` sum to `device_idle_pct.serve`."""
from benchmark import program_trace

SPANS = ("serving.prefill_batch", "serving.prefill.launch",
         "serving.prefill.sync")


def read(trace, host, cell):
    return program_trace.idle_pct(program_trace.current(trace), SPANS)
