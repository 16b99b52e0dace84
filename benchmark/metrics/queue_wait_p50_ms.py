"""Median time a request waited in the engine's queue before it got a slot:
`queued_us` of the `serving.admitted` marks in the traced part (first
admissions only; about eleven requests, so no higher percentile). A load
generator that hands requests over between steps, as the harness's does,
leaves little of the wait here: the rest is `gen_lag_p95_ms`."""
from benchmark import program_trace
from benchmark.hostlog import percentile


def read(trace, host, cell):
    admitted = program_trace.marks(program_trace.current(trace),
                                   "serving.admitted")
    return percentile([a["queued_us"] / 1e3 for a in admitted
                       if not a.get("requeue")], 50)
