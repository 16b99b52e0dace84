"""Programs built inside the traced part: `jit.build` marks, one per backend
compile AND one per load from the persistent cache (which adds no file for
`compiles_in_window` to count). Must read 0."""
from benchmark import program_trace


def read(trace, host, cell):
    parsed = program_trace.current(trace)
    if not program_trace.has_program_spans(parsed):
        return None
    return len(program_trace.marks(parsed, "jit.build"))
