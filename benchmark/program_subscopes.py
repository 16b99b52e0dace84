"""Device time under a model's FINER scopes (`mlp/experts`, `attn/latent`)
and the counts a burst hands to `serving.emit`.

`program_trace.scope_seconds` sums an operation's self time by the
top-level scope in its `op_name` (`tracing.SCOPES`). A model that names
parts of a scope (`attn/kv_write`, `attn/latent`, `mlp/router`,
`mlp/experts`, `mlp/shared`) puts the finer name right behind the
top-level one, and `program_trace` sums by that pair in the same pass
over the operations (`scope_and_path`, `path_seconds`): the same data,
window, nesting and module line. A trace whose operations carry no such
name (another model's, an older commit's) gives no value: every reader
then returns None.
"""
from __future__ import annotations

import re

from benchmark import program_trace, trace_reduce


def path_ms(reduced, module_pattern, path, steps=1):
    """Milliseconds of `path` per step inside the modules that match
    (`steps` a module execution); None where the reader was handed no
    trace, one without the program's side, or no operation of those
    modules carries the path."""
    parsed = program_trace.current(reduced)
    if not parsed:
        return None
    found = [v for m, v in parsed["path_seconds"].items()
             if re.search(module_pattern, m)]
    _, runs = trace_reduce.module_seconds(reduced, module_pattern)
    if not runs or not any(path in v for v in found):
        return None
    return 1e3 * sum(v.get(path, 0.0) for v in found) / (runs * steps)


def emit_ratio(reduced, over, under):
    """sum(`over`) / sum(`under`) of the attributes a model's burst put on
    its `serving.emit` phases in the traced run (the program's own counts);
    None where no phase carries `under`, or one that does lacks `over` (a
    commit before that count existed)."""
    emits = [a for a in program_trace.marks(program_trace.current(reduced),
                                            "serving.emit") if a.get(under)]
    if not emits or any(over not in a for a in emits):
        return None
    return sum(a[over] for a in emits) / sum(a[under] for a in emits)


def emit_pct(reduced, over, under):
    """`emit_ratio` as a share of 100."""
    ratio = emit_ratio(reduced, over, under)
    return None if ratio is None else 100.0 * ratio
