"""Share of the held experts whose weights a decode step STREAMS, per
expert layer: `experts_read` over `experts_held`, counted by the burst
program and handed to `serving.emit`. 100 where every held expert's product
is taken; `experts_hit_pct` where only the experts hit are read."""
from benchmark import program_subscopes


def read(trace, host, cell):
    return program_subscopes.emit_pct(trace, "experts_read", "experts_held")
