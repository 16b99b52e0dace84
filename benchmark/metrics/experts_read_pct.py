"""Share of the held experts whose weights a decode step STREAMS, per
expert layer: `experts_read` over `experts_held`, counted by the burst
program and handed to `serving.emit`. 100 where every held expert's product
is taken; `experts_hit_pct` where only the experts hit are read."""
from benchmark import program_subscopes


def read(trace, host, cell):
    try:
        ratio = program_subscopes.emit_ratio(trace, "experts_read",
                                             "experts_held")
    except KeyError:
        # a burst that counts the experts it holds and not the ones it
        # reads (a commit before the count existed): no value
        return None
    return None if ratio is None else 100.0 * ratio
