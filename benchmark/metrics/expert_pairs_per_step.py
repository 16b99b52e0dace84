"""Token-expert pairs that land on the experts held here, per decode step
and expert layer: `expert_pairs` over `expert_layer_steps`, the counts the
burst program makes of its own routing and hands to `serving.emit` (8 at 16
live rows under uniform routing over 256 experts of which 16 are held)."""
from benchmark import program_subscopes


def read(trace, host, cell):
    return program_subscopes.emit_ratio(trace, "expert_pairs",
                                        "expert_layer_steps")
