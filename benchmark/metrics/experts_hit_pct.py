"""Share of the held experts that some live row picked, per decode step and
expert layer: `experts_hit` over `experts_held`, counted by the burst
program and handed to `serving.emit`. What a step that read only the
experts hit would save is the rest."""
from benchmark import program_subscopes


def read(trace, host, cell):
    return program_subscopes.emit_pct(trace, "experts_hit", "experts_held")
