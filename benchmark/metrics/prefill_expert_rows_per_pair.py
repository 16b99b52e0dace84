"""Rows of expert products a prefill takes for each (live token, held
expert it picked) pair it made: `prefill_expert_rows` over
`prefill_expert_pairs`, the counts a prefill program makes of its own
expert layers and hands to the `serving.emit` phase that commits its first
tokens. 1 would be the pairs alone; a grouped product reads above it by
its tiles' padding; every held expert's product for every padded position
(16 rows a token over half a pair or one a live token) reads 20 to 40."""
from benchmark import program_subscopes


def read(trace, host, cell):
    return program_subscopes.emit_ratio(trace, "prefill_expert_rows",
                                        "prefill_expert_pairs")
