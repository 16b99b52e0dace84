"""A window-and-full expert program's traced window whose prefill names
both kinds of attention and which has no shared expert: `mimo_raw` with
the MLP's finer scopes, the phases round an admission and the experts'
counts."""
from benchmark_suite_helpers import (EXPERT_COUNTS, PREFILL_COUNTS,
                                     mimo_raw, serving)
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    return serving(mimo_raw(), ("mlp/router/top_k", "mlp/experts/pallas_call",
                                "head/dot_general"),
                   EXPERT_COUNTS, PREFILL_COUNTS)
