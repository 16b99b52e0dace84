"""A window-and-full expert program's traced window (a shared expert
beside the routed ones): `afmoe_raw` with the MLP's finer scopes, the
phases round an admission and the experts' counts."""
from benchmark_suite_helpers import (EXPERT_COUNTS, PREFILL_COUNTS,
                                     afmoe_raw, serving)
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    return serving(afmoe_raw(), ("mlp/router/top_k", "mlp/experts/pallas_call",
                                 "mlp/shared/dot_general", "head/dot_general"),
                   EXPERT_COUNTS, PREFILL_COUNTS)
