"""A latent-attention expert program's traced window: `pangu_raw` with
the phases round an admission and every count its bursts and prefills
hand on."""
from benchmark_suite_helpers import (EXPERT_COUNTS, PREFILL_COUNTS,
                                     pangu_raw, serving)
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    return serving(pangu_raw(), counts=EXPERT_COUNTS,
                   prefill_counts=PREFILL_COUNTS)
