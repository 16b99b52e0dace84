"""A GPT train step's traced window."""
from benchmark_suite_helpers import gpt_host as host  # noqa: F401
from benchmark_suite_helpers import train_raw as raw  # noqa: F401
