"""The prefill programs' share of their roofline: operations of the TRUE
prompt tokens (padding to the batch and token buckets shows as loss) over
the chip's peak, against the prefill modules' device time. Compute-bound."""
from benchmark import flops, trace_reduce

MODULE = r"pure_prefill"


def read(trace, host, cell):
    if trace is None:
        return None
    seconds, runs = trace_reduce.module_seconds(trace, MODULE)
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if not runs or not prompts or seconds <= 0:
        return None
    need = sum(flops.prefill_flops(cell.config, n) for n in prompts) \
        / cell.peaks["bf16_flops_per_s"]
    return 100.0 * need / seconds
