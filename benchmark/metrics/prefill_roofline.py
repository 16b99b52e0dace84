"""The prefill programs' share of their roofline, by the table of the
configuration's family: operations of the TRUE prompt tokens (padding to
the batch and token buckets, and a routed expert's product taken for a
token that did not pick it, show as loss) over the chip's peak, against
the prefill modules' device time. Compute-bound."""
from benchmark import families, trace_reduce

MODULE = r"pure_prefill"


def read(trace, host, cell):
    if trace is None:
        return None
    seconds, runs = trace_reduce.module_seconds(trace, MODULE)
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if not runs or not prompts or seconds <= 0:
        return None
    need = families.needs(cell.config)
    return 100.0 * sum(need.prefill_flops(cell.config, n) for n in prompts) \
        / cell.peaks["bf16_flops_per_s"] / seconds
