"""The window layers' prefill attention against its roofline: the
operations of the TRUE prompt tokens' visible (query, key) pairs in the
window layers (a query sees at most `sliding_window` keys; padding to the
buckets and key blocks computed and masked show as loss), by the family's
table, over the chip's peak, against the device time under `attn/window`
in the prefill programs. From 1,024 tokens on that time is the
grouped-query Pallas kernel's (`flash_attention_gqa_bshd`), below it XLA's
masked attention. Compute-bound."""
from benchmark import families, program_subscopes, trace_reduce

MODULE = r"pure_prefill"


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "attn/window")
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if per_run is None or not prompts or per_run <= 0:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    need = families.needs(cell.config)
    ops = sum(need.window_attn_flops(cell.config, n) for n in prompts)
    return 100.0 * ops / cell.peaks["bf16_flops_per_s"] \
        / (per_run * runs / 1e3)
