"""The full layers' prefill attention against its roofline: the operations
of the TRUE prompt tokens' causal (query, key) pairs in the full layers
(n (n + 1) / 2 a prompt a layer, each a key's width for the score and a
value's for the weighted sum over every query head; padding to the
buckets and the masked half of the diagonal blocks show as loss), by the
family's table, over the chip's peak, against the device time under
`attn/full` in the prefill programs. From 1,024 tokens on that time is the
grouped-query Pallas kernel's (`flash_attention_gqa_bshd`, 16 query heads
a kv head in two blocks of 8), below it XLA's masked attention.
Compute-bound."""
from benchmark import families, program_subscopes, trace_reduce

MODULE = r"pure_prefill"


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "attn/full")
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if per_run is None or not prompts or per_run <= 0:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    need = families.needs(cell.config)
    ops = sum(need.full_attn_flops(cell.config, n) for n in prompts)
    return 100.0 * ops / cell.peaks["bf16_flops_per_s"] \
        / (per_run * runs / 1e3)
