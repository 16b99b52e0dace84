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


def full_attn_flops(cfg, prompt_len):
    """By the family's table; a configuration without one (GPT) is full
    attention in every layer: both products of n (n + 1) / 2 pairs, 2 x
    hidden multiply-adds a pair."""
    need = families.needs(cfg)
    if hasattr(need, "full_attn_flops"):
        return need.full_attn_flops(cfg, prompt_len)
    return 4 * cfg["hidden_size"] * cfg["num_hidden_layers"] \
        * prompt_len * (prompt_len + 1) // 2


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "attn/full")
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if per_run is None or not prompts or per_run <= 0:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    ops = sum(full_attn_flops(cell.config, n) for n in prompts)
    return 100.0 * ops / cell.peaks["bf16_flops_per_s"] \
        / (per_run * runs / 1e3)
