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


def window_attn_flops(cfg, prompt_len):
    """By the family's table; a configuration without one (GPT) has no
    window, which is a window as long as the prompt in every layer: both
    products of n (n + 1) / 2 pairs, 2 x hidden multiply-adds a pair. (No
    program of such a configuration names `attn/window`, so the reader has
    nothing to read there and no cell of one lists it.)"""
    need = families.needs(cfg)
    if hasattr(need, "window_attn_flops"):
        return need.window_attn_flops(cfg, prompt_len)
    return 4 * cfg["hidden_size"] * cfg["num_hidden_layers"] \
        * prompt_len * (prompt_len + 1) // 2


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "attn/window")
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if per_run is None or not prompts or per_run <= 0:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    ops = sum(window_attn_flops(cell.config, n) for n in prompts)
    return 100.0 * ops / cell.peaks["bf16_flops_per_s"] \
        / (per_run * runs / 1e3)
