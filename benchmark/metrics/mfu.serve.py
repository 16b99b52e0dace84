"""The whole serving step's share of the chip's peak: model operations of
every prompt prefilled and every token decoded in the traced part, by the
table of the configuration's family (`benchmark/families`; GPT's is
`benchmark/flops.py`), over chips x peak x its length. It bounds every
kernel's roofline share: a kernel taken off the path leaves its own metric
silent, not this one."""
from benchmark import families


def read(trace, host, cell):
    if trace is None or trace["window_s"] <= 0:
        return None
    cfg, need = cell.config, families.needs(cell.config)
    ops = sum(need.prefill_flops(cfg, v[1])
              for v in host.samples.get("prefill", []))
    for _, tokens, rows, kv_tokens in host.samples.get("decode", []):
        ops += tokens * need.decode_flops(cfg, kv_tokens / max(rows, 1))
    if not ops:
        return None
    return 100.0 * ops / (cell.chips * cell.peaks["bf16_flops_per_s"]
                          * trace["window_s"])
