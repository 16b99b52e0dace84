"""The prompts' share of the chip's peak over the whole traced part, by the
table of the configuration's family: model operations of every prompt
prefilled in it over chips x peak x its length. Stands beside
`latent_moe_prefill_roofline` and moves the same end-to-end metric."""
from benchmark import families


def read(trace, host, cell):
    if trace is None or trace["window_s"] <= 0:
        return None
    need = families.needs(cell.config)
    ops = sum(need.prefill_flops(cell.config, v[1])
              for v in host.samples.get("prefill", []))
    if not ops:
        return None
    return 100.0 * ops / (cell.chips * cell.peaks["bf16_flops_per_s"]
                          * trace["window_s"])
