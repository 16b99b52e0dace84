"""The prompts' share of the chip's peak over the whole traced part: model
operations of every prompt prefilled in it, by the table of the
configuration's family, over chips x peak x its length. It stands beside
`prefill_roofline` (which divides by the prefill programs' own device
time) and moves the same end-to-end metric."""
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
