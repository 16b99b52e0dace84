"""The prompts' share of the chip's peak over the whole traced part: model
operations of every prompt prefilled in it over chips x peak x its length.
It stands beside `prefill_roofline` (which divides by the prefill programs'
own device time) and moves the same end-to-end metric."""
from benchmark import flops


def read(trace, host, cell):
    if trace is None or trace["window_s"] <= 0:
        return None
    ops = sum(flops.prefill_flops(cell.config, v[1])
              for v in host.samples.get("prefill", []))
    if not ops:
        return None
    return 100.0 * ops / (cell.chips * cell.peaks["bf16_flops_per_s"]
                          * trace["window_s"])
