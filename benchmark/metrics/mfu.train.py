"""Model FLOP/s utilisation of training: operations the forward and
backward passes need per token (recomputation not counted) x the tokens of
the steps dispatched in the traced part, over chips x peak x its length."""
from benchmark import flops


def read(trace, host, cell):
    if trace is None or trace["window_s"] <= 0:
        return None
    steps = sum(1 for name, _, _ in host.spans if name == "step")
    if not steps:
        return None
    mix = cell.mix
    tokens = steps * int(mix["batch_rows"]) * int(mix["seq_len"])
    return 100.0 * tokens * flops.train_flops_per_token(
        cell.config, int(mix["seq_len"])) / (
        cell.chips * cell.peaks["bf16_flops_per_s"] * trace["window_s"])
