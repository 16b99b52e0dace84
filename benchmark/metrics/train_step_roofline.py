"""The train step program's share of its roofline: the model's operations
for the steps executed (recomputation not counted) over the chip's peak,
against the step module's device time on the busiest device."""
from benchmark import flops, trace_reduce

MODULE = r"pure_step"


def read(trace, host, cell):
    if trace is None:
        return None
    seconds, runs = trace_reduce.module_seconds(trace, MODULE)
    if not runs or seconds <= 0:
        return None
    mix = cell.mix
    tokens = int(mix["batch_rows"]) * int(mix["seq_len"])
    need = runs * tokens * flops.train_flops_per_token(
        cell.config, int(mix["seq_len"])) \
        / (cell.chips * cell.peaks["bf16_flops_per_s"])
    return 100.0 * need / seconds
