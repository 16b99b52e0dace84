"""The PolyNorm expert kernel of a prefill (`expert_grouped.py`'s
`_grouped_poly_kernel`, two passes over each tile's expert's width)
against its roofline: the products of the (live token, held expert) pairs
a prefill made (`prefill_expert_pairs`, the prefill's own count on the
`serving.emit` that commits its first tokens) and one read of every held
expert of every expert layer (at the cell's prompts of 512 tokens and more
each is hit: a held expert is missed by all of 512 tokens' 8 picks of 384
with odds of 2e-5), the larger of the two times at the chip's peaks,
against the device time under `mlp/experts` in the prefill programs, a
program at a time. That scope holds the kernel with the rows' gather and
the one-hot product that adds each row into its token, whose time shows as
loss. None where no prefill counts its pairs."""
from benchmark import families, flops, program_subscopes, program_trace

MODULE = r"pure_prefill"


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "mlp/experts")
    pairs = [a["prefill_expert_pairs"] for a in program_trace.marks(
        program_trace.current(trace), "serving.emit")
        if "prefill_expert_pairs" in a]
    if per_run is None or not pairs or per_run <= 0:
        return None
    cfg = cell.config
    need = families.needs(cfg)
    least = flops.roofline_seconds(
        need.expert_pair_flops(cfg) * sum(pairs) / len(pairs),
        need.expert_read_bytes(cfg, need.expert_layers(cfg)
                               * cfg["num_experts"]), cell.peaks)
    return 100.0 * least / (per_run / 1e3)
