"""The hyper-connected residual's prefill mixes against their roofline BY
BYTES: the least bytes the mixes of the TRUE prompt tokens must move
(`hyper_bytes` of the family's table: every sublayer's read of the n
streams and write of its input, and its read of the streams and its output
and write of the streams back, at the served width), at the HBM peak,
against the device time under `attn/mhc` and `mlp/mhc` in the prefill
programs. The mixes are elementwise work and small products over n x
hidden numbers a token: bound by memory. A bucket's padding past a prompt
shows as loss."""
from benchmark import families, program_subscopes, trace_reduce

MODULE = r"pure_prefill"
PATHS = ("attn/mhc", "mlp/mhc")


def read(trace, host, cell):
    parts = [program_subscopes.path_ms(trace, MODULE, p) for p in PATHS]
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if None in parts or not prompts or sum(parts) <= 0:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    least = families.needs(cell.config).hyper_bytes(
        cell.config, sum(prompts)) / cell.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(parts) * runs / 1e3)
