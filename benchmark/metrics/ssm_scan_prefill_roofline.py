"""The Mamba layers' prefill scan against its roofline BY BYTES: the least
bytes the scans of the TRUE prompt tokens must move (`ssm_scan_bytes` of
the family's table: u', dt and y of every real token and layer at the
served width, B and C at float32, the final state of every prompt and
layer), at the HBM peak, against the device time under `attn/ssm` in the
prefill programs. That scope holds the selective-scan kernel
(`kernels/selective_scan.py`, its own `attn/ssm/scan` inside it) with the
conv's four taps and the conv tail's gather beside it: the trace's paths
are two names deep, so the kernel is not read apart from them. peaks.json
has no vector-unit peak, so the share is by bytes, and the kernel, bound
by the vector and transcendental units (an exp, two products and a sum of
d_state numbers a channel a token), is expected to sit well below 100 %;
scanning a bucket's padding past a prompt's last chunk is skipped, within
it shows as loss."""
from benchmark import families, program_subscopes, trace_reduce

MODULE = r"pure_prefill"


def read(trace, host, cell):
    per_run = program_subscopes.path_ms(trace, MODULE, "attn/ssm")
    prompts = [v[1] for v in host.samples.get("prefill", [])]
    if per_run is None or not prompts or per_run <= 0:
        return None
    _, runs = trace_reduce.module_seconds(trace, MODULE)
    need = families.needs(cell.config)
    least = need.ssm_scan_bytes(cell.config, sum(prompts), len(prompts)) \
        / cell.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (per_run * runs / 1e3)
