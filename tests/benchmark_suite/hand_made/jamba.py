"""A Mamba-and-attention program's traced window: the prefill program
10-30 ms holds the attention layers' kernel (4 ms under `attn/full`), the
Mamba layers' scan with its conv (6 ms under `attn/ssm`, 5 of them the
kernel's own `attn/ssm/scan`) and the FFNs; the burst 40-60 ms (4 steps)
holds a `while` whose body has the Mamba layers' conv and state update
(5 ms, `attn/ssm`), the attention layers' decode kernel (2 ms,
`attn/full`), the token write (1 ms, `attn/kv_write`) and the projections
(3 ms, `attn` but no finer name); the phases round an admission and the
page writes, as the program puts them there."""
from benchmark_suite_helpers import BODY, MS, planes, serving
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    p = BODY
    ops = [
        ["call.20", 10 * MS, 4 * MS, "jit(pure_prefill)/attn/full/x"],
        ["fusion.21", 14 * MS, 1 * MS, "jit(pure_prefill)/attn/ssm/add"],
        ["call.22", 15 * MS, 5 * MS,
         "jit(pure_prefill)/attn/ssm/scan/selective_scan"],
        ["fusion.23", 20 * MS, 10 * MS, "jit(pure_prefill)/mlp/dot_general"],
        ["while.4", 40 * MS, 20 * MS, "jit(pure_burst)/while"],
        ["fusion.1", 41 * MS, 5 * MS, p + "attn/ssm/exp"],
        ["call.2", 46 * MS, 2 * MS, p + "attn/full/pallas_call"],
        ["fusion.3", 48 * MS, 1 * MS, p + "attn/kv_write/scatter"],
        ["fusion.4", 49 * MS, 3 * MS, p + "attn/dot_general"],
        ["fusion.5", 52 * MS, 2 * MS, p + "mlp/dot_general"],
        ["fusion.6", 54 * MS, 1 * MS, p + "head/dot_general"],
    ]
    modules = [["jit_pure_prefill(11)", 10 * MS, 20 * MS],
               ["jit_pure_burst(13)", 40 * MS, 20 * MS]]
    host_events = [
        ["bench.traced_window", 0, 100 * MS, {}],
        ["serving.prefill_batch", 8 * MS, 24 * MS, {}],
        ["serving.kv_scatter", 30 * MS, 2 * MS, {}],
        ["serving.decode.sync", 40 * MS, 20 * MS, {}],
        ["serving.emit", 61 * MS, 2 * MS,
         {"attn_pages_read": 80, "attn_pages_mapped": 1000}],
        ["serving.emit", 70 * MS, 1 * MS, {}]]
    return serving(planes(modules, ops, host_events))
