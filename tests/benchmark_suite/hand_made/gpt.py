"""A GPT serving program's traced window: `gpt_raw` with the scopes,
phases and page counts the program puts there."""
from benchmark_suite_helpers import BODY, MS, gpt_raw, serving
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    trace = gpt_raw()
    ops = trace["planes"][0]["lines"][1]["events"]
    # the burst's first operation is attention's, its second the
    # compiler's own; the prefill's is the MLP's
    ops[0].append(BODY + "attn/dot_general")
    ops[2].append("jit(pure_prefill)/mlp/dot_general")
    trace["planes"][1]["lines"][0]["events"] += [
        ["serving.decode.sync", 11 * MS, 30 * MS, {}],
        ["serving.emit", 41 * MS, 2 * MS,
         {"attn_pages_read": 10, "attn_pages_mapped": 64}],
        ["serving.prefill_batch", 45 * MS, 29 * MS, {}],
        ["serving.kv_scatter", 70 * MS, 4 * MS, {}]]
    return serving(trace)
