"""A hyper-connected GDLA expert program's traced window: the prefill
program 10-30 ms holds the window layers' attention (2 ms under
`attn/window`), the full layers' (3 ms under `attn/latent`), the residual
mixes (1 + 1 ms under `attn/mhc` and `mlp/mhc`) and the experts (4 ms under
`mlp/experts`); the burst 40-60 ms (4 steps) holds a `while` whose body has
the window layers' attention over their rings (4 ms), the full layers'
over their pages (2 ms), the token write (1 ms), the projections (3 ms),
the mixes (1 + 1 ms) and the MLP's finer scopes; the phases round an
admission, the window pages' and the experts' counts on the emits, the
prefill's pairs on a phase of their own."""
from benchmark_suite_helpers import (BODY, EXPERT_COUNTS, MS, PAGE_COUNTS,
                                     PREFILL_COUNTS, planes, serving)
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    p, pre = BODY, "jit(pure_prefill)/"
    ops = [
        ["call.20", 10 * MS, 2 * MS, pre + "attn/window/x"],
        ["fusion.21", 12 * MS, 3 * MS, pre + "attn/latent/dot_general"],
        ["fusion.22", 15 * MS, 1 * MS, pre + "attn/mhc/dot_general"],
        ["fusion.23", 16 * MS, 1 * MS, pre + "mlp/mhc/add"],
        ["call.24", 17 * MS, 4 * MS, pre + "mlp/experts/pallas_call"],
        ["fusion.25", 21 * MS, 9 * MS, pre + "mlp/dot_general"],
        ["while.4", 40 * MS, 20 * MS, "jit(pure_burst)/while"],
        ["fusion.1", 41 * MS, 4 * MS, p + "attn/window/gather"],
        ["fusion.2", 45 * MS, 2 * MS, p + "attn/latent/gather"],
        ["fusion.3", 47 * MS, 1 * MS, p + "attn/kv_write/scatter"],
        ["fusion.4", 48 * MS, 3 * MS, p + "attn/dot_general"],
        ["fusion.5", 51 * MS, 1 * MS, p + "attn/mhc/dot_general"],
        ["fusion.6", 52 * MS, 1 * MS, p + "mlp/mhc/add"],
    ]
    modules = [["jit_pure_prefill(11)", 10 * MS, 20 * MS],
               ["jit_pure_burst(13)", 40 * MS, 20 * MS]]
    events = [["bench.traced_window", 0, 100 * MS, {}],
              ["serving.decode.sync", 40 * MS, 20 * MS, {}],
              ["serving.emit", 61 * MS, 2 * MS, dict(PAGE_COUNTS)],
              ["serving.emit", 70 * MS, 2 * MS,
               dict(PAGE_COUNTS, attn_window_pages_read=120)],
              ["serving.emit", 80 * MS, 1 * MS, {}]]
    return serving(planes(modules, ops, events),
                   ("mlp/router/top_k", "mlp/experts/pallas_call",
                    "mlp/shared/dot_general", "head/dot_general"),
                   EXPERT_COUNTS, PREFILL_COUNTS)
