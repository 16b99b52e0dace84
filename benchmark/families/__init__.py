"""What a configuration's programs NEED, by family.

A configuration file with a `family` key names `benchmark/families/<family>
.py`: the family's weight table, how its model is built through the
program's public entry points, and the operations and bytes its ALGORITHM
needs from shapes alone. A configuration without the key is a GPT one,
whose table is `benchmark/flops.py` (and whose build is
`benchmark/program.py`'s). `needs(cfg)` gives the readers one face for
both: `prefill_flops(cfg, prompt_len)`, `decode_flops(cfg, context_len)`
and `decode_bytes(cfg, kv_tokens, rows)`, which is all that `mfu.serve`,
`mfu.prefill`, `decode_roofline` and `prefill_roofline` ask of it. What
only some families have (`page_bytes`, `window_attn_flops`,
`full_attn_flops`) is asked by readers that list those families' cells.
"""
from __future__ import annotations

import importlib
import types

from benchmark import flops


def load(family: str):
    return importlib.import_module("benchmark.families." + family)


_GPT = types.SimpleNamespace(
    prefill_flops=flops.prefill_flops, decode_flops=flops.decode_flops,
    decode_bytes=lambda cfg, kv_tokens, rows=None:
        flops.decode_bytes(cfg, kv_tokens))


def needs(cfg: dict):
    family = cfg.get("family")
    return load(family) if family else _GPT
