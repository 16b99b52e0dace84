"""Rule: jax-compat — use of jax APIs that the installed jax (0.9.0) no
longer has.

The repo runs on one installation. An API that an older jax spelled
differently and this one removed fails as a pure attribute lookup or
import — `AttributeError` / `ImportError` at the first call, or, behind a
catch-everything handler, a silent fall to the slow path on every step
(how the whole Pallas library once ran as dead code with green tests).
Both are statically detectable from a table.

Skipped on purpose: lookups inside a try/except-AttributeError guard, the
feature-detection idiom, including aliases assigned there.
"""
from __future__ import annotations

import ast

from ..core import Rule, register

# path -> what to write instead. Verified against jax 0.9.0 (hasattr
# probes): each of these is gone there.
COMPAT_TABLE = {
    "jax.experimental.enable_x64":
        "removed in jax 0.9.0 — use jax.enable_x64 (in this package: "
        "paddle_tpu.kernels.x64_off())",
    "jax.experimental.disable_x64":
        "removed in jax 0.9.0 — use jax.enable_x64(False)",
    "jax.experimental.host_callback":
        "removed — use jax.pure_callback / jax.experimental.io_callback",
    "jax.tree_map":
        "removed — use jax.tree.map (or jax.tree_util.tree_map)",
}


@register
class JaxCompatRule(Rule):
    name = "jax-compat"
    description = ("use of jax APIs the installed jax 0.9.0 removed "
                   "(jax.experimental.enable_x64, jax.tree_map, ...) — "
                   "raises at runtime, or worse, a guarded call site "
                   "silently falls back to XLA")
    hazard = ("The repo runs on jax 0.9.0; an API that jax removed "
              "raises AttributeError/ImportError at the first call — or "
              "a catch-everything handler around it silently takes the "
              "slow fallback path on every step.")
    example = ("`with jax.experimental.enable_x64(False):` (0.9.0 spells "
               "it `jax.enable_x64`)")
    fix = "Use the 0.9.0 spelling listed in the finding."

    def check(self, ctx):
        for node in ctx.nodes:
            if isinstance(node, ast.Attribute):
                if not isinstance(node.ctx, ast.Load):
                    continue
                path = ctx.imports.expand(node)
                advice = COMPAT_TABLE.get(path) if path else None
                if advice is None or ctx.in_attr_guard(node.lineno):
                    continue
                yield ctx.finding(self.name, node, f"`{path}` {advice}")
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                # the from-import spelling of a removed API fails
                # identically (ImportError instead of AttributeError)
                for a in node.names:
                    path = f"{node.module}.{a.name}" \
                        if node.module else a.name
                    advice = COMPAT_TABLE.get(path)
                    if advice is None or ctx.in_attr_guard(node.lineno):
                        continue
                    yield ctx.finding(
                        self.name, node,
                        f"`from {node.module} import {a.name}`: "
                        f"`{path}` {advice}")
