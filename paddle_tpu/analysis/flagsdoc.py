"""Flag-declaration scraper shared by the flag-hygiene rule and the
`--emit-flags-doc` generator. AST-only: parsing framework/config.py
must not import it (imports pull jax; lint runs stay sub-second)."""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import List, Optional

CONFIG_RELPATH = os.path.join("paddle_tpu", "framework", "config.py")


@dataclasses.dataclass
class FlagDecl:
    name: str
    default: object
    type_name: str
    help: str
    lineno: int

    def one_line_help(self) -> str:
        text = re.sub(r"\s+", " ", self.help).strip()
        # first sentence: split on ". " but not inside e.g. "0.9.0"
        m = re.search(r"\.\s+[A-Z`'\"(]", text)
        return text[: m.start() + 1] if m else text


def _literal(node) -> object:
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return ast.unparse(node) if hasattr(ast, "unparse") else "?"


def parse_flag_declarations(config_path: str) -> List[FlagDecl]:
    with open(config_path, "r", encoding="utf-8") as f:
        tree = ast.parse(f.read())
    decls: List[FlagDecl] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "define_flag"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        name = node.args[0].value
        default = _literal(node.args[1]) if len(node.args) > 1 else None
        help_ = ""
        if len(node.args) > 2 and isinstance(node.args[2], ast.Constant):
            help_ = str(node.args[2].value)
        type_name: Optional[str] = None
        for kw in node.keywords:
            if kw.arg == "help_" and isinstance(kw.value, ast.Constant):
                help_ = str(kw.value.value)
            elif kw.arg == "type_":
                type_name = ast.unparse(kw.value) \
                    if hasattr(ast, "unparse") else "?"
        if type_name is None:
            type_name = type(default).__name__
        decls.append(FlagDecl(name=name, default=default,
                              type_name=type_name, help=help_,
                              lineno=node.lineno))
    decls.sort(key=lambda d: d.name)
    return decls


def to_markdown(decls: List[FlagDecl]) -> str:
    lines = [
        "# `FLAGS_*` reference",
        "",
        "Every runtime flag the framework declares "
        "(`paddle_tpu/framework/config.py`). Set via environment "
        "(`FLAGS_x=1 python ...`) or `paddle.set_flags({...})`.",
        "",
        "Generated by `python tools/tpu_lint.py --emit-flags-doc "
        "docs/FLAGS.md` — do not edit by hand; "
        "`tests/test_tpu_lint.py` fails when this file goes stale. "
        "The flag-hygiene lint rule cross-checks both directions: "
        "every `FLAGS_*` read must be declared here, and every "
        "declaration must have a live read.",
        "",
        "| Flag | Type | Default | Description |",
        "| --- | --- | --- | --- |",
    ]
    for d in decls:
        default = repr(d.default).replace("|", "\\|")
        help_ = d.one_line_help().replace("|", "\\|")
        lines.append(f"| `{d.name}` | `{d.type_name}` | `{default}` "
                     f"| {help_} |")
    lines.append("")
    return "\n".join(lines)
