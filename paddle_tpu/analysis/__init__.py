"""tpu-lint: dependency-free AST static analysis for JAX/TPU hazards.

Every rule encodes a bug this repo actually shipped (CHANGES.md):

  jax-compat               jax APIs the installed jax 0.9.0 removed
                           (the PR 2 dead-kernel-library class)
  weak-float-in-kernel     bare float literals lowering f64 inside
                           Pallas kernel bodies under global x64
  rank-divergent-collective  collectives under `if rank == ...` —
                           fleet-wide deadlock, statically visible
  side-effect-under-jit    metrics/tracing record calls that run at
                           trace time instead of per step
  donated-arg-reuse        reads of buffers already donated to XLA
  flag-hygiene             FLAGS_* declared/used cross-check, both
                           directions
  unlocked-shared-write    an attribute written from a thread-target
                           entry path without the lock the majority
                           of its write sites hold
  lock-order-cycle         interprocedural nested-`with` lock-order
                           graph cycle — the static ABBA deadlock
  thread-lifecycle         non-daemon Thread started but never joined
                           in any close()/stop()/atexit path

The interprocedural rules ride on `core.ProjectIndex` — a cross-file
symbol table + call graph built once per run, so rules follow helper
calls from `threading.Thread(target=...)` launch sites into the
attributes and locks they actually touch. The runtime companion is
`paddle_tpu/observability/lockwatch.py` (`FLAGS_lockwatch`): its
inversion verdicts cite `lock-order-cycle`, and the rule docs point
back at the lockwatch telemetry.

CLI: `python tools/tpu_lint.py [paths...]` — exits non-zero on any
finding not in the committed baseline (tools/tpu_lint_baseline.json).
Per-line suppression: `# tpu-lint: disable=<rule>`. `--changed` lints
only git-touched files; `--jobs N` parses in parallel;
`--emit-rules-doc` generates docs/LINT_RULES.md. Docs: README.md
"Static analysis" + "Concurrency analysis".

This package imports neither jax nor the rest of paddle_tpu, so the
CLI loads it directly off sys.path and lint failures surface in
seconds.
"""
from .core import (  # noqa: F401
    FileContext,
    Finding,
    ImportMap,
    ProjectIndex,
    RULES,
    Rule,
    iter_py_files,
    load_contexts,
    register,
    repo_root,
    run,
)
from . import baseline, flagsdoc, reporters, rules, rulesdoc  # noqa: F401
