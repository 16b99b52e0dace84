"""Bench regression gate: compare a fresh bench.py metric JSON against
earlier measured rows.

Dependency-free (stdlib json only — runs before any framework import
can fail). The fresh row is the compact JSON line a measured (on-chip)
bench.py run prints last (pass the captured file, or `-` to read stdin
and take the last parseable line); a `--smoke` row carries no metric
and is not a fresh row. Baselines, most-recent comparable row wins:

- `BENCH_HISTORY.jsonl` — the append-only trajectory bench.py writes
  one row per measured run (commit + date);
- `--baseline FILE` — optionally, a JSON object of further rows.

Rows are comparable when metric AND backend AND geometry (batch / seq /
hidden / layers, where both sides carry them) match — rows from
different platforms never judge each other. Per-metric tolerances,
direction-aware:

    value            default 10% (lower is a regression)
    extra.mfu        10% (lower is a regression)
    extra.loss_last  5%  (higher is a regression — seeded runs are
                          deterministic; a loss jump is a correctness
                          smell, not noise)
    extra.peak_hbm_bytes  50% + 32 MiB absolute floor (higher
                          regresses — the floor keeps tiny CPU-smoke
                          baselines, whose peaks are a few MB, from
                          flagging small absolute buffer growth)
    extra.compiles / decode_recompiles  +50% and +2 absolute slack
                          (higher regresses — a compile-count jump is
                          the recompile-storm smell)

    python tools/bench_compare.py --fresh chiprun_out/bench_row.json
    python tools/bench_compare.py --fresh - --tolerance 0.10 < out.txt

Exit codes: 0 = within tolerance, 1 = regression beyond tolerance,
2 = fresh/baseline missing or unparseable, or no comparable baseline
row (first run on a new config: append history first, then the gate
arms itself).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# (name, path into the row, higher_is_better, relative tolerance,
#  absolute slack, noisy). Only `noisy` metrics (timing-derived —
# throughput/MFU wobble with machine load) honor the --tolerance
# widening knob; loss/peak-HBM/compile counts are deterministic on a
# seeded run, so a "CPU noise margin" must never loosen them.
METRICS = (
    ("value", ("value",), True, 0.10, 0.0, True),
    ("mfu", ("extra", "mfu"), True, 0.10, 0.0, True),
    ("loss_last", ("extra", "loss_last"), False, 0.05, 0.0, False),
    ("peak_hbm_bytes", ("extra", "peak_hbm_bytes"), False, 0.50,
     32 * 1024 * 1024, False),
    ("compiles", ("extra", "compiles"), False, 0.50, 2.0, False),
    ("decode_recompiles", ("extra", "decode_recompiles"), False,
     0.0, 0.0, False),
)

# geometry AND the tuning knobs mfu_sweep varies at identical geometry
# (recompute/scan/fused_ce trade throughput legitimately — a sweep
# variant's history row must never baseline a canonical run). A key
# absent on EITHER side is not compared, so pre-knob rows stay usable.
GEOMETRY_KEYS = ("batch", "seq", "hidden", "layers", "prompt_len",
                 "new_tokens", "recompute", "scan_layers", "fused_ce")

# the serving decode knobs are comparability keys too — a speculative
# or quantized row must never baseline a vanilla run or vice versa —
# but with ABSENT == None: pre-knob baseline rows (no spec_decode key)
# are vanilla runs, and skipping the key would let a ~2x speculative
# row baseline the vanilla 357 tok/s capture, the exact mis-baselining
# these keys exist to prevent
KNOB_KEYS_ABSENT_IS_NONE = ("quant", "kv_quant", "spec_decode",
                            "draft_layers", "overlap", "grad_bucket_mb",
                            "prefetch_depth", "replicas",
                            "router_policy", "prefix_cache",
                            "prefill_chunk", "kv_tier")


def _knob(extra: dict, key: str):
    """Knob value normalized for comparability. `replicas` treats 1 ==
    absent == None (a single-engine run IS the un-routed baseline —
    pre-router history rows must keep baselining fresh single-engine
    rows), while a multi-replica router row (replicas >= 2) never
    matches a single-engine one."""
    v = extra.get(key)
    if key == "replicas" and v == 1:
        return None
    return v


def _get(row, path):
    cur = row
    for k in path:
        if not isinstance(cur, dict) or k not in cur:
            return None
        cur = cur[k]
    return cur if isinstance(cur, (int, float)) else None


def load_fresh(path: str):
    """The fresh compact JSON row: a file holding it, or '-' for stdin
    (last parseable line wins — the bench stdout-tail contract)."""
    try:
        text = sys.stdin.read() if path == "-" else open(path).read()
    except OSError as e:
        print(f"bench_compare: cannot read fresh row: {e}",
              file=sys.stderr)
        return None
    row = None
    for line in text.strip().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "metric" in cand:
            row = cand
    if row is None:
        print(f"bench_compare: no parseable metric JSON in {path}",
              file=sys.stderr)
    return row


def load_baselines(cache_path: str, history_path: str):
    """Candidate baseline rows in source order (the optional
    `--baseline` object's rows, then the history trajectory); the gate
    re-orders the comparable ones by their `date` field before taking
    the most recent."""
    rows = []
    if cache_path:
        try:
            with open(cache_path) as f:
                cache = json.load(f)
            for key in sorted(cache):
                row = cache[key]
                if isinstance(row, dict) and "metric" in row:
                    rows.append({**row, "_source": f"cache[{key}]"})
        except (OSError, ValueError):
            pass
    try:
        with open(history_path) as f:
            for i, line in enumerate(f):
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, dict) and "metric" in row:
                    rows.append({**row, "_source": f"history[{i}]"})
    except OSError:
        pass
    return rows


def comparable(fresh: dict, base: dict) -> bool:
    """Same metric, same backend, same geometry (where both declare
    it), same smoke-ness — never judge a CPU smoke against an on-chip
    capture. The fresh row must not itself be an error artifact."""
    if fresh.get("metric") != base.get("metric"):
        return False
    fe = fresh.get("extra") or {}
    be = base.get("extra") or {}
    if fe.get("backend") != be.get("backend"):
        return False
    if bool(fresh.get("smoke")) != bool(base.get("smoke")):
        return False
    for k in GEOMETRY_KEYS:
        if k in fe and k in be and fe[k] != be[k]:
            return False
    for k in KNOB_KEYS_ABSENT_IS_NONE:
        if (k in fe or k in be) and _knob(fe, k) != _knob(be, k):
            return False
    return True


def compare(fresh: dict, base: dict, tolerance=None):
    """[(name, fresh_v, base_v, delta_frac, regressed)] for every
    metric both rows carry. `tolerance` (the CLI --tolerance knob)
    WIDENS the relative tolerance of the NOISY (timing-derived)
    metrics only — it never tightens a per-metric ceiling, and never
    loosens the deterministic correctness metrics (loss/peak-HBM/
    compile counts), which don't wobble with machine load."""
    out = []
    for name, path, higher_better, rel, slack, noisy in METRICS:
        fv = _get(fresh, path)
        bv = _get(base, path)
        if fv is None or bv is None:
            continue
        rel_eff = rel
        if tolerance is not None and noisy:
            rel_eff = max(rel, float(tolerance))
        if higher_better:
            floor = bv * (1.0 - rel_eff) - slack
            regressed = fv < floor
            delta = (fv - bv) / bv if bv else 0.0
        else:
            ceil = bv * (1.0 + rel_eff) + slack
            regressed = fv > ceil
            delta = (fv - bv) / bv if bv else (1.0 if fv > bv else 0.0)
        out.append((name, fv, bv, delta, regressed))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fresh", required=True,
                    help="file holding the fresh compact JSON row "
                         "('-' = stdin, last parseable line)")
    ap.add_argument("--baseline", default="",
                    help="optional JSON object {key: row} of further "
                         "baseline rows (default: none)")
    ap.add_argument("--history",
                    default=os.path.join(REPO, "BENCH_HISTORY.jsonl"),
                    help="bench trajectory ledger (default: "
                         "BENCH_HISTORY.jsonl); most recent comparable "
                         "row wins")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="widen the relative tolerance of the noisy "
                         "timing-derived metrics (value/mfu) to "
                         "max(table value, this) — for loaded CI "
                         "boxes; deterministic metrics (loss, "
                         "peak-HBM, compiles) keep their own "
                         "tolerances (default: the per-metric table; "
                         "'value' is 0.10)")
    args = ap.parse_args(argv)

    fresh = load_fresh(args.fresh)
    if fresh is None:
        return 2
    if "error" in fresh:
        print(f"bench_compare: fresh row is an error artifact: "
              f"{fresh['error']}", file=sys.stderr)
        return 2
    baselines = [b for b in load_baselines(args.baseline, args.history)
                 if comparable(fresh, b)]
    if not baselines:
        print(f"bench_compare: no comparable baseline row for "
              f"metric={fresh.get('metric')} "
              f"backend={(fresh.get('extra') or {}).get('backend')} "
              f"in {args.baseline} / {args.history} — run bench.py "
              f"once to seed the history ledger", file=sys.stderr)
        return 2
    # most recent comparable row wins BY DATE (ISO-8601 UTC strings
    # order lexicographically; stable sort keeps the baseline→history
    # source order for date-less or tied rows) — a --baseline row newer
    # than the history tail must beat it, not lose on file order
    baselines.sort(key=lambda b: str(b.get("date") or ""))
    base = baselines[-1]
    # bench.py appends the fresh run's own row to the history ledger
    # BEFORE this gate runs — comparing the run against itself would
    # make the gate vacuous. A most-recent history row with the exact
    # same value IS that self-row (a timing-derived float colliding
    # across distinct runs is negligible): step back to the previous
    # comparable baseline, and when the echo is the ONLY comparable
    # row (first run of a new config) the gate is unarmed — exit 2,
    # same as no baseline at all, never a self-passing 0.
    if base.get("_source", "").startswith("history") \
            and base.get("value") == fresh.get("value"):
        if len(baselines) < 2:
            print("bench_compare: the only comparable baseline is this "
                  "run's own history echo — the gate is unarmed until "
                  "a prior run exists for this config", file=sys.stderr)
            return 2
        base = baselines[-2]
    rows = compare(fresh, base, tolerance=args.tolerance)
    if not rows:
        print("bench_compare: comparable baseline found but no shared "
              "numeric metrics to compare", file=sys.stderr)
        return 2
    print(f"baseline: {base['_source']} "
          f"(commit {base.get('commit', '?')}, "
          f"date {base.get('date', '?')})")
    print(f"{'metric':<18} {'fresh':>14} {'baseline':>14} "
          f"{'delta':>8}  verdict")
    regressed = False
    for name, fv, bv, delta, bad in rows:
        regressed |= bad
        print(f"{name:<18} {fv:>14.4f} {bv:>14.4f} "
              f"{delta * 100.0:>7.1f}%  "
              f"{'REGRESSION' if bad else 'ok'}")
    if regressed:
        print("bench_compare: REGRESSION beyond tolerance — see the "
              "table above (baseline commit/date printed; a deliberate "
              "trade re-banks the baseline by rerunning bench.py)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
