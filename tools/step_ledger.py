"""Step-time ledger report: the waterfall an operator reads before
picking the next perf move.

Loads the `stepledger_*` families from a Prometheus exposition written
by a FLAGS_stepledger run (a `tools/serving_metrics_snapshot.py --out`
artifact, a fleet `rank_<i>/ledger.prom` shard, a merged `fleet.prom`,
or a `FLAGS_telemetry_dir` root — rank shards summed) and prints, per
entry point:

- the step-time WATERFALL: wall time reconciled into compute / host /
  collective / data_wait / compile / residual buckets;
- the roofline classification (compute- vs HBM- vs comms-bound from
  cost_analysis flops/bytes against the shared device-peak table) and
  measured MFU where the entry point registered its cost;
- the top-N optimization targets, each naming the dominant bucket and
  the ROADMAP move it implicates ("collective wait 22% of step ->
  overlap dp reduce-scatter").

    python tools/step_ledger.py /tmp/ci_metrics_traced.prom
    python tools/step_ledger.py /tmp/ci_fleet --json
    python tools/step_ledger.py metrics.prom --max-residual 0.25  # CI

Exit codes: 0 = report printed, 1 = --max-residual given and some
entry's residual fraction crossed it (CI treats an unexplained step as
red), 2 = no stepledger samples found (was FLAGS_stepledger set?).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_samples(path: str):
    """Parsed Prometheus samples from a .prom file, or the summed
    rank_<i>/{ledger,metrics}.prom shards of a telemetry dir."""
    from paddle_tpu.observability import stepledger

    paths = []
    if os.path.isdir(path):
        for cand in ("fleet.prom", "ledger.prom", "metrics.prom"):
            p = os.path.join(path, cand)
            if os.path.exists(p):
                paths.append(p)
                break
        else:
            for fname in ("ledger.prom", "metrics.prom"):
                paths = sorted(
                    glob.glob(os.path.join(path, "rank_*", fname)))
                if paths:
                    break
        if not paths:
            raise OSError(f"{path}: no fleet.prom / ledger.prom / "
                          f"rank_*/ledger.prom inside")
    else:
        paths = [path]
    return stepledger.samples_from_prom_files(paths)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("exposition",
                    help="Prometheus exposition holding stepledger_* "
                         "families (metrics snapshot, ledger.prom "
                         "shard, fleet.prom, or a telemetry dir)")
    ap.add_argument("--json", action="store_true",
                    help="emit the waterfall rows + targets as JSON "
                         "instead of text")
    ap.add_argument("--top", type=int, default=3,
                    help="optimization targets to name (default 3)")
    ap.add_argument("--max-residual", type=float, default=None,
                    metavar="FRAC",
                    help="exit 1 when any entry's residual bucket "
                         "exceeds this fraction of its wall time "
                         "(CI gate: 0.25)")
    ap.add_argument("--max-data-wait-frac", type=float, default=None,
                    metavar="FRAC",
                    help="exit 1 when any entry's data_wait bucket "
                         "exceeds this fraction of its wall time — the "
                         "input-starvation gate for prefetch-on runs "
                         "(CI gate: 0.05)")
    args = ap.parse_args(argv)

    from paddle_tpu.observability import stepledger

    try:
        samples = _load_samples(args.exposition)
    except OSError as e:
        print(f"step_ledger: cannot load {args.exposition}: {e}",
              file=sys.stderr)
        return 2
    agg = stepledger.aggregate_from_samples(samples)
    rows = stepledger.waterfall(agg)
    if not rows:
        print(f"step_ledger: no stepledger_* samples in "
              f"{args.exposition} (was FLAGS_stepledger set on the "
              f"workload?)", file=sys.stderr)
        return 2
    tg = stepledger.targets(rows, top=args.top)
    if args.json:
        print(json.dumps({"waterfall": rows, "targets": tg}, indent=1))
    else:
        sys.stdout.write(stepledger.format_report(rows, top=args.top))
    if args.max_residual is not None:
        worst = max(rows, key=lambda r: r["residual_frac"])
        if worst["residual_frac"] > args.max_residual:
            print(f"step_ledger: residual gate FAILED — "
                  f"{worst['entry']} leaves "
                  f"{worst['residual_frac'] * 100.0:.1f}% of step wall "
                  f"time unexplained (> "
                  f"{args.max_residual * 100.0:.0f}%); enable "
                  f"FLAGS_compilewatch/FLAGS_telemetry_dir or lower "
                  f"FLAGS_stepledger_block_every to name it",
                  file=sys.stderr)
            return 1
    if args.max_data_wait_frac is not None:
        worst = max(rows,
                    key=lambda r: r["buckets"]["data_wait"]["frac"])
        frac = worst["buckets"]["data_wait"]["frac"]
        if frac > args.max_data_wait_frac:
            print(f"step_ledger: data-wait gate FAILED — "
                  f"{worst['entry']} starves "
                  f"{frac * 100.0:.1f}% of step wall time on input "
                  f"(> {args.max_data_wait_frac * 100.0:.0f}%); is "
                  f"FLAGS_prefetch_depth > 0 and the staging thread "
                  f"keeping up? (raise FLAGS_prefetch_depth or speed "
                  f"up the host loader)", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
