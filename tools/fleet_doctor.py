"""Fleet doctor: one-shot diagnosis + postmortem bundle for a serving
fleet.

Reads a `FLAGS_telemetry_dir` root of `rank_<i>/` shards (or scrapes
live endpoints first, exactly like `fleet_report --scrape`), runs the
full aggregation stack (rank / HBM / ledger / SLO / history tables,
observability/fleet.py) PLUS the anomaly detector engine
(observability/anomaly.py: KV-leak, mean-shift, queue-saturation,
recovery-storm, straggler-drift, and any live canary verdicts the
ranks published at /debug/anomalies), and prints a RANKED DIAGNOSIS:
each verdict with its likely cause and the concrete lever that fixes
it (the `step_ledger.py` advice-table pattern — a report that does not
name the next action is half a report).

`--bundle out.tar.gz` snapshots the whole story into one support
bundle for a postmortem: every rank shard (metrics.prom, trace.json,
history.jsonl, statusz/healthz/readyz.json, stacks.txt when scraped
live), the merged fleet.prom + fleet_trace.json, the rendered report,
and the verdicts + diagnosis as JSON — attach one file to the
incident, not nine terminals of copy-paste.

    python tools/fleet_doctor.py /tmp/ci_fleet
    python tools/fleet_doctor.py /tmp/live --scrape auto --json
    python tools/fleet_doctor.py /tmp/live --scrape r0:9100,r1:9101 \
        --bundle /tmp/postmortem.tar.gz

Exit codes: 0 = diagnosis printed (verdicts or not), 1 =
--fail-above SEV given and a verdict at/above that severity exists
(deploy gate), 2 = no shards found / nothing scraped.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tarfile
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# kind -> (likely cause, fix lever). The doctor's whole value over the
# raw verdict list: an operator paged at 3am reads the RIGHT column.
ADVICE = {
    "kv_leak": (
        "KV / spill-tier occupancy only ever grows: prefix-cache pages "
        "pinned by leaked refcounts, requests that never finish, or a "
        "spill tier admitting faster than it evicts",
        "check serving_prefix_cache_* evictions and the kv_tiers block "
        "in /statusz; cap the tiers (FLAGS_kv_host_cache_mb / "
        "FLAGS_kv_disk_cache_mb) — ROADMAP: tiered KV fabric "
        "promote/evict path"),
    "mean_shift": (
        "a signal's regime changed mid-run (TTFT/load/queue mean "
        "shifted): recompile storm, queue buildup, or a replica "
        "falling out of the fleet",
        "align the shift timestamp with /debug/trace and "
        "compilewatch (FLAGS_compilewatch recompile storms); for TTFT "
        "shifts check chunked prefill (FLAGS_prefill_chunk) and "
        "router shedding — ROADMAP: closed-loop autoscaling consumes "
        "exactly this signal"),
    "queue_saturation": (
        "arrival rate exceeds decode throughput; the admission queue "
        "extrapolates to FLAGS_router_queue_depth and the router will "
        "429-shed",
        "scale out replicas (replica_worker.spawn_replicas + router "
        "auto-discovery; ROADMAP item: autoscaler control loop) or "
        "shed earlier (scheduler_policy=slo, FLAGS_router_admission)"),
    "recovery_storm": (
        "the engine is heal-looping (drain->rebuild->re-admit over "
        "and over): decode OOM storm, donated-buffer faults, or "
        "injected chaos",
        "read the recoveries-per-rank causes in the report and the "
        "flight recorder (serving.recover events); shrink the working "
        "set (max_batch / page_size / FLAGS_kv_host_cache_mb) before "
        "FLAGS_serving_max_recoveries poisons the engine"),
    "straggler_drift": (
        "one rank is persistently slower than the fleet median "
        "(thermal throttling, a noisy neighbor, chaos rank.slow, or "
        "skewed sharding)",
        "cross-check the collective-skew and stepledger-per-rank "
        "tables for the same rank; drain it at the router and compare "
        "its ledger buckets against a healthy peer"),
    "canary_mismatch": (
        "the black-box canary's greedy tokens diverged from the "
        "golden reference: a replica is serving WRONG answers "
        "(weights skew, a bad kernel, quantization drift) "
        "while every internal counter stays green",
        "bit-compare the replica against a reference engine "
        "(tools/serving_parity_smoke.py) and re-verify the checkpoint "
        "digest before trusting this rank again"),
    "canary_timeout": (
        "the canary probe could not complete inside its deadline: "
        "the request plane is wedged or unreachable even if the "
        "process looks alive",
        "pull /debug/stacks on the rank (or the stacks.txt shard in "
        "this bundle) for parked threads; check watchdog stall dumps "
        "and the replica's stderr log; restart the rank if the HTTP "
        "plane is dead"),
}
DEFAULT_ADVICE = (
    "unrecognized verdict kind (a newer detector than this tool)",
    "read the verdict's evidence field and the fleet report sections "
    "above")


def diagnose(verdicts) -> list:
    """Verdicts -> ranked diagnosis rows (severity order preserved)."""
    out = []
    for v in verdicts:
        cause, lever = ADVICE.get(v.get("kind"), DEFAULT_ADVICE)
        out.append({**v, "likely_cause": cause, "lever": lever})
    return out


def format_diagnosis(rows, report) -> str:
    lines = []
    dead = report.get("dead") or []
    missing = report.get("missing") or []
    lines.append("== doctor diagnosis (ranked) ==")
    if not rows and not dead and not missing:
        lines.append("no anomaly verdicts — the fleet looks healthy "
                     "over the sampled window")
        hist = report.get("history") or []
        if not hist:
            lines.append("note: no history.jsonl shards were found, "
                         "so the trend detectors had nothing to read "
                         "— set FLAGS_timeseries_interval_s on the "
                         "workers (or --scrape a live fleet) for "
                         "leak/shift/saturation coverage")
        return "\n".join(lines) + "\n"
    for d in dead:
        lines.append(f"[1.00] rank {d['rank']} DEAD: "
                     + ("never beat — hung before its first step?"
                        if d.get("never_beat") else
                        f"stopped beating at step {d['step']}"))
    for r in missing:
        lines.append(f"[1.00] rank {r} MISSING: declared by the job "
                     f"but wrote no shard")
    for i, d in enumerate(rows, 1):
        lines.append(
            f"{i}. [{d['severity']:.2f}] rank {d['rank']} "
            f"{d['kind']} ({d['metric']}): {d['summary']}")
        lines.append(f"   likely cause: {d['likely_cause']}")
        lines.append(f"   lever: {d['lever']}")
    return "\n".join(lines) + "\n"


def write_bundle(path: str, root: str, report: dict, rows: list,
                 report_text: str) -> list:
    """One postmortem tarball: every shard file under `root` plus the
    doctor's own artifacts. Returns the member names written."""
    members = []
    mode = "w:gz" if path.endswith((".tgz", ".tar.gz")) else "w"
    with tarfile.open(path, mode) as tar:
        for dirpath, _dirs, files in os.walk(root):
            for fname in sorted(files):
                full = os.path.join(dirpath, fname)
                arc = os.path.join(
                    "fleet", os.path.relpath(full, root))
                tar.add(full, arcname=arc)
                members.append(arc)
        with tempfile.TemporaryDirectory() as td:
            extras = {
                "doctor/report.txt": report_text,
                "doctor/diagnosis.json": json.dumps(
                    {"verdicts": rows,
                     "dead": report.get("dead") or [],
                     "missing": report.get("missing") or []},
                    indent=1),
            }
            for arc, text in extras.items():
                tmp = os.path.join(td, os.path.basename(arc))
                with open(tmp, "w") as fh:
                    fh.write(text)
                tar.add(tmp, arcname=arc)
                members.append(arc)
    return members


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("root", help="FLAGS_telemetry_dir root holding "
                                 "rank_<i>/ shards (scrape target dir "
                                 "with --scrape)")
    ap.add_argument("--scrape", default=None, metavar="EP,EP,...",
                    help="live telemetry endpoints (host:port or "
                         "URLs) to pull into the root first — "
                         "/metrics, statusz extras, /debug/timeseries "
                         "history and /debug/stacks per rank; 'auto' "
                         "discovers endpoints from shard heartbeats")
    ap.add_argument("--json", action="store_true",
                    help="emit verdicts + diagnosis as JSON instead "
                         "of text (doctor_smoke parses this)")
    ap.add_argument("--bundle", default=None, metavar="OUT.tar.gz",
                    help="write the one-file postmortem support "
                         "bundle (shards + merged artifacts + this "
                         "diagnosis)")
    ap.add_argument("--fail-above", type=float, default=None,
                    metavar="SEV",
                    help="exit 1 when any verdict's severity is >= "
                         "this (deploy gate, e.g. 0.5)")
    ap.add_argument("--stale-s", type=float, default=None,
                    help="dead-rank heartbeat threshold in seconds "
                         "(default: 3x the declared flush interval)")
    args = ap.parse_args(argv)

    from paddle_tpu.observability import fleet

    if args.scrape:
        if args.scrape.strip().lower() == "auto":
            eps = fleet.endpoints_from_heartbeats(args.root)
            if not eps:
                print(f"fleet_doctor: --scrape auto found no live "
                      f"endpoints under {args.root}", file=sys.stderr)
                return 2
        else:
            eps = [e for e in args.scrape.split(",") if e.strip()]
        scraped = fleet.scrape_to_shards(eps, args.root)
        for _r, v in sorted(scraped.items()):
            if "error" in v:
                print(f"fleet_doctor: scrape of {v['endpoint']} "
                      f"FAILED: {v['error']}", file=sys.stderr)
        if not any("shard" in v for v in scraped.values()):
            print(f"fleet_doctor: none of the {len(eps)} endpoints "
                  f"could be scraped", file=sys.stderr)
            return 2
    report = fleet.aggregate(args.root, stale_s=args.stale_s)
    if not report["shards"]:
        print(f"fleet_doctor: no rank_<i>/ shards under {args.root} "
              f"(was FLAGS_telemetry_dir set, or pass --scrape?)",
              file=sys.stderr)
        return 2
    rows = diagnose(report.get("anomalies") or [])
    report_text = fleet.format_report(report)
    diag_text = format_diagnosis(rows, report)
    if args.json:
        print(json.dumps({
            "root": args.root,
            "ranks": sorted(report["shards"]),
            "dead": report.get("dead") or [],
            "missing": report.get("missing") or [],
            "verdicts": rows,
        }, indent=1))
    else:
        sys.stdout.write(report_text)
        sys.stdout.write("\n" + diag_text)
    if args.bundle:
        members = write_bundle(args.bundle, args.root, report, rows,
                               report_text + "\n" + diag_text)
        print(f"bundle: {args.bundle} ({len(members)} files)",
              file=sys.stderr if args.json else sys.stdout)
    if args.fail_above is not None:
        severe = [d for d in rows
                  if d["severity"] >= args.fail_above]
        dead_or_missing = (report.get("dead") or
                           report.get("missing"))
        if severe or dead_or_missing:
            print(f"fleet_doctor: gate FAILED — "
                  f"{len(severe)} verdict(s) at severity >= "
                  f"{args.fail_above:.2f}"
                  + (", plus dead/missing ranks"
                     if dead_or_missing else ""), file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
