"""MFU operating-point sweep for the llama train configurations.

MFU on a v5e-class chip is mostly a function of how much arithmetic each
compiled step amortizes over its fixed overheads (dispatch, HBM traffic
per token), so the operating point has to be found empirically: this
tool sweeps (batch, seq, remat, scan_layers, fused_ce) combos through
`bench.py` itself — one measurement codepath, no duplicated flop
accounting — and writes every row as it lands, so a sweep cut short
keeps its partial results.

One process per chip: this parent never imports jax, so it never holds
the chip, and it runs the combos ONE AT A TIME, each in a subprocess
with a hard timeout. A combo that OOMs or fails to compile is recorded
as an error row (bench.py exits non-zero and prints no metric line)
without killing the sweep.

Usage (through the chip tool):
    python tools/mfu_sweep.py [--model base|long|1b] [--budget 1800]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

_PEAKS = None


def load_device_peaks():
    """The shared per-chip peak table
    (paddle_tpu/observability/device_peaks.py), loaded by file path so
    this subprocess driver never pays the framework/jax import. ONE
    table for bench.py, PerfMeter, the stepledger roofline, and this
    sweep — tests/test_stepledger.py pins that they agree."""
    import importlib.util

    path = os.path.join(REPO, "paddle_tpu", "observability",
                        "device_peaks.py")
    spec = importlib.util.spec_from_file_location(
        "_mfu_sweep_device_peaks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _peaks():
    global _PEAKS
    if _PEAKS is None:
        _PEAKS = load_device_peaks()
    return _PEAKS

# sweep grids per model size: batch up => more arithmetic per dispatch;
# seq up => attention flops grow but so does the causal discount; remat
# trades flops for HBM headroom at the big points; scan_layers shrinks the
# compiled program
GRIDS = {
    "base": [
        # (batch, seq, recompute, scan_layers, fused_ce_chunks)
        (32, 1024, 0, 0, 0),   # the measured optimum (bench default)
        (32, 1024, 0, 0, 8),   # fused-CE control at the same point
        (64, 1024, 0, 0, 8),   # the OOM point, logits chunked away
        (128, 1024, 0, 0, 16),
        (64, 2048, 0, 0, 16),
    ],
    # long-context rows on the base geometry: seq >= 4096 engages the
    # Pallas flash dispatch (kernels/flash_attention.py `use_flash`)
    # inside the FULL train step; fused CE keeps the f32 logits
    # from OOMing at 8k+ tokens x 32k vocab
    "long": [
        (8, 4096, 0, 0, 16),
        (4, 4096, 0, 0, 16),   # smaller-batch fallback if 8x4096 OOMs
        (4, 8192, 0, 0, 16),
        (4, 8192, 1, 0, 16),   # remat headroom variant
        (2, 16384, 1, 0, 32),  # deep flash regime
    ],
    "1b": [
        # the grid recorded at 993efc6 (MFU_SWEEP.json); hidden 2048 x
        # seq 2048 compiles under the current toolchain (chip_smoke.py
        # trains that shape), so seq-2048 rows can join it
        (8, 1024, 0, 1, 0),    # full 0.74B model at seq 1024
        (16, 1024, 0, 1, 0),
        (16, 1024, 0, 1, 8),
        (8, 1024, 0, 0, 0),    # unrolled control (scan cost check)
        (16, 512, 0, 1, 0),
        (16, 1024, 1, 1, 0),   # remat headroom probe
    ],
}


def run_combo(model, batch, seq, recompute, scan, fused_ce, timeout):
    env = dict(
        os.environ,
        BENCH_CONFIG="llama",
        BENCH_MODEL="base" if model == "long" else model,
        BENCH_BATCH=str(batch), BENCH_SEQ=str(seq),
        BENCH_RECOMPUTE=str(recompute), BENCH_SCAN_LAYERS=str(scan),
        BENCH_FUSED_CE=str(fused_ce),
    )
    row = {"model": model, "batch": batch, "seq": seq,
           "recompute": recompute, "scan_layers": scan,
           "fused_ce": fused_ce}
    t0 = time.perf_counter()
    try:
        r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                           env=env, timeout=timeout, capture_output=True,
                           text=True)
    except subprocess.TimeoutExpired:
        row["error"] = f"timeout after {timeout:.0f}s"
        return row
    row["elapsed_s"] = round(time.perf_counter() - t0, 1)
    line = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ""
    try:
        res = json.loads(line)
    except ValueError:
        res = None
    if r.returncode != 0 or not isinstance(res, dict) \
            or "metric" not in res:
        # bench.py failed (no TPU, OOM, compile error): non-zero exit,
        # no metric line — the message is at the end of stderr
        row["error"] = (f"rc={r.returncode}: "
                        + (r.stderr or "no output")[-400:])
        return row
    extra = res.get("extra", {})
    row.update(tok_per_sec_chip=res["value"], mfu=extra.get("mfu"),
               loss_last=extra.get("loss_last"))
    # bench reports the per-chip peak it used (the shared device_peaks
    # table); annotate the achieved TFLOPs and flag any drift between
    # the measurement codepath and the table this sweep was built on
    peak = extra.get("peak_flops_per_chip")
    if peak:
        row["peak_tflops_bf16"] = round(peak / 1e12, 1)
        table = _peaks()
        if peak not in table.PEAK_FLOPS_BF16.values():
            row["peak_table_mismatch"] = True
        if row.get("mfu"):
            row["achieved_tflops_per_chip"] = round(
                row["mfu"] * peak / 1e12, 2)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="base", choices=sorted(GRIDS))
    ap.add_argument("--budget", type=float, default=1800.0,
                    help="total seconds across all combos")
    ap.add_argument("--per-combo-timeout", type=float, default=420.0)
    # chiprun_out/ is what the chip tool brings back; the root-level
    # MFU_SWEEP.json is the dated 993efc6 record and is not written
    ap.add_argument("--json", default=os.path.join(
        REPO, "chiprun_out", "mfu_sweep.json"))
    ap.add_argument("--require-success", action="store_true",
                    help="exit 1 unless at least one combo produced a "
                         "TPU measurement")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)

    deadline = time.monotonic() + args.budget
    out = {"model": args.model, "rows": []}
    # merge with an existing sweep file so base + 1b runs accumulate
    if os.path.exists(args.json):
        try:
            with open(args.json) as f:
                prev = json.load(f)
            out["rows"] = [r for r in prev.get("rows", [])
                           if r.get("model") != args.model]
        except (OSError, ValueError):
            pass

    for combo in GRIDS[args.model]:
        remaining = deadline - time.monotonic()
        if remaining < 30:
            print(f"budget exhausted before {combo}", file=sys.stderr)
            break
        row = run_combo(args.model, *combo,
                        timeout=min(args.per_combo_timeout, remaining))
        out["rows"].append(row)
        print(json.dumps(row), file=sys.stderr)
        tmp = args.json + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1)
        os.replace(tmp, args.json)

    ok = [r for r in out["rows"]
          if r.get("mfu") and r.get("model") == args.model]
    if ok:
        best = max(ok, key=lambda r: r["mfu"])
        print(json.dumps({"best": best}))
    else:
        print(json.dumps({"best": None, "note": "no successful TPU rows"}))
        if args.require_success:
            sys.exit(1)


if __name__ == "__main__":
    main()
