#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process, which takes the chip itself and starts no other. It builds the
cell's system with weights made from the seed, warms every shape the cell's
traffic can meet (all of that is `setup_s`), measures for `--seconds`, reads
the devices' peak memory, frees the program's state, holds what the timed
path produced against the plain reference, and prints as its LAST stdout
line one JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`
(and `breakdown` in a traced run), then `checks` (each number compared,
beside its limit), which are also the last lines on stderr.

`--trace 0` reports the cell's end-to-end metrics with the profiler off;
`--trace 1` traces a few seconds inside the window and reports the cell's
per-layer metrics over that traced part. It exits non-zero, printing no
result, when jax finds no TPU, a device the peak table lacks, or fewer
chips than the cell asks for. It sets no `FLAGS_*`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest, program_trace, trace_reduce  # noqa: E402
from benchmark.hostlog import HostLog  # noqa: E402
from benchmark.tracer import Tracer  # noqa: E402

TRACE_DIR = os.path.join(manifest.ROOT, ".bench_trace")


def say(text):
    print(text, file=sys.stderr, flush=True)


def take_devices(cell):
    """The devices this run measures on, or SystemExit: no fallback."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise SystemExit(
            f"benchmark: jax found no TPU (platform={first.platform!r}); "
            "nothing was measured and nothing is reported")
    if len(devices) < cell.chips:
        raise SystemExit(f"benchmark: {cell.name} needs {cell.chips} chips, "
                         f"jax sees {len(devices)}")
    cell.peaks = manifest.load_peaks(first.device_kind)
    return devices


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def read_trace(path):
    """The ONE parse of the file a traced run left: the reduced trace
    (`trace_reduce.reduce`) with, under `program`, what the program says
    of itself in the same data (`program_trace.parse`: its spans, the idle
    time charged to them, and ONE pass over the operations for the scopes'
    and the finer paths' seconds). Every reader is handed this."""
    raw = program_trace.load(path)
    reduced = trace_reduce.reduce(raw)
    reduced["program"] = program_trace.parse(raw, reduced)
    return reduced


def measure(cell, seed, seconds, trace, devices, t_start=T_START,
            trace_dir=TRACE_DIR):
    """Build, warm, measure, free, compare. Returns the result object."""
    from benchmark import program

    program.configure_compile_cache()
    driver = manifest.load_driver(cell.mix["kind"])
    log = HostLog(annotate=bool(trace))
    system = driver.build(cell, seed)
    driver.warm(system, log)
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.1f} s")

    tracer = None
    if trace:
        length = float(cell.mix["trace_seconds"])
        tracer = Tracer(trace_dir, start_at=max(0.0, (seconds - length) / 2),
                        length=length, sync=getattr(system, "sync", None))
    driver.window(system, seconds, log, tick=tracer.tick if tracer else None)
    if tracer:
        tracer.stop()
    values, attempted, failed = driver.end_to_end(system, seconds, log)
    values["setup_s"] = setup_s
    used = devices[:cell.chips]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak(used)}

    driver.release(system)
    checks = driver.check(system, log)
    checks.append(("requests_failed", failed, 0))
    correct = all(row[2] is None or row[1] <= row[2] for row in checks)
    for row in checks:
        if len(row) > 3:
            say(f"note {row[0]}: {row[3]}")

    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    if tracer:
        reduced = read_trace(tracer.xplane_path())
        host = log.between(tracer.t0, tracer.t1)
        metrics = {}
        for m in cell.per_layer:
            value = manifest.load_reader(m["name"])(reduced, host, cell)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_seconds(reduced)
        device["window_s"] = reduced["window_s"]
        # the idle time under the program's own span names, the rest under
        # the harness's: the `idle_pct.*` readers' charge, listed
        result.update(metrics=metrics, device=device,
                      breakdown=trace_reduce.breakdown(
                          reduced,
                          *program_trace.idle_charge(reduced["program"])))
    else:
        result.update(
            metrics={m["name"]: {"value": values[m["name"]],
                                 "unit": m["unit"]}
                     for m in cell.end_to_end if m["name"] in values},
            device=device)
    result["checks"] = {row[0]: {"value": row[1], "limit": row[2]}
                        for row in checks}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    devices = take_devices(cell)
    result = measure(cell, args.seed, args.seconds, args.trace, devices)
    for name, row in result["checks"].items():
        say(f"check {name}: {row['value']} (limit {row['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
