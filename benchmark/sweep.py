#!/usr/bin/env python3
"""Find a serving cell's knee ONCE, by a sweep of fixed rates on the chip.

    python benchmark/sweep.py --workload <cell> --rates 2,3,4,5,6,7 \
        --seconds 30 --seed 5 --out chiprun_out/sweep.jsonl

One process: set-up is paid once, then one window per rate (the engine is
drained between them). A rate is sustained when the backlog does not grow:
the requests still unanswered when the window closes are about what is in
flight at any moment, and the output rate keeps up with what was offered.
The cell's fixed rate (`rate_per_s` in benchmark/cells/<cell>.json) is 0.8 x
the highest sustained rate; the benchmark's runs never search for one.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest, run  # noqa: E402
from benchmark.hostlog import HostLog, percentile  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload)
    run.take_devices(cell)
    from benchmark import program

    program.configure_compile_cache()
    driver = manifest.load_driver(cell.mix["kind"])
    system = driver.build(cell, args.seed)
    driver.warm(system, HostLog())
    for rate in (float(r) for r in args.rates.split(",")):
        cell.params["rate_per_s"] = rate
        log = HostLog()
        driver.window(system, args.seconds, log)
        values, attempted, failed = driver.end_to_end(system, args.seconds,
                                                      log)
        records = system.records
        backlog = sum(1 for r in records
                      if r.last is None or r.last > args.seconds)
        waits = [1e3 * (r.first - r.due) for r in records
                 if r.first is not None]
        offered = sum(r.request.max_new_tokens for r in records) \
            / args.seconds
        row = {"workload": cell.name, "rate_per_s": rate,
               "attempted": attempted, "failed": failed,
               "unanswered_at_close": backlog,
               "offered_out_tokens_per_s": offered,
               "ttft_p50_ms": percentile(waits, 50), **values,
               "compiles_in_window": log.counts["compiles_in_window"]}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
