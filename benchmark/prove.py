#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from ("How correct is
decided", steps 2-5): on the chip, at the cell's own size, over several
seeds in ONE process (set-up is paid once where the cell allows it).

    python benchmark/prove.py --workload <cell> --seeds 11,12,13 \
        --seconds 12 --out chiprun_out/prove.jsonl

For each seed it prints one JSON line: the program's reading of every
number compared (against the plain reference), the CONTROL's reading (the
reference computed in int8 and in fp8, put in the program's place), and for
a training cell the planted faults' readings (half of the batch left out; a
step that returns its state unchanged). The benchmark's own runs never run
this; the limits in `benchmark/cells/<cell>.json` are set from its output.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest, run  # noqa: E402
from benchmark.hostlog import HostLog  # noqa: E402

CONTROL_MODES = ("int8", "fp8")


def prove_serve(cell, driver, seeds, seconds):
    from benchmark import program, traffic, weights
    
    system = driver.build(cell, seeds[0])
    driver.warm(system, HostLog())
    hi_p = traffic.length_support(cell.mix["prompt_tokens"])[1]
    hi_o = traffic.length_support(cell.mix["output_tokens"])[1]
    for seed in seeds:
        system.seed = seed
        program.set_weights(system.model, cell.config, seed)
        system.engine.refresh_params()
        log = HostLog()
        driver.window(system, seconds, log)
        values, attempted, failed = driver.end_to_end(system, seconds, log)
        sample = driver.sample_for_check(
            system.records, cell.mix["check_requests"], seed)
        tree = weights.make(cell.config, seed, cell.config["dtype"])
        row = {"seed": seed, "attempted": attempted, "failed": failed,
               "metrics": values,
               "compiles_in_window": log.counts["compiles_in_window"]}
        for mode in ("f32",) + CONTROL_MODES:
            t0 = time.perf_counter()
            gap, compared = driver.logit_gaps(
                cell.config, seed, sample, hi_p + hi_o, hi_o, mode, tree)
            row[f"logit_gap_max.{'program' if mode == 'f32' else mode}"] \
                = gap
            row["tokens_compared"] = compared
            row[f"seconds.{mode}"] = time.perf_counter() - t0
        del tree
        yield row


def prove_train(cell, driver, seeds, seconds):
    for seed in seeds:
        system = driver.build(cell, seed)
        driver.warm(system, HostLog())
        got = system.readings
        batches = system.first_batches
        driver.release(system)
        cfg, trainer = cell.config, cell.config["trainer"]
        t0 = time.perf_counter()
        ref = driver.reference_readings(cfg, trainer, seed, batches)
        row = {"seed": seed, "seconds.reference": time.perf_counter() - t0,
               "losses.program": got["losses"],
               "losses.reference": ref["losses"]}

        def add(label, readings):
            for r in driver.compare(readings, ref, {}):
                row[f"{r[0]}.{label}"] = r[1]
                if len(r) > 3:
                    row[f"{r[0]}.{label}.leaf"] = r[3]

        add("program", got)
        for mode in CONTROL_MODES:
            add(mode, driver.reference_readings(cfg, trainer, seed, batches,
                                                mode=mode))
        half = list(range(len(batches[0][0]) // 2))
        add("half_batch", driver.reference_readings(
            cfg, trainer, seed, batches, rows=half))
        add("frozen_state", driver.reference_readings(
            cfg, trainer, seed, batches, frozen=True))
        yield row


PROVERS = {"serve": prove_serve, "train": prove_train}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload)
    run.take_devices(cell)
    from benchmark import program

    program.configure_compile_cache()
    driver = manifest.load_driver(cell.mix["kind"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for row in PROVERS[cell.mix["kind"]](cell, driver, seeds, args.seconds):
        line = json.dumps({"workload": cell.name, **row})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
