#!/usr/bin/env python3
"""The attributes the engine puts on its phases, and a phase cut at a mark.

    python benchmark/program_marks.py [<trace directory or .xplane.pb>]

prints, as one JSON line, what `READERS` read from the file a traced run
left (`--trace 1`; by default the last one's, under `run.TRACE_DIR`).

`program_trace` keeps every span of the program with its start, end and
attributes, and charges each idle instant of the device to the innermost
span that covers it (`idle_by_span`). Two things the scheduler's readers
ask of the same data (PR 36):

- the spans of one name WITH their lengths and attributes (`spans`): a
  prefill round (`serving.prefill_batch`) says how many decoding rows it
  held up (`rows_held`), its launch how many positions it computed for how
  many prompt tokens, a step's close-out how many tokens it committed, a
  burst's sync how many blocking reads it makes;
- the idle time inside a phase on either side of a mark it holds
  (`cut_idle_ms`): `serving.decode.sync` at `serving.fetched` (the tokens
  have arrived), `serving.decode.launch` at `serving.dispatch` (the
  arguments are copied). A mark charges no idle time (`MARK_NS`), it only
  says where to cut, so the two sides of a phase sum to what
  `idle_by_span` charges it and the `idle_pct.*` keep their sum.

A cut between two HOST marks is on one clock. A cut between a host mark
and the device's own start or end of a program (a sync before its
`serving.fetched`, a launch after its `serving.dispatch`) rests on how the
profiler aligned the two clocks, which it does to about a millisecond and
differently in every session (PERF.md section 6, PR 36): those two sides
are given only as their sum (`wake_dispatch_idle_ms`), in which the
alignment cancels.

`READERS` are `read(trace, host, cell)` as a file under `metrics/` has it,
by the name a `benchmark` PR lists each under: no manifest lists them yet
(PERF.md section 7 says which file needs which lines first). A trace of a
program without the attribute or the mark (an older commit's) gives nothing
to read: every reader then returns None.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import program_trace  # noqa: E402

BEFORE, AFTER = "|before", "|after"
SYNC = ("serving.decode.sync", "serving.fetched")
LAUNCH = ("serving.decode.launch", "serving.dispatch")


def spans(reduced, name, attr=None):
    """The program's spans called `name` in the traced part, in time
    order; where `attr` is given, those that carry it."""
    parsed = program_trace.current(reduced)
    return [s for s in (parsed or {"spans": []})["spans"]
            if s["name"] == name and (attr is None or attr in s["attrs"])]


def seconds(span) -> float:
    return span["end_s"] - span["start_s"]


def attr_sum(reduced, name, attr):
    """Sum of `attr` over the `name` spans that carry it; None where none
    does."""
    found = spans(reduced, name, attr)
    return sum(s["attrs"][attr] for s in found) if found else None


def cut_idle_ms(reduced, name, mark, side):
    """The device's idle time inside the `name` spans on one `side` (BEFORE
    or AFTER) of the `mark` each holds, in ms a span. The charge is
    `idle_by_span`'s own (`program_trace._charge` over the reduced trace's
    gaps), made with each such span cut in two at its mark, so a gap that
    straddles the mark is divided at it and the two sides times the spans
    are what `idle_by_span` charges them. A span with no mark inside it is
    left out of both sides; None where no span has one. (A span cut in two
    must hold no other span across its mark: these two hold marks alone.)"""
    parsed = program_trace.current(reduced)
    if not parsed or not reduced["devices"]:
        return None
    at = [(s["line"], s["start_s"]) for s in parsed["spans"]
          if s["name"] == mark]
    cut, n = [], 0
    for s in parsed["spans"]:
        inside = [t for line, t in at if line == s["line"]
                  and s["start_s"] <= t <= s["end_s"]] \
            if s["name"] == name else []
        if inside:
            cut += [dict(s, name=name + BEFORE, end_s=inside[0]),
                    dict(s, name=name + AFTER, start_s=inside[0])]
            n += 1
        else:
            cut.append(s)
    if not n:
        return None
    idle, _ = program_trace._charge(reduced["devices"][0]["gaps"], cut)
    return 1e3 * idle.get(name + side, 0.0) / n


# -- the readers, by the names a manifest would list them under ---------------

def prefill_stall_ms_per_token(trace, host=None, cell=None):
    """Time a decoding row stood still behind somebody else's prefill, per
    token the engine committed: the sum over the prefill rounds
    (`serving.prefill_batch`) of the round's length x the rows it held up
    (`rows_held`: slots that had emitted a token and had more due), over
    the tokens the bursts and steps committed (`tokens` on `serving.close`;
    first tokens are not among them). With every slot decoding it is the
    part of the mean gap between a row's tokens that a prefill put there;
    chunked prefill is aimed at it."""
    rounds = spans(trace, "serving.prefill_batch")
    tokens = attr_sum(trace, "serving.close", "tokens")
    if not tokens or any("rows_held" not in s["attrs"] for s in rounds):
        return None
    return 1e3 * sum(seconds(s) * s["attrs"]["rows_held"]
                     for s in rounds) / tokens


def prefill_round_ms_max(trace, host=None, cell=None):
    """The longest prefill round of the traced part that held up at least
    one decoding row: the longest single gap a stream sees between two of
    its tokens, the number that chunked prefill bounds. A traced part holds
    10-40 rounds, so no percentile; 0 where no round held a row."""
    rounds = spans(trace, "serving.prefill_batch", "rows_held")
    if not rounds:
        return None
    return 1e3 * max((seconds(s) for s in rounds
                      if s["attrs"]["rows_held"] > 0), default=0.0)


def prefill_pad_pct(trace, host=None, cell=None):
    """Share of the positions the prefill programs computed that held no
    prompt token: 100 x (1 - `prompt_tokens` / `padded_tokens`), both
    summed over the `serving.prefill.launch` phases (`padded_tokens` is the
    round's batch bucket x token bucket). `mfu.prefill` and
    `prefill_roofline` count true tokens alone, so this waste is inside
    their readings."""
    padded = attr_sum(trace, "serving.prefill.launch", "padded_tokens")
    true = attr_sum(trace, "serving.prefill.launch", "prompt_tokens")
    if not padded or true is None:
        return None
    return 100.0 * (1.0 - true / padded)


def sync_fetches_per_burst(trace, host=None, cell=None):
    """Blocking device-to-host reads a `serving.decode.sync` makes, at the
    mean: its `fetches` (the tokens, a burst's `emits`, and one for each
    count the program hands on)."""
    syncs = spans(trace, "serving.decode.sync", "fetches")
    if not syncs:
        return None
    return sum(s["attrs"]["fetches"] for s in syncs) / len(syncs)


def sync_idle_ms_rest(trace, host=None, cell=None):
    """Device idle inside a `serving.decode.sync` AFTER its
    `serving.fetched` mark, a burst: the reads that follow the tokens
    (`emits` and one for each of the program's counts)."""
    return cut_idle_ms(trace, *SYNC, AFTER)


def launch_idle_ms_prepare(trace, host=None, cell=None):
    """Device idle inside a `serving.decode.launch` BEFORE its
    `serving.dispatch` mark, a launch: page growth, the launch state and
    the argument copies."""
    return cut_idle_ms(trace, *LAUNCH, BEFORE)


def wake_dispatch_idle_ms(trace, host=None, cell=None):
    """Device idle from a program's end to its tokens' arrival on the host
    (a sync before its `serving.fetched`: the wake-up and the first
    transfer) plus from a launch's `serving.dispatch` to the next
    program's start (the compiled call's own dispatch), a burst. One
    number, because each half is cut where the host's clock meets the
    device's."""
    woke = cut_idle_ms(trace, *SYNC, BEFORE)
    called = cut_idle_ms(trace, *LAUNCH, AFTER)
    return None if woke is None or called is None else woke + called


READERS = {
    "prefill_stall_ms_per_token": prefill_stall_ms_per_token,
    "prefill_round_ms_max": prefill_round_ms_max,
    "prefill_pad_pct": prefill_pad_pct,
    "sync_fetches_per_burst": sync_fetches_per_burst,
    "sync_idle_ms.rest": sync_idle_ms_rest,
    "launch_idle_ms.prepare": launch_idle_ms_prepare,
    "wake_dispatch_idle_ms": wake_dispatch_idle_ms}


def main(argv=None):
    from benchmark import run
    from benchmark.tracer import Tracer

    where = (argv if argv is not None else sys.argv[1:]) or [run.TRACE_DIR]
    path = where[0] if where[0].endswith(".xplane.pb") \
        else Tracer(where[0], 0, 0).xplane_path()
    if not path or not os.path.exists(path):
        raise SystemExit(f"program_marks: no traced run's file under "
                         f"{where[0]!r}")
    reduced = run.read_trace(path)
    print(json.dumps({name: read(reduced)
                      for name, read in READERS.items()}), flush=True)


if __name__ == "__main__":
    main()
