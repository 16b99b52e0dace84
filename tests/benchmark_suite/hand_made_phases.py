"""What the engine says on its phases since PR 36, laid over a hand-made
trace of a serving program (`hand_made()`'s, which `serving()` built): the
attributes of a prefill round, its launch, a sync and a close-out, and the
two marks.

A file of its own because a PR that is not a `benchmark` PR adds files
here and edits none. The readers' own tests call `say` on a family's
trace themselves (test_benchmark_program_marks.py); the pair cases of
test_benchmark_manifest.py read `serving()`'s trace as it is, so no
manifest can list a reader of these lines until a `benchmark` PR folds
them into `serving()` (PERF.md section 7).
"""
from benchmark_suite_helpers import MS

ROUND = {"rows_held": 3}
LAUNCH = {"prompt_tokens": 300, "padded_tokens": 512}
TOKENS = 32


def _give(ev, **attrs):
    ev[3:] = [dict(ev[3] if len(ev) > 3 else {}, **attrs)]


def say(raw):
    """`raw` with: on a prefill round (the family's own
    `serving.prefill_batch`, or one round the prefill program, 9-31 ms)
    ROUND, and a `serving.prefill.launch` with LAUNCH in its first 2 ms; a
    second `serving.decode.launch` 82-84, `serving.decode.sync` 84-88 and a
    `serving.close` 88-89 ms with TOKENS, where every family's device is
    idle, so that both sides of both marks hold idle time; in every launch
    a `serving.dispatch` mark at its middle; on every sync `fetches` (the
    tokens, the emits and one for each count the burst's `serving.emit`
    carries) and a `serving.fetched` mark half a millisecond before its
    end."""
    events = raw["planes"][1]["lines"][0]["events"]
    counts = max((len(ev[3]) for ev in events
                  if ev[0] == "serving.emit" and len(ev) > 3), default=0)
    if not any(ev[0] == "serving.prefill_batch" for ev in events):
        events.append(["serving.prefill_batch", 9 * MS, 22 * MS, {}])
    events += [["serving.decode.launch", 82 * MS, 2 * MS, {}],
               ["serving.decode.sync", 84 * MS, 4 * MS, {}],
               ["serving.close", 88 * MS, MS, {"tokens": TOKENS}]]
    for ev in list(events):
        name, start, length = ev[:3]
        if name == "serving.prefill_batch":
            _give(ev, **ROUND)
            events.append(["serving.prefill.launch", start, 2 * MS,
                           dict(LAUNCH)])
        elif name == "serving.decode.launch":
            events.append(["serving.dispatch", start + length // 2, 900, {}])
        elif name == "serving.decode.sync":
            _give(ev, fetches=2 + counts)
            events.append(["serving.fetched", start + length - MS // 2, 900,
                           {}])
    return raw
