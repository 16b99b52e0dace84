"""The measured window of a serving cell: one thread, one engine, a clock.

`run_window` drives anything with `add_request(prompt, max_new_tokens=,
on_token=)`, `step()` and `has_work()` — the program's `ServingEngine`, or a
scripted double in the tests — so its arithmetic is checked on a scripted
clock without a chip.

Open loop: a request is handed to the engine at the first loop turn at or
after its due time and timed FROM its due time, so a stall (a long step, a
compile) is charged to every request it delayed. Closed loop: a client's
next request is due the moment its last is answered. When the window closes
the engine is stepped on, for at most the mix's `drain_seconds`, until every
request that was due has been answered: an answer that comes late is late
(its latency counts the wait), one that never comes is failed.
"""
from __future__ import annotations

import time

from benchmark.hostlog import percentile


class RequestRecord:
    __slots__ = ("request", "due", "added", "first", "last", "tokens",
                 "done")

    def __init__(self, request, due):
        self.request = request
        self.due = due          # on the window's clock (0 = window start)
        self.added = None
        self.first = None       # time of the first on_token
        self.last = None
        self.tokens = []
        self.done = False


def run_window(engine, requests, seconds, log, drain_seconds=60.0,
               clients=0, clock=time.perf_counter, sleep=time.sleep,
               slot_tokens=None, stats=None, tick=None):
    """Measure for `seconds`. Returns the records of every request that
    became due in the window. `slot_tokens`
    (max_batch x burst) and `stats` (callable -> {"rows", "kv_tokens",
    "pages_used"} of the engine before a step) feed the per-layer samples;
    both optional; `tick(now)` is called every loop turn (the tracer's
    start and stop)."""
    t_open = clock()
    now = lambda: clock() - t_open  # noqa: E731
    records, by_rid = [], {}
    # open loop: requests in due order; closed loop: a queue per client
    queues = {}
    for r in requests if clients else ():
        queues.setdefault(r.client, []).append(r)
    ready = [(0.0, q.pop(0)) for q in queues.values()]
    upcoming = [] if clients else list(requests)

    def on_token(rid, token):
        rec = by_rid[rid]
        t = now()
        if rec.first is None:
            rec.first = t
            log.count("first_tokens")
            log.sample("prefill", (t_open + t, len(rec.request.prompt)))
        rec.last = t
        rec.tokens.append(int(token))
        log.count("tokens_total")
        if t < seconds:
            log.count("tokens_in_window")

    def submit(req, due):
        rec = RequestRecord(req, due)
        with log.span("add_request"):
            rid = engine.add_request(req.prompt,
                                     max_new_tokens=req.max_new_tokens,
                                     on_token=on_token)
        rec.added = now()
        by_rid[rid] = rec
        records.append(rec)
        log.sample("gen_lag_s", (t_open + rec.added, rec.added - due))

    def step():
        before = (log.counts.get("tokens_total", 0)
                  - log.counts.get("first_tokens", 0))
        seen = stats() if stats is not None else None
        with log.span("step"):
            finished = engine.step()
        t = now()
        decoded = (log.counts.get("tokens_total", 0)
                   - log.counts.get("first_tokens", 0)) - before
        if slot_tokens and decoded:
            log.sample("occupancy", (t_open + t, decoded / slot_tokens))
        if seen is not None:
            log.sample("pages_used", (t_open + t, seen["pages_used"]))
            if decoded:
                log.sample("decode", (t_open + t, decoded, seen["rows"],
                                      seen["kv_tokens"]))
        for f in finished:
            rec = by_rid.get(f.request_id)
            if rec is None:
                continue  # left in the engine by an earlier window (sweep)
            rec.done = True
            if clients and t < seconds and queues[rec.request.client]:
                ready.append((t, queues[rec.request.client].pop(0)))

    while now() < seconds:
        t = now()
        if tick is not None:
            tick(t)
        if not clients:
            while upcoming and upcoming[0].due <= t:
                req = upcoming.pop(0)
                ready.append((req.due, req))
        while ready:
            due, req = ready.pop(0)
            submit(req, due)
        if engine.has_work():
            step()
        elif clients:
            break  # every client's queue ran dry: the pool was too small
        else:
            wake = min(upcoming[0].due, seconds) if upcoming else seconds
            with log.span("idle"):
                sleep(min(max(wake - now(), 1e-4), 0.05))
    t_close = now()
    # due inside the window but not yet handed over: still attempted
    for req in upcoming:
        if req.due < seconds:
            submit(req, req.due)
    while engine.has_work() and now() < t_close + drain_seconds:
        with log.span("drain"):
            step()
    return records


def end_to_end(records, seconds, log):
    """The serving end-to-end metrics over ALL requests due in the window
    and all tokens committed inside it; nothing is taken from medians of
    pieces. A request is failed when it was not answered in full."""
    failed = [r for r in records
              if not r.done or len(r.tokens) != r.request.max_new_tokens]
    ttft = [1e3 * (r.first - r.due) for r in records if r.first is not None]
    tpot = [1e3 * (r.last - r.first) / (len(r.tokens) - 1)
            for r in records if r.done and len(r.tokens) > 1]
    out = {"out_tokens_per_s":
           log.counts.get("tokens_in_window", 0) / seconds}
    if ttft:
        out["ttft_p95_ms"] = percentile(ttft, 95)
    if tpot:
        out["tpot_p95_ms"] = percentile(tpot, 95)
    return out, len(records), len(failed)
