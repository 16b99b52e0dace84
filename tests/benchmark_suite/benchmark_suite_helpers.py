"""Shared by the benchmark's own tests: the tiny cells under data/, found by
name like any other (which is also the proof that cells are data), a
scripted clock and a scripted engine."""
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, run  # noqa: E402

TEST_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(name):
    cell = manifest.load_cell(name, root=DATA, bench_dir=DATA)
    cell.peaks = dict(TEST_PEAKS)
    return cell


def tiny_measure(name, seed=7, seconds=1.5, trace=0, tmp_path=None):
    """The rest of a run after the look for a chip."""
    import jax

    return run.measure(tiny_cell(name), seed, seconds, trace, jax.devices(),
                       t_start=time.perf_counter(),
                       trace_dir=str(tmp_path) if tmp_path else None)


class Clock:
    """A clock that only moves when told to."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Finished:
    def __init__(self, rid):
        self.request_id = rid


class ScriptedEngine:
    """An engine double: admits up to `slots` requests, each step takes
    `step_s` on the scripted clock (or `stall_s` once, at step number
    `stall_at`) and commits one token to every admitted request. Requests
    whose prompt starts with `drop_token` are swallowed and never answered."""

    def __init__(self, clock, slots=2, step_s=0.01, first_s=0.0,
                 stall_at=None, stall_s=0.0, drop_token=None):
        self.clock, self.slots, self.step_s = clock, slots, step_s
        self.first_s, self.stall_at, self.stall_s = first_s, stall_at, stall_s
        self.drop_token = drop_token
        self.pending, self.active, self.dropped = [], [], []
        self.next_rid = 0
        self.steps = 0
        self.seen = []

    def add_request(self, prompt, max_new_tokens, on_token):
        rid = self.next_rid
        self.next_rid += 1
        self.seen.append((list(prompt), max_new_tokens))
        req = {"rid": rid, "left": max_new_tokens, "cb": on_token, "n": 0}
        if self.drop_token is not None and prompt[0] == self.drop_token:
            self.dropped.append(req)
        else:
            self.pending.append(req)
        return rid

    def has_work(self):
        return bool(self.pending or self.active)

    def step(self):
        self.steps += 1
        dt = self.step_s
        if self.steps == self.stall_at:
            dt += self.stall_s
        while self.pending and len(self.active) < self.slots:
            self.active.append(self.pending.pop(0))
            dt += self.first_s
        self.clock.sleep(dt)
        finished = []
        for req in list(self.active):
            req["cb"](req["rid"], req["n"] % 7)
            req["n"] += 1
            req["left"] -= 1
            if req["left"] == 0:
                self.active.remove(req)
                finished.append(Finished(req["rid"]))
        return finished
