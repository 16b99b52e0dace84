"""Shared by the benchmark's own tests: the tiny cells under data/, found by
name like any other (which is also the proof that cells are data), a
scripted clock and a scripted engine, and the hand-made traces: one a
family of programs, found by the family's name under `hand_made/` as a
reader and a family's table are found, each written as the `.xplane.pb` a
traced run leaves (xplane_writer.py) and handed to the readers as `run.py`
hands it (`run.read_trace`: the file parsed once)."""
import importlib.util
import itertools
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
HAND_MADE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "hand_made")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from xplane_writer import write  # noqa: E402

from benchmark import manifest, run  # noqa: E402
from benchmark.hostlog import HostLog  # noqa: E402

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


# -- hand-made traces ---------------------------------------------------------
#
# `<family>_raw()` is the trace a family's arithmetic tests know the answers
# of, and stays as those tests need it. `hand_made/<family>.py` is the same
# trace with the rest of what that family's PROGRAM says of itself, so that
# every metric a cell of the family lists finds something to read, and a
# metric of another family's (a scope this program does not name, a count it
# does not hand on) finds nothing. A PR that brings a family brings that
# file (`planes` and `serving` below are there to build it from) and edits
# nothing here.

MS = 1_000_000  # ns
BODY = "jit(pure_burst)/while/body/closed_call/"


@pytest.fixture
def traced(tmp_path):
    """`traced(raw)`: leave `raw` behind as a traced run's file and return
    what `run.py` hands every reader: that file parsed once
    (`run.read_trace`). `traced.path` is the last file written."""
    count = itertools.count()

    def leave(raw, **kw):
        leave.path = write(raw, tmp_path, stamp=f"run_{next(count):02d}",
                           **kw)
        return run.read_trace(leave.path)

    return leave


def planes(modules, ops, host, thread="python3"):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": thread, "events": host}]}]}


def gpt_raw():
    """Window 0..100 ms. Device 0: a burst module 10-40 ms made of two ops
    (10-25, 25-40), a prefill module 50-70 ms (one op), an op that starts
    before the window (-5..5 ms) and one that ends after it (95..105)."""
    dev_ops = [["fusion.1", 10 * MS, 15 * MS], ["fusion.2", 25 * MS, 15 * MS],
               ["convolution.3", 50 * MS, 20 * MS],
               ["fusion.1", -5 * MS, 10 * MS], ["copy.4", 95 * MS, 10 * MS]]
    modules = [["jit_pure_burst(123)", 10 * MS, 30 * MS],
               ["jit_pure_prefill(456)", 50 * MS, 20 * MS]]
    host = [["bench.traced_window", 0, 100 * MS],
            ["bench.step", 8 * MS, 34 * MS], ["bench.add_request", 43 * MS, MS],
            ["bench.step", 45 * MS, 30 * MS], ["bench.idle", 76 * MS, 18 * MS]]
    return planes(modules, dev_ops, host, thread="python")


def gpt_host():
    """The harness's own log of the same window."""
    log = HostLog()
    log.spans = [("step", 0.0, 0.004), ("step", 0.01, 0.016),
                 ("add_request", 0.02, 0.021)]
    log.samples = {
        "gen_lag_s": [(0.0, 0.001), (0.0, 0.003)],
        "occupancy": [(0.0, 0.5), (0.0, 0.75)],
        "pages_used": [(0.0, 0.25), (0.0, 0.35)],
        "prefill": [(0.0, 20), (0.0, 30)],
        "decode": [(0.0, 8, 2, 50), (0.0, 4, 1, 30)]}
    log.counts = {"compiles_in_window": 0}
    return log


def pangu_raw():
    """Window 0..100 ms. The burst 40-60 ms holds a `while` whose body has
    the latent attention's gather (3 ms, with a 1 ms child of its own), the
    router (1 ms), the experts (6 ms), the shared expert (2 ms) and the
    attention's projections (2 ms, `attn` but no finer name); the prefill
    10-30 ms; two emit phases carry the program's counts, one carries
    none."""
    p = BODY
    ops = [
        ["fusion.20", 10 * MS, 20 * MS, "jit(pure_prefill)/mlp/experts/dot"],
        ["while.4", 40 * MS, 20 * MS, "jit(pure_burst)/while"],
        ["fusion.1", 41 * MS, 3 * MS, p + "attn/latent/gather"],
        ["fusion.2", 42 * MS, 1 * MS, p + "attn/latent/dot_general"],
        ["fusion.3", 44 * MS, 2 * MS, p + "attn/dot_general"],
        ["fusion.4", 46 * MS, 1 * MS, p + "mlp/router/top_k"],
        ["fusion.5", 47 * MS, 6 * MS, p + "mlp/experts/dot_general"],
        ["fusion.6", 53 * MS, 2 * MS, p + "mlp/shared/dot_general;mlp/add"],
    ]
    modules = [["jit_pure_prefill(11)", 10 * MS, 20 * MS],
               ["jit_pure_burst(13)", 40 * MS, 20 * MS]]
    counts = {"expert_pairs": 30, "experts_hit": 24,
              "expert_layer_steps": 8, "experts_held": 64}
    host = [["bench.traced_window", 0, 100 * MS, {}],
            ["serving.decode.sync", 40 * MS, 20 * MS, {}],
            ["serving.emit", 61 * MS, 2 * MS, counts],
            ["serving.emit", 70 * MS, 2 * MS, dict(counts, expert_pairs=34)],
            ["serving.emit", 80 * MS, 1 * MS, {}]]
    return planes(modules, ops, host)


def pangu_host():
    log = HostLog()
    log.samples = {"prefill": [(0.0, 12), (0.0, 20)],
                   "decode": [(0.0, 8, 2, 40), (0.0, 4, 1, 30)]}
    return log


PAGE_COUNTS = {"attn_window_pages_read": 90, "attn_window_pages_live": 90,
               "attn_window_pages_context": 240, "attn_pages_read": 80,
               "attn_pages_mapped": 1000}


def _window_and_full(prefill_ops):
    """The burst 40-60 ms (4 steps) holds a `while` whose body has the
    window layers' attention (4 ms), the full layers' (2 ms), the token
    write (1 ms, `attn/kv_write`) and the projections (3 ms, `attn` but no
    finer name); two emit phases carry the program's page counts, one
    carries none."""
    p = BODY
    ops = prefill_ops + [
        ["while.4", 40 * MS, 20 * MS, "jit(pure_burst)/while"],
        ["call.1", 41 * MS, 4 * MS, p + "attn/window/pallas_call"],
        ["call.2", 45 * MS, 2 * MS, p + "attn/full/pallas_call"],
        ["fusion.3", 47 * MS, 1 * MS, p + "attn/kv_write/scatter"],
        ["fusion.4", 48 * MS, 3 * MS, p + "attn/dot_general"],
    ]
    modules = [["jit_pure_prefill(11)", 10 * MS, 20 * MS],
               ["jit_pure_burst(13)", 40 * MS, 20 * MS]]
    host = [["bench.traced_window", 0, 100 * MS, {}],
            ["serving.decode.sync", 40 * MS, 20 * MS, {}],
            ["serving.emit", 61 * MS, 2 * MS, dict(PAGE_COUNTS)],
            ["serving.emit", 70 * MS, 2 * MS,
             dict(PAGE_COUNTS, attn_window_pages_read=120)],
            ["serving.emit", 80 * MS, 1 * MS, {}]]
    return planes(modules, ops, host)


def afmoe_raw():
    """Window 0..100 ms; the prefill program 10-30 ms is the window
    layers' attention; the burst as `_window_and_full` says."""
    return _window_and_full([
        ["fusion.20", 10 * MS, 20 * MS, "jit(pure_prefill)/attn/window/x"]])


def mimo_raw():
    """Window 0..100 ms. The prefill program 10-30 ms holds the full
    layers' attention (8 ms) and the window layers' (2 ms); the burst as
    `_window_and_full` says."""
    return _window_and_full([
        ["call.20", 10 * MS, 8 * MS, "jit(pure_prefill)/attn/full/x"],
        ["call.21", 18 * MS, 2 * MS, "jit(pure_prefill)/attn/window/x"],
        ["fusion.22", 20 * MS, 10 * MS, "jit(pure_prefill)/mlp/dot_general"]])


def without_scopes_and_counts(raw):
    """The same trace of a program without the finer scopes and the counts
    (the parent's, or another family's)."""
    for ev in raw["planes"][0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("/window", "").replace("/full", "")
    for ev in raw["planes"][1]["lines"][0]["events"]:
        ev[3] = {}
    return raw


def train_raw():
    """Window 0..100 ms, one train step 10-90 ms: attention 10-40 of which
    the flash kernels' backward pass 30-40 (`attn/flash`), the MLP 40-70,
    the head 70-80, the optimizer 80-85, an operation the compiler named
    itself 85-90."""
    p = "jit(pure_step)/"
    ops = [["fusion.1", 10 * MS, 20 * MS,
            p + "transpose(jvp(attn))/dot_general"],
           ["call.2", 30 * MS, 10 * MS,
            p + "transpose(jvp(attn))/flash/flash_bwd_dq"],
           ["fusion.3", 40 * MS, 30 * MS, p + "jvp(mlp)/dot_general"],
           ["fusion.4", 70 * MS, 10 * MS, p + "jvp(head)/dot_general"],
           ["fusion.5", 80 * MS, 5 * MS, p + "optimizer/mul"],
           ["copy.6", 85 * MS, 5 * MS, ""]]
    host = [["bench.traced_window", 0, 100 * MS, {}],
            ["bench.step", 5 * MS, 4 * MS, {}],
            ["bench.loss_read", 9 * MS, 85 * MS, {}]]
    return planes([["jit_pure_step(9)", 10 * MS, 80 * MS]], ops, host)


EXPERT_COUNTS = {"expert_pairs": 30, "experts_hit": 24, "experts_read": 24,
                 "expert_rows": 96, "expert_layer_steps": 8,
                 "experts_held": 64}
PREFILL_COUNTS = {"prefill_expert_pairs": 20, "prefill_expert_rows": 256}


def serving(raw, burst_ops=(), counts=None, prefill_counts=None):
    """`raw` with the phases every serving program opens round an
    admission (`serving.admit` with its `serving.admitted` mark,
    `serving.decode.launch`), `burst_ops` (scope paths) as 1 ms operations
    in the burst's last milliseconds, `counts` on every emit phase that
    carries counts and `prefill_counts` on a phase of their own."""
    device, host = raw["planes"]
    ops = device["lines"][1]["events"]
    events = host["lines"][0]["events"]
    for i, path in enumerate(burst_ops):
        ops.append([f"fusion.{90 + i}", (55 + i) * MS, MS, BODY + path])
    for ev in events:
        if ev[0] == "serving.emit" and len(ev) > 3 and ev[3] and counts:
            ev[3].update(counts)
    events += [["serving.admit", 4 * MS, 2 * MS, {}],
               ["serving.admitted", 5 * MS, 900,
                {"rid": 1, "queued_us": 250, "requeue": 0}],
               ["serving.decode.launch", 6 * MS, 2 * MS, {}]]
    if prefill_counts:
        events.append(["serving.emit", 90 * MS, MS, dict(prefill_counts)])
    return raw


def hand_made(cell, directory=HAND_MADE):
    """(raw trace, host log) of a run of `cell`'s family of programs:
    `raw()` and `host()` of `<directory>/<family>.py` (GPT's where the
    configuration names no family; `<family>.train.py` for a mix of kind
    `train`), loaded by path as a reader is."""
    name = cell.config.get("family", "gpt") \
        + (".train" if cell.mix["kind"] == "train" else "")
    spec = importlib.util.spec_from_file_location(
        "hand_made_" + name.replace(".", "_"),
        os.path.join(directory, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.raw(), module.host()
