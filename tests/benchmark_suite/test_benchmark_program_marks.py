"""The scheduler's readers of PR 36 (benchmark/program_marks.py `READERS`):
what a prefill round costs the rows it holds up, how much of a round is
padding, and the idle time inside `serving.decode.sync` /
`serving.decode.launch` cut at the marks `serving.fetched` /
`serving.dispatch`, on hand-made traces written with xplane_writer.py. No
manifest lists them yet, so they are read here by name from `READERS`, and
a family's hand-made trace gains what the engine now says
(hand_made_phases.py `say`) here and nowhere else."""
import json
import os
import subprocess
import sys

import pytest

from benchmark_suite_helpers import (MS, REPO, hand_made, planes,
                                     traced)  # noqa: F401
from hand_made_phases import say

from benchmark import manifest, program_marks, program_trace, trace_reduce
from benchmark.hostlog import HostLog

READERS = tuple(program_marks.READERS)
IDLE_PCT = ("idle_pct.prefill", "idle_pct.kv_scatter",
            "idle_pct.decode_launch", "idle_pct.emit", "idle_pct.outside")
# the cells that run the engine: those `device_idle_pct.serve` lists
SERVING_CELLS = sorted(next(
    p for p in manifest.load_manifest(REPO)["per_layer"]
    if p["name"] == "device_idle_pct.serve")["workloads"])
US = 1_000  # ns


def _read(name, reduced, cell=None):
    if name in program_marks.READERS:
        return program_marks.READERS[name](reduced)
    return manifest.load_reader(name)(
        reduced, HostLog(), cell or manifest.load_cell(SERVING_CELLS[0]))


def _raw(host, busy=((10, 20), (40, 20))):
    """Window 0..100 ms, one device busy over `busy` [(start, length) ms]
    (a prefill, a burst), and `host` as the program's spans."""
    modules = [[f"jit_pure_{'burst' if i else 'prefill'}({i + 1})",
                int(a * MS), int(n * MS)] for i, (a, n) in enumerate(busy)]
    ops = [[f"fusion.{i}", m[1], m[2], "jit(x)/mlp/dot_general"]
           for i, m in enumerate(modules)]
    return planes(modules, ops,
                  [["bench.traced_window", 0, 100 * MS, {}]] + host)


def _rounds(*rounds):
    """Prefill rounds [(start ms, length ms, rows_held)] of one prompt."""
    return [["serving.prefill_batch", a * MS, n * MS, {"rows_held": rows}]
            for a, n, rows in rounds]


@pytest.mark.parametrize("host, stall, longest", [
    # two rounds of 30 ms x 3 rows and 10 ms x 0 rows over 60 tokens
    (_rounds((5, 30, 3), (50, 10, 0))
     + [["serving.close", 70 * MS, MS, {"tokens": 60}]], 1.5, 30.0),
    # the tokens of every close-out, the rows of every round
    (_rounds((5, 10, 2), (20, 20, 8))
     + [["serving.close", 70 * MS, MS, {"tokens": 16}],
        ["serving.close", 80 * MS, MS, {"tokens": 24}]], 4.5, 20.0),
    # an engine that was empty at every round stalled nobody
    (_rounds((5, 30, 0)) + [["serving.close", 70 * MS, MS, {"tokens": 8}]],
     0.0, 0.0),
    # no round in the traced part: no stall, and no round to be the longest
    ([["serving.close", 70 * MS, MS, {"tokens": 8}]], 0.0, None)])
def test_what_a_prefill_costs_the_rows_it_holds_up(traced, host, stall,
                                                   longest):
    reduced = traced(_raw(host))
    assert _read("prefill_stall_ms_per_token", reduced) == \
        pytest.approx(stall)
    assert _read("prefill_round_ms_max", reduced) == \
        (None if longest is None else pytest.approx(longest))


@pytest.mark.parametrize("launches, pad", [
    ([(300, 512)], 100 * (1 - 300 / 512)),
    # the sums' ratio, not the mean of the ratios
    ([(256, 256), (100, 1024)], 100 * (1 - 356 / 1280)),
    ([(512, 512)], 0.0)])
def test_the_share_of_a_round_that_is_padding(traced, launches, pad):
    host = [["serving.prefill.launch", (5 + 10 * i) * MS, 2 * MS,
             {"prompt_tokens": true, "padded_tokens": padded}]
            for i, (true, padded) in enumerate(launches)]
    assert _read("prefill_pad_pct", traced(_raw(host))) == pytest.approx(pad)


def _burst(start, fetches, fetched_at=None, dispatch_at=None):
    """A launch of 4 ms and a sync of 10 ms from `start` ms on, with the
    marks at `dispatch_at` / `fetched_at` ms where given."""
    host = [["serving.decode.launch", start * MS, 4 * MS, {}],
            ["serving.decode.sync", (start + 4) * MS, 10 * MS,
             {"fetches": fetches}]]
    if dispatch_at is not None:
        host.append(["serving.dispatch", int(dispatch_at * MS), US, {}])
    if fetched_at is not None:
        host.append(["serving.fetched", int(fetched_at * MS), US, {}])
    return host


def test_a_gap_that_straddles_a_mark_is_divided_at_it(traced):
    """The device is idle 0-12, 22-40 and 52-100 ms. The first launch 8-12
    is idle throughout and dispatches at 9: 1 ms before, 3 after. The first
    sync 12-22 holds no idle time. The second launch 36-40 dispatches at
    39.5: 3.5 and 0.5; the second sync 40-50 is busy. The third launch
    50-54 (idle from 52) dispatches at 53: 1 and 1; the third sync 54-64
    is idle throughout and has its tokens at 61: 7 and 3. A fourth launch
    and sync 70-84 hold no mark and are in neither side."""
    host = (_burst(8, 4, fetched_at=21, dispatch_at=9)
            + _burst(36, 8, fetched_at=49, dispatch_at=39.5)
            + _burst(50, 12, fetched_at=61, dispatch_at=53)
            + _burst(70, 12))
    reduced = traced(_raw(host, busy=((12, 10), (40, 12))))
    assert _read("launch_idle_ms.prepare", reduced) == \
        pytest.approx((1 + 3.5 + 1) / 3)
    assert program_marks.cut_idle_ms(
        reduced, *program_marks.LAUNCH, program_marks.AFTER) == \
        pytest.approx((3 + 0.5 + 1) / 3)
    assert program_marks.cut_idle_ms(
        reduced, *program_marks.SYNC, program_marks.BEFORE) == \
        pytest.approx(7 / 3)
    assert _read("sync_idle_ms.rest", reduced) == pytest.approx(3 / 3)
    # the two sides cut where the clocks meet, as one number
    assert _read("wake_dispatch_idle_ms", reduced) == \
        pytest.approx((3 + 0.5 + 1) / 3 + 7 / 3)
    assert _read("sync_fetches_per_burst", reduced) == pytest.approx(9.0)
    # the spans without a mark keep their idle time in `idle_by_span`
    idle = program_trace.current(reduced)["idle_by_span"]
    assert idle["serving.decode.sync"] == pytest.approx(0.020)
    assert idle["serving.decode.launch"] == pytest.approx(0.014)


@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_the_sums_on_a_familys_hand_made_trace(cell, traced):
    """By construction: the three idle times of a burst are the idle time
    charged to `serving.decode.sync` and `serving.decode.launch`; and the
    five `idle_pct.*` still sum to `device_idle_pct.serve`, which a child
    span in place of a mark would have broken."""
    cell = manifest.load_cell(cell)
    raw, _host = hand_made(cell)
    reduced = traced(say(raw))
    read = {n: _read(n, reduced, cell) for n in READERS + IDLE_PCT}
    assert all(v is not None for v in read.values()), read
    idle = program_trace.current(reduced)["idle_by_span"]
    n = len(program_marks.spans(reduced, "serving.decode.sync"))
    assert n == len(program_marks.spans(reduced, "serving.decode.launch")) \
        and n >= 2
    burst = ("sync_idle_ms.rest", "launch_idle_ms.prepare",
             "wake_dispatch_idle_ms")
    assert all(read[name] > 0 for name in burst)
    assert sum(read[name] for name in burst) * n == pytest.approx(
        1e3 * (idle["serving.decode.sync"] + idle["serving.decode.launch"]),
        rel=1e-9)
    assert sum(read[n] for n in IDLE_PCT) == pytest.approx(
        _read("device_idle_pct.serve", reduced, cell), rel=1e-9)
    # the tokens, the emits, and a read for each count the family hands on
    counts = max(len(a) for a in program_trace.marks(
        program_trace.current(reduced), "serving.emit"))
    assert read["sync_fetches_per_burst"] == 2 + counts


@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_a_familys_hand_made_trace_as_it_is_reads_none(cell, traced):
    """`serving()`'s trace has the phases and none of what the engine says
    on them since PR 36: the pair cases of test_benchmark_manifest.py would
    fail for every reader here, which is why no manifest lists one yet."""
    raw, _host = hand_made(manifest.load_cell(cell))
    reduced = traced(raw)
    assert {n: _read(n, reduced) for n in READERS} == dict.fromkeys(READERS)


def _parents():
    """The phases of a program before PR 36: no attribute, no mark."""
    host = [["serving.prefill_batch", 5 * MS, 30 * MS, {}],
            ["serving.prefill.launch", 5 * MS, 4 * MS, {}],
            ["serving.decode.launch", 36 * MS, 4 * MS, {}],
            ["serving.decode.sync", 40 * MS, 22 * MS, {}],
            ["serving.emit", 62 * MS, 3 * MS, {"attn_pages_read": 10}],
            ["serving.close", 65 * MS, MS, {}]]
    return _raw(host)


@pytest.mark.parametrize("name", READERS)
def test_without_the_attributes_and_marks_nothing_is_read(name, traced):
    """No trace, an empty one, the trace of a program without phases, and
    the parent's (phases without the attributes and marks): None, never
    0."""
    read = program_marks.READERS[name]
    assert read(None) is None
    assert read(trace_reduce.reduce({"planes": []})) is None
    assert read(traced(_raw([]))) is None
    assert read(traced(_parents())) is None


@pytest.mark.parametrize("host", [
    # half of what a reader needs: no value, no raise
    _rounds((5, 30, 3)),
    [["serving.prefill_batch", 5 * MS, 30 * MS, {}],
     ["serving.close", 70 * MS, MS, {"tokens": 60}]],
    _rounds((5, 30, 3)) + [["serving.close", 70 * MS, MS, {"tokens": 0}]],
    [["serving.prefill.launch", 5 * MS, MS, {"prompt_tokens": 7}]],
    _burst(50, 4, dispatch_at=10, fetched_at=30)])   # marks outside them
def test_half_of_what_a_reader_needs_reads_none(host, traced):
    reduced = traced(_raw(host))
    for name in ("prefill_stall_ms_per_token", "prefill_pad_pct",
                 "sync_idle_ms.rest", "wake_dispatch_idle_ms"):
        assert _read(name, reduced) is None


def test_the_command_reads_the_file_a_traced_run_left(traced, tmp_path):
    """`python benchmark/program_marks.py <directory>`: one JSON line with
    every reader's value from the newest `.xplane.pb` under the directory,
    as a traced run of `run.py` leaves it; a directory without one exits
    non-zero and prints no line."""
    raw, _host = hand_made(manifest.load_cell(SERVING_CELLS[0]))
    reduced = traced(say(raw))      # written where a session puts its file
    command = [sys.executable,
               os.path.join(REPO, "benchmark", "program_marks.py")]
    out = subprocess.run(command + [str(tmp_path)], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    said = json.loads(out.stdout.splitlines()[-1])
    assert said == {n: pytest.approx(_read(n, reduced)) for n in READERS}
    out = subprocess.run(command + [str(tmp_path / "nothing")],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and not out.stdout
    assert "no traced run" in out.stderr
