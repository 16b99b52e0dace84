"""benchmark/program_trace.py and the readers built on it: on a hand-made
trace whose answers are known, on a small trace recorded on the chip
(data/recorded_program_trace.json: `program_trace.load()` of a traced run
of each cell, PR 25, cut down as its `how` says), and on the parent's
recorded trace, which has no span and no scope of the program's.

A reader is handed the reduced trace with the program's side under
`program`, both from ONE parse of the file the traced run left
(`run.read_trace`). The tests that call a reader therefore write their
trace as such a file (xplane_writer.py) and go the whole way: `load`,
`op_names`, `reduce`, `parse`, `current`."""
import json
import os

import pytest

from benchmark_suite_helpers import DATA, REPO
from benchmark_suite_helpers import traced as traced_run  # noqa: F401
from xplane_writer import write

from benchmark import manifest, program_trace, trace_reduce

MS = 1_000_000  # ns


def _a_cell_of_kind(kind):
    return next(w["name"] for w in manifest.load_manifest(REPO)["workloads"]
                if manifest.load_cell(w["name"]).mix["kind"] == kind)


SERVE_CELL, TRAIN_CELL = _a_cell_of_kind("serve"), _a_cell_of_kind("train")
# every per-layer metric of BENCHMARK.json that reads the program's own
# spans and scopes, with the cell of the fixture it is read from
NEW = {m["name"]: ("serve" if SERVE_CELL in m["workloads"] else "train")
       for m in manifest.load_manifest(REPO)["per_layer"]
       if m["name"].split(".")[0] in ("queue_wait_p50_ms", "idle_pct",
                                      "builds_in_trace", "decode_ms",
                                      "train_ms")}


def _raw():
    """Window 0..100 ms, one host thread, one device.

    Host: bench.step 5-60 holds serving.admit 6-8 (with a mark inside),
    serving.prefill_batch 10-40 (launch 10-14, kv_scatter 14-36, sync
    36-39) and
    serving.decode.launch 42-50; serving.decode.sync 50-58; bench.idle
    62-90; a compile mark at 95. Device: the prefill program 12-16, three
    scatters inside kv_scatter (17-19, 22-24, 30-32), the burst 48-60
    whose `while` holds two scoped children and one the compiler left
    unnamed."""
    host = [
        ["bench.traced_window", 0, 100 * MS],
        ["bench.step", 5 * MS, 55 * MS],
        ["serving.admit", 6 * MS, 2 * MS, {}],
        ["serving.admitted#rid=7,queued_us=1500,requeue=0#", 7 * MS, 900],
        ["serving.prefill_batch", 10 * MS, 30 * MS, {}],
        ["serving.prefill.launch", 10 * MS, 4 * MS, {}],
        ["serving.kv_scatter", 14 * MS, 22 * MS, {}],
        ["serving.prefill.sync", 36 * MS, 3 * MS, {}],
        ["serving.decode.launch", 42 * MS, 8 * MS, {}],
        ["serving.decode.sync", 50 * MS, 8 * MS, {}],
        ["bench.idle", 62 * MS, 28 * MS],
        ["jit.build", 95 * MS, 1000, {"kind": "cache_load", "seconds": 2.5}],
    ]
    p = "jit(pure_burst)/while/body/closed_call/"
    ops = [
        ["fusion.9", 12 * MS, 4 * MS, "jit(pure_prefill)/mlp/dot_general"],
        ["copy.1", 17 * MS, 2 * MS, ""],
        ["copy.2", 22 * MS, 2 * MS, ""],
        ["copy.3", 30 * MS, 2 * MS, ""],
        ["while.4", 48 * MS, 12 * MS, "jit(pure_burst)/while"],
        ["fusion.1", 49 * MS, 5 * MS, p + "attn/kv_write/scatter"],
        ["fusion.2", 54 * MS, 2 * MS, p + "mlp/dot_general;mlp/add"],
        ["maximum_convert_fusion.7", 56 * MS, 3 * MS,
         "jit(pure_burst)/while"],
    ]
    modules = [["jit_pure_prefill(11)", 12 * MS, 4 * MS],
               ["jit_scatter(12)", 17 * MS, 2 * MS],
               ["jit_scatter(12)", 22 * MS, 2 * MS],
               ["jit_scatter(12)", 30 * MS, 2 * MS],
               ["jit_pure_burst(13)", 48 * MS, 12 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            _as_loaded(*ev) for ev in host]}]}]}


def _as_loaded(name, start, duration, stats=None):
    """A host event as `program_trace.load` hands it on."""
    name, attrs = program_trace.split_attrs(name, stats or {})
    return [name, start, duration, attrs]


def _reduced(raw):
    """What `run.py` hands a reader beside it: trace_reduce's own view."""
    return trace_reduce.reduce({"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
            for ln in p["lines"]]} for p in raw["planes"]]})


def _parse(raw):
    return program_trace.parse(raw, _reduced(raw))


def _recorded(which):
    with open(os.path.join(DATA, "recorded_program_trace.json")) as f:
        return json.load(f)[which]


def _parent():
    """PR 24's recorded trace of the train cell, as PR 24's program would
    have left it in a file: its operations have an `op_name` (jax gives
    every operation one) and none holds a scope."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        raw = json.load(f)
    for plane in raw["planes"]:
        for line in plane["lines"]:
            if line["name"] == trace_reduce.OP_LINE:
                for ev in line["events"]:
                    ev.append("jit(pure_step)/jvp()/dot_general")
    return raw


def test_spans_nest_by_containment_on_their_thread():
    spans = _parse(_raw())["spans"]
    depth = {(s["name"], round(1e3 * s["start_s"])): s["depth"]
             for s in spans}
    assert depth[("serving.admit", 6)] == 0
    assert depth[("serving.admitted", 7)] == 1
    assert depth[("serving.prefill_batch", 10)] == 0
    assert {depth[(n, t)] for n, t in (("serving.prefill.launch", 10),
                                       ("serving.kv_scatter", 14),
                                       ("serving.prefill.sync", 36))} == {1}
    assert depth[("serving.decode.launch", 42)] == 0
    # the harness's own spans are not the program's
    assert not [s for s in spans if s["name"].startswith("bench.")]
    # a span that began before the window is kept, cut to it
    raw = _raw()
    window = raw["planes"][1]["lines"][0]["events"][0]
    window[1:3] = [7 * MS + MS // 2, 90 * MS]
    first = _parse(raw)["spans"][0]
    assert (first["name"], first["start_s"]) == ("serving.admit", 0.0)
    assert first["end_s"] == pytest.approx(0.0005)


def test_attributes_arrive_as_stats_or_in_the_name(tmp_path):
    parsed = _parse(_raw())
    assert program_trace.marks(parsed, "serving.admit") == [{}]
    assert program_trace.marks(parsed, "serving.admitted") == \
        [{"rid": 7, "queued_us": 1500, "requeue": 0}]
    # read from a file, both ways: an event's own stats (a whole number,
    # a fraction, a string), and a name that kept TraceMe's encoding
    host = [["serving.admitted#rid=7,queued_us=1500,requeue=0#", 7 * MS,
             900],
            ["jit.build", 9 * MS, 900, {"kind": "cache_load",
                                        "seconds": 2.5, "n": -3}]]
    loaded = program_trace.load(write({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}, tmp_path))
    assert loaded["planes"][0]["lines"][0]["events"] == [
        ["serving.admitted", 7 * MS, 900,
         {"rid": 7, "queued_us": 1500, "requeue": 0}], host[1]]
    assert program_trace.split_attrs("jit.build#kind=compile,seconds=0.5#",
                                     {"kind": "cache_load"}) == \
        ("jit.build", {"kind": "cache_load", "seconds": 0.5})
    assert program_trace.split_attrs("serving.close", {}) == \
        ("serving.close", {})
    # on the recorded trace they are the events' stats
    admitted = program_trace.marks(_parse(_recorded("serve")),
                                   "serving.admitted")
    assert admitted == [{"rid": 165, "queued_us": 66, "requeue": 0}]


def test_idle_is_charged_to_the_innermost_span_by_overlap():
    parsed = _parse(_raw())
    idle = {k: round(1e3 * v, 6) for k, v in parsed["idle_by_span"].items()}
    # busy: 12-16, 17-19, 22-24, 30-32, 48-60. The gap 0-12 crosses the
    # window's start (0-5, nothing), bench.step alone (5-6, 8-10),
    # serving.admit (6-8; the mark inside it charges nothing) and
    # prefill.launch (10-12)
    assert idle == {
        "(outside)": 5 + 1 + 2 + 2 + 40,          # ..., 40-42, 60-100
        "serving.admit": 2.0,
        "serving.prefill.launch": 2.0,
        "serving.kv_scatter": 1 + 3 + 6 + 4,      # 16-17, 19-22, 24-30, 32-36
        "serving.prefill.sync": 3.0,              # 36-39
        "serving.prefill_batch": 1.0,             # 39-40: its own rest
        "serving.decode.launch": 6.0,             # 42-48
    }
    assert sum(idle.values()) == pytest.approx(
        1e3 * (parsed["window_s"] - 0.022))
    # only the harness's span covers 8-10 ms: that is outside the program
    gap_in_bench_step_only = program_trace._charge(
        [[0.008, 0.002]], parsed["spans"])
    assert gap_in_bench_step_only == ({"(outside)": pytest.approx(0.002)},
                                      [[0.008, pytest.approx(0.002)]])
    # the stretches no span of the program covers are handed on whole
    assert sorted([round(1e3 * a, 6), round(1e3 * n, 6)]
                  for a, n in parsed["idle_outside"]) == [
        [0, 6], [8, 2], [40, 2], [60, 40]]


def test_scope_seconds_are_self_time_by_module():
    parsed = _parse(_raw())
    burst = parsed["scope_seconds"]["jit_pure_burst"]
    # the while's 12 ms less its children's 10 stay unscoped, and so does
    # the fusion the compiler named after the while
    assert burst == {None: pytest.approx(0.002 + 0.003),
                     "attn": pytest.approx(0.005),
                     "mlp": pytest.approx(0.002)}
    assert parsed["scope_seconds"]["jit_pure_prefill"] == \
        {"mlp": pytest.approx(0.004)}
    # a module none of whose operations carries a scope is left out
    assert "jit_scatter" not in parsed["scope_seconds"]
    assert program_trace.scope_ms(parsed, r"pure_burst", "attn", 2) == \
        pytest.approx(2.5)
    assert program_trace.rest_ms(parsed, r"pure_burst",
                                 ("attn", "mlp", "head"), 2) == \
        pytest.approx(6.0 - 2.5 - 1.0)
    assert program_trace.scope_ms(parsed, r"pure_step", "attn") is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(pure_step)/transpose(jvp(attn))/dot_general", "attn"),
    ("jit(pure_step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general",
     "mlp"),
    ("jit(pure_step)/jvp(head)/dot_general", "head"),
    ("jit(pure_step)/optimizer/convert_element_type", "optimizer"),
    ("jit(pure_burst)/while/body/closed_call/attn/kv_write/scatter",
     "attn"),
    ("jit(pure_burst)/while/body/closed_call/head/sample/argmax", "head"),
    ("jit(pure_step)/transpose(jvp(embed))/jit(_take)/scatter-add",
     "embed"),
    ("attn/kv_write/squeeze;attn/squeeze", "attn"),
    ("jit(pure_burst)/while", None),
    ("params['gpt.layers.3.mlp.fc_in.weight']", None),
    ("", None),
])
def test_the_top_level_scope_of_an_op_name(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


@pytest.mark.parametrize("which", ["hand-made", "serve", "train"])
def test_a_file_reads_back_as_the_trace_it_was_written_from(which,
                                                            traced_run):
    """`load` on a file in the chip's format (xplane_writer.py): every
    span with its attributes, every operation with the `op_name` of its
    event metadata, found by the program it ran in."""
    raw = _raw() if which == "hand-made" else _recorded(which)
    reduced = traced_run(raw)
    path = traced_run.path
    assert program_trace.load(path) == raw
    table = program_trace.op_names(path)
    named = [ev for p in raw["planes"] for ln in p["lines"]
             if ln["name"] == trace_reduce.OP_LINE
             for ev in ln["events"] if ev[3]]
    assert named and set(table.values()) == {ev[3] for ev in named}
    # the whole HLO line names the event, with the number of its program
    assert all(isinstance(program, int) and line.startswith("%")
               for program, line in table)
    # and what a reader is given is what `parse` makes of the same data
    assert {k: v for k, v in reduced.items() if k != "program"} \
        == _reduced(raw)
    assert program_trace.current(reduced) == _parse(raw)


def test_the_same_name_in_two_programs_keeps_each_op_name(traced_run):
    raw = _raw()
    modules, ops = (ln["events"] for ln in raw["planes"][0]["lines"])
    modules.append(["jit_pure_step(14)", 70 * MS, 10 * MS])
    ops.append(["fusion.1", 71 * MS, 8 * MS,
                "jit(pure_step)/jvp(head)/dot_general"])
    parsed = program_trace.current(traced_run(raw))
    assert parsed["scope_seconds"]["jit_pure_step"] == \
        {"head": pytest.approx(0.008)}
    assert parsed["scope_seconds"]["jit_pure_burst"]["attn"] == \
        pytest.approx(0.005)


def test_an_op_name_that_moved_is_an_error_not_an_empty_scope(traced_run):
    """Where libtpu keeps `op_name` is read off a raw trace, not promised:
    operations with metadata and no `tf_op` among it must not read as a
    program without scopes."""
    with pytest.raises(RuntimeError, match="tf_op"):
        traced_run(_raw(), tf_op=False)
    with pytest.raises(RuntimeError, match="tf_op"):
        program_trace.op_names(traced_run.path)


def test_the_recorded_trace_keeps_both_sums(traced_run):
    """The five idle shares sum to the device's idle share, the four
    decode times to the decode step, each as its accepted reader gives
    it. `idle_pct.outside` and `decode_ms.other` are remainders, so the
    sums hold by construction; what this pins is that both sides cut the
    same window and count the same executions."""
    reduced = traced_run(_recorded("serve"))
    cell = manifest.load_cell(SERVE_CELL)

    def read(name):
        return manifest.load_reader(name)(reduced, None, cell)

    shares = [read(n) for n in NEW if n.startswith("idle_pct.")]
    assert len(shares) == 5 and min(shares) > 0
    assert sum(shares) == pytest.approx(read("device_idle_pct.serve"),
                                        abs=0.1)
    # the page scatter holds it: what the chip showed (PERF.md section 5)
    assert read("idle_pct.kv_scatter") > 0.9 * sum(shares)
    steps = [read(n) for n in NEW if n.startswith("decode_ms.")]
    assert len(steps) == 4 and min(steps) > 0
    assert sum(steps) == pytest.approx(read("decode_step_ms"), abs=0.1)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_on_the_recorded_trace_and_on_the_parents(metric,
                                                         traced_run):
    which = NEW[metric]
    cell = manifest.load_cell(SERVE_CELL if which == "serve" else TRAIN_CELL)
    read = manifest.load_reader(metric)
    reduced = traced_run(_recorded(which))
    value = read(reduced, None, cell)
    assert value is not None and value >= 0
    expected = {"queue_wait_p50_ms": 0.066, "builds_in_trace": 0,
                "idle_pct.kv_scatter": 100 * 0.406162 / 0.485,
                "decode_ms.attn": 1e3 * 0.013785 / 32,
                "train_ms.head": 34.875, "train_ms.optimizer": 4.396}
    if metric in expected:
        assert value == pytest.approx(expected[metric], rel=1e-3)
    # nor does a reader that got no trace look for a file
    assert read(None, None, cell) is None
    # the parent's program has no phases and no scopes: nothing to read,
    # and nothing raised
    of_parent = traced_run(_parent())
    parent = program_trace.current(of_parent)
    assert parent["spans"] == [] and parent["scope_seconds"] == {}
    assert parent["modules"]
    assert read(of_parent, None, cell) is None


def test_without_the_programs_side_no_value():
    """A reduced trace that `run.read_trace` did not make (trace_reduce's
    view alone) has no `program`: every reader of the program's spans and
    scopes reads nothing, and looks for no file."""
    reduced = _reduced(_recorded("serve"))
    assert program_trace.current(reduced) is None
    assert program_trace.current(None) is None
    for metric, which in NEW.items():
        cell = manifest.load_cell(SERVE_CELL if which == "serve"
                                  else TRAIN_CELL)
        assert manifest.load_reader(metric)(reduced, None, cell) is None


def test_a_build_in_the_traced_part_is_counted(traced_run):
    reduced = traced_run(_raw())
    cell = manifest.load_cell(SERVE_CELL)
    assert manifest.load_reader("builds_in_trace")(reduced, None, cell) == 1
    assert manifest.load_reader("queue_wait_p50_ms")(reduced, None, cell) \
        == 1.5


def test_the_programs_side_rides_on_the_reduced_trace(traced_run):
    reduced = traced_run(_raw())
    assert program_trace.current(reduced) is reduced["program"]
    assert set(reduced["program"]) == {
        "spans", "scope_seconds", "path_seconds", "window_s",
        "idle_by_span", "idle_outside", "modules"}
    assert reduced["program"]["window_s"] == reduced["window_s"]
    assert reduced["program"]["modules"] == reduced["devices"][0]["modules"]
    assert not hasattr(program_trace, "newest")


def test_a_traced_run_parses_its_file_once(traced_run, monkeypatch):
    """`run.read_trace` loads the file; no reader of the cell loads it
    again, or any other."""
    loads = []
    real = program_trace.load
    monkeypatch.setattr(program_trace, "load",
                        lambda path: loads.append(path) or real(path))
    reduced = traced_run(_recorded("serve"))
    cell = manifest.load_cell(SERVE_CELL)
    values = {m["name"]: manifest.load_reader(m["name"])(reduced, None, cell)
              for m in cell.per_layer if m["name"] in NEW}
    assert len(values) >= 11 and None not in values.values()
    assert loads == [traced_run.path]
    assert not hasattr(trace_reduce, "load")


IDLE_SPANS = {
    "idle_pct.prefill": ("serving.prefill_batch", "serving.prefill.launch",
                         "serving.prefill.sync"),
    "idle_pct.kv_scatter": ("serving.kv_scatter",),
    "idle_pct.decode_launch": ("serving.admit", "serving.decode.launch"),
    "idle_pct.emit": ("serving.decode.sync", "serving.emit",
                      "serving.close"),
    "idle_pct.outside": ("step", "idle", "add_request", "(none)")}


@pytest.mark.parametrize("metric", sorted(IDLE_SPANS))
@pytest.mark.parametrize("which", ["hand-made", "serve"])
def test_idle_gaps_and_the_idle_shares_are_two_views_of_one_charge(
        metric, which, traced_run):
    """`breakdown.idle_gaps` names the program's spans: the seconds it
    lists under a metric's spans are that `idle_pct.*` x the window. What
    no span of the program covers is the harness's: `idle` while the
    engine is empty, `step`, `add_request`, `(none)`."""
    reduced = traced_run(_raw() if which == "hand-made"
                         else _recorded("serve"))
    charge = program_trace.idle_charge(reduced["program"])
    listed = trace_reduce.breakdown(reduced, *charge)["idle_gaps"]
    assert len(listed) <= 10 and listed == sorted(listed,
                                                  key=lambda g: -g[1])
    gaps = dict(trace_reduce.attribute_gaps(reduced, *charge, top=None))
    assert listed == [[n, s] for n, s in gaps.items()][:10]
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["devices"][0]["busy_s"])
    assert "(outside)" not in gaps
    share = manifest.load_reader(metric)(reduced, None,
                                         manifest.load_cell(SERVE_CELL))
    assert sum(gaps.get(n, 0.0) for n in IDLE_SPANS[metric]) \
        == pytest.approx(share * reduced["window_s"] / 100, abs=1e-9)
    assert set(gaps) <= {n for names in IDLE_SPANS.values() for n in names}
    if which == "hand-made":
        # the stretches outside the program's spans, each charged whole
        # to the harness span at its middle: 60-100 ms to `idle` (62-90),
        # 8-10 and 40-42 to `step`, 0-6 to none (`step` starts at 5)
        assert gaps["idle"] == pytest.approx(0.040)
        assert gaps["step"] == pytest.approx(0.004)
        assert gaps["(none)"] == pytest.approx(0.006)


def test_a_program_without_phases_reads_as_the_harness_alone(traced_run):
    """The train cell's program opens no phase: every idle stretch goes to
    the harness's spans, as before the program had any."""
    reduced = traced_run(_recorded("train"))
    assert reduced["program"]["spans"] == []
    charged, stretches = program_trace.idle_charge(reduced["program"])
    assert charged == {}
    both = [trace_reduce.breakdown(reduced, charged, stretches),
            trace_reduce.breakdown(reduced)]
    assert both[0]["device_ops"] == both[1]["device_ops"]
    assert [n for n, _ in both[0]["idle_gaps"]] \
        == [n for n, _ in both[1]["idle_gaps"]] != []
    assert [s for _, s in both[0]["idle_gaps"]] == pytest.approx(
        [s for _, s in both[1]["idle_gaps"]])


def test_op_names_come_from_the_event_metadata_of_a_real_xplane(tmp_path):
    """The wire reader against a file jax itself writes (CPU: no device
    plane, so the table is empty, but every field was walked)."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            with jax.profiler.TraceAnnotation("serving.admitted", rid=3):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert program_trace.op_names(path) == {}
    raw = program_trace.load(path)
    spans = [ev for p in raw["planes"] for ln in p["lines"]
             for ev in ln["events"] if ev[0] == "serving.admitted"]
    assert [ev[3] for ev in spans] == [{"rid": 3}]
    assert _parse(raw)["spans"] == []   # no device: no window


def test_the_scope_names_are_the_programs():
    from paddle_tpu.observability import tracing

    assert program_trace.SCOPES == tracing.SCOPES
