"""benchmark/trace_reduce.py and the readers of the harness's own counts on
a hand-made trace whose answers are known (benchmark_suite_helpers.py
`gpt_raw`), and on a small trace recorded on the chip
(data/recorded_trace.json: a traced run of the train cell, PR 24, cut to
its first events). Which reader reads a value in which cell is
test_benchmark_manifest.py's to ask, of the manifest."""
import json
import os

import pytest

from benchmark_suite_helpers import DATA, REPO, TEST_PEAKS, tiny_cell
from benchmark_suite_helpers import gpt_host as _host
from benchmark_suite_helpers import gpt_raw as _raw

from benchmark import manifest, trace_reduce

MS = 1_000_000  # ns


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    r = trace_reduce.reduce(_raw())
    assert r["window_s"] == pytest.approx(0.100)
    dev = r["devices"][0]
    # 0-5, 10-40, 50-70, 95-100
    assert dev["busy_s"] == pytest.approx(0.060)
    assert trace_reduce.busy_seconds(r) == pytest.approx(0.060)
    gaps = sorted(dev["gaps"])
    assert [round(g[1], 6) for g in gaps] == [0.005, 0.010, 0.025]
    assert [round(g[0], 6) for g in gaps] == [0.005, 0.040, 0.070]


def test_time_per_module():
    r = trace_reduce.reduce(_raw())
    assert trace_reduce.module_seconds(r, "pure_burst") == \
        (pytest.approx(0.030), 1)
    assert trace_reduce.module_seconds(r, "pure_prefill") == \
        (pytest.approx(0.020), 1)
    assert trace_reduce.module_seconds(r, "pure_step") == (0.0, 0)
    assert trace_reduce.module_name("jit_pure_burst(123)") == "jit_pure_burst"


def test_idle_gaps_are_charged_to_what_the_host_was_doing():
    r = trace_reduce.reduce(_raw())
    charged = dict(trace_reduce.attribute_gaps(r))
    # 5-10 ms: its middle (7.5) is before any span; 40-50: middle 45 is in
    # the second step; 70-95: middle 82.5 is in the idle span
    assert charged == {"(none)": pytest.approx(0.005),
                       "step": pytest.approx(0.010),
                       "idle": pytest.approx(0.025)}
    b = trace_reduce.breakdown(r)
    assert b["idle_gaps"][0][0] == "idle" and len(b["device_ops"]) <= 10
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.020)]


def test_without_the_window_span_the_device_events_bound_the_window():
    raw = _raw()
    raw["planes"] = raw["planes"][:1]
    r = trace_reduce.reduce(raw)
    assert r["window_s"] == pytest.approx(0.110)
    assert trace_reduce.reduce({"planes": []})["devices"] == []


def test_known_values_of_the_readers():
    base = os.path.join(REPO, "benchmark")
    serve = tiny_cell("tiny-gpt.tiny-open")
    reduced = trace_reduce.reduce(_raw())
    host = _host()

    def read(name):
        return manifest.load_reader(name, base)(reduced, host, serve)

    assert read("gen_lag_p95_ms") == pytest.approx(2.9)
    assert read("batch_occupancy_pct") == pytest.approx(62.5)
    assert read("kv_pages_used_pct") == pytest.approx(30.0)
    assert read("compiles_in_window") == 0
    # 30 ms of burst module, one execution of a 4-step burst
    assert read("decode_step_ms") == pytest.approx(7.5)
    assert read("device_idle_pct.serve") == pytest.approx(40.0)
    assert manifest.load_reader("dispatch_ms.train", base)(
        reduced, host, serve) == pytest.approx(5.0)
    from benchmark import flops

    cfg = serve.config
    ops = flops.prefill_flops(cfg, 20) + flops.prefill_flops(cfg, 30)
    assert read("prefill_roofline") == pytest.approx(
        100 * ops / TEST_PEAKS["bf16_flops_per_s"] / 0.020)
    assert read("mfu.prefill") == pytest.approx(
        100 * ops / TEST_PEAKS["bf16_flops_per_s"] / 0.100)
    ops += 8 * flops.decode_flops(cfg, 25) + 4 * flops.decode_flops(cfg, 30)
    assert read("mfu.serve") == pytest.approx(
        100 * ops / TEST_PEAKS["bf16_flops_per_s"] / 0.100)
    need = flops.roofline_seconds(
        1.5 * flops.decode_flops(cfg, 40 / 1.5),
        flops.decode_bytes(cfg, 40), TEST_PEAKS)
    assert read("decode_roofline") == pytest.approx(100 * 4 * need / 0.030)


def test_on_a_trace_recorded_on_the_chip():
    path = os.path.join(DATA, "recorded_trace.json")
    with open(path) as f:
        raw = json.load(f)
    r = trace_reduce.reduce(raw)
    assert r["devices"] and r["devices"][0]["name"] == "/device:TPU:0"
    dev = r["devices"][0]
    assert 0 < dev["busy_s"] <= r["window_s"]
    step = [n for n in dev["modules"] if "pure_step" in n]
    assert step, dev["modules"].keys()
    secs, runs = trace_reduce.module_seconds(r, "pure_step")
    assert runs >= 1 and 0 < secs <= dev["busy_s"] * 1.001
    assert {s[0] for s in r["host_spans"]} >= {"step"}
    assert trace_reduce.breakdown(r)["device_ops"]
