"""benchmark/trace_reduce.py and every reader in benchmark/metrics/ on a
hand-made trace whose answers are known, and on a small trace recorded on
the chip (data/recorded_trace.json: `trace_reduce.load()` of a traced run
of the train cell, PR 24, cut to its first events)."""
import json
import os

import pytest

from benchmark_suite_helpers import DATA, REPO, TEST_PEAKS, tiny_cell

from benchmark import manifest, trace_reduce
from benchmark.hostlog import HostLog

MS = 1_000_000  # ns


def _raw():
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
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": dev_ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


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


def _host():
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


def _every_reader():
    names = sorted(f[:-3] for f in os.listdir(
        os.path.join(REPO, "benchmark", "metrics")) if f.endswith(".py"))
    assert len(names) >= 14
    return names


@pytest.mark.parametrize("name", _every_reader())
def test_every_reader_on_the_hand_made_trace(name):
    read = manifest.load_reader(name, os.path.join(REPO, "benchmark"))
    serve = tiny_cell("tiny-gpt.tiny-open")
    train = tiny_cell("tiny-gpt.tiny-train")
    raw = _raw()
    raw["planes"][0]["lines"][0]["events"].append(
        ["jit_pure_step(9)", 75 * MS, 10 * MS])
    reduced = trace_reduce.reduce(raw)
    cell = train if name.endswith("train") or name.startswith("train") \
        else serve
    value = read(reduced, _host(), cell)
    assert value is not None and value >= 0
    if name.endswith("_roofline") or "mfu" in name:
        assert 0 < value
    # a reader that finds nothing to read returns nothing, never 0
    empty = trace_reduce.reduce({"planes": []})
    nothing = read(empty, HostLog(), cell)
    assert nothing is None or name == "compiles_in_window"
    assert read(None, HostLog(), cell) is None


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
