"""Step-time ledger channel (observability/stepledger.py): bucket
reconciliation on the CPU backend, the roofline golden table, the
shared device-peak table (single source of truth with PerfMeter /
bench.py / tools/mfu_sweep.py), fleet ledger-shard round-trip, the
report tools, and the zero-overhead off path."""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import device_peaks as dp
from paddle_tpu.observability import fleet as fleet_mod
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import stepledger as sl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    """Import a repo-root tool module by file path (tools/ is not a
    package)."""
    path = os.path.join(REPO, *name.split("/"))
    spec = importlib.util.spec_from_file_location(
        name.replace("/", "_").replace(".py", ""), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def ledger_on():
    """FLAGS_stepledger on with clean ledger state; restored after."""
    prev = paddle.get_flags(["FLAGS_stepledger",
                             "FLAGS_stepledger_block_every"])
    sl._reset_for_tests()
    paddle.set_flags({"FLAGS_stepledger": True,
                      "FLAGS_stepledger_block_every": 1})
    yield
    paddle.set_flags(prev)
    sl._reset_for_tests()


def _tiny_train_step():
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   build_train_step)

    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                           seq=32)
    m = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=m.parameters())
    return build_train_step(m, opt)


def _tiny_engine(**kw):
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                           seq=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("page_size", 8)
    return ServingEngine(m, **kw), cfg


class TestBuckets:
    def test_synthetic_reconciliation(self, ledger_on):
        snap = sl.begin()
        assert snap is not None
        time.sleep(0.02)
        t_disp = time.perf_counter()
        t2 = sl.end(snap, "unit.step", t_disp, out=None,
                    data_wait=0.005, tokens=10)
        assert t2 >= t_disp
        a = sl.snapshot()["unit.step"]
        assert a["steps"] == 1
        assert a["tokens"] == 10
        total = sum(a["buckets"].values())
        # named buckets + residual reconcile to the measured wall
        assert abs(total - a["wall"]) <= 0.05 * a["wall"] + 1e-6
        assert a["buckets"]["data_wait"] == pytest.approx(0.005)
        assert a["buckets"]["host"] >= 0.015  # the sleep

    def test_trainer_integration_reconciles(self, ledger_on):
        step = _tiny_train_step()
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randint(0, 97, (2, 16)))
        y = paddle.to_tensor(rng.randint(0, 97, (2, 16)))
        for _ in range(3):
            step(x, y)
        snap = sl.snapshot()
        a = snap["train.step"]
        assert a["steps"] == 3
        total = sum(a["buckets"].values())
        assert abs(total - a["wall"]) <= 0.10 * a["wall"] + 1e-6
        # residual is the gauge the CI smoke gates under 25%
        assert a["buckets"]["residual"] <= 0.25 * a["wall"] + 1e-6
        # the registry families exist and agree on step count
        reg = om.default_registry()
        assert reg.value("stepledger_steps_total",
                         entry="train.step") == 3
        # cost_analysis registered via AOT lowering (jit/api.py hook)
        assert a["cost"]["flops"] > 0
        assert a["cost"]["bytes_accessed"] > 0
        assert reg.value("stepledger_flops_per_step",
                         entry="train.step") == a["cost"]["flops"]

    def test_serving_integration_records(self, ledger_on):
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(6), max_new_tokens=4)
        eng.run()
        snap = sl.snapshot()
        assert "serving.decode_step" in snap
        a = snap["serving.decode_step"]
        assert a["steps"] >= 1
        assert a["cost"] is not None  # registered from the decode fn
        total = sum(a["buckets"].values())
        assert abs(total - a["wall"]) <= 0.10 * a["wall"] + 1e-6

    def test_block_every_cadence(self, ledger_on):
        import jax.numpy as jnp

        paddle.set_flags({"FLAGS_stepledger_block_every": 2})
        out = jnp.ones((4,))
        for _ in range(4):
            snap = sl.begin()
            sl.end(snap, "unit.cadence", time.perf_counter(), out=out)
        a = sl.snapshot()["unit.cadence"]
        assert a["steps"] == 4
        assert a["blocked"] == 2  # every 2nd step blocks

    def test_cross_thread_deltas_clamped_to_window(self, ledger_on):
        # the compile/collective sources are process-global counters: a
        # concurrent step on another thread can grow them past THIS
        # entry's dispatch window; the deltas must be capped so the
        # named buckets never exceed the exported wall (no >100%
        # fractions, which the residual gate could never flag)
        reg = om.Registry()
        c = reg.counter("collective_wait_seconds_total", "synthetic",
                        labels=("op",))
        c.labels("all_reduce").inc(5.0)  # >> the ~10ms window
        t0 = time.perf_counter() - 0.01
        sl.end((t0, 0.0, 0.0), "unit.clamp", time.perf_counter(),
               registry=reg)
        a = sl.snapshot()["unit.clamp"]
        total = sum(a["buckets"].values())
        assert total <= a["wall"] + 1e-9
        assert a["buckets"]["collective"] <= a["wall"] + 1e-9

    def test_overlap_efficiency_golden_reconciliation(self, ledger_on):
        # golden overlap attribution (ISSUE 12): a 0.2s raw collective
        # delta against a 0.1s dispatch window means 0.1s was EXPOSED
        # (the bucket) and 0.1s was hidden behind compute — efficiency
        # hidden/raw = 0.5, and the named buckets still reconcile to
        # the exported wall
        reg = om.Registry()
        c = reg.counter("collective_wait_seconds_total", "synthetic",
                        labels=("op",))
        c.labels("all_reduce").inc(0.2)
        t_disp = time.perf_counter()
        t0 = t_disp - 0.1  # window = exactly 0.1s
        sl.end((t0, 0.0, 0.0), "unit.overlap", t_disp, registry=reg)
        a = sl.snapshot()["unit.overlap"]
        assert a["buckets"]["collective"] == pytest.approx(0.1)
        assert a["coll_raw"] == pytest.approx(0.2)
        assert a["coll_hidden"] == pytest.approx(0.1)
        assert reg.value("stepledger_overlap_efficiency",
                         entry="unit.overlap") == pytest.approx(0.5)
        total = sum(a["buckets"].values())
        assert total <= a["wall"] + 1e-9

    def test_overlap_efficiency_zero_when_fully_exposed(self, ledger_on):
        # raw delta fits inside the dispatch window: nothing was
        # hidden, the bucket carries the full delta, efficiency 0.0
        reg = om.Registry()
        c = reg.counter("collective_wait_seconds_total", "synthetic",
                        labels=("op",))
        c.labels("all_reduce").inc(0.05)
        t_disp = time.perf_counter()
        t0 = t_disp - 0.1
        sl.end((t0, 0.0, 0.0), "unit.exposed", t_disp, registry=reg)
        a = sl.snapshot()["unit.exposed"]
        assert a["buckets"]["collective"] == pytest.approx(0.05)
        assert reg.value("stepledger_overlap_efficiency",
                         entry="unit.exposed") == 0.0

    def test_block_every_cadence_is_per_entry(self, ledger_on):
        # two strictly-alternating entries under block_every=2: a
        # PROCESS-global modulus would block one entry always and the
        # other never (its device time landing in residual) — the
        # cadence must be per entry point
        import jax.numpy as jnp

        paddle.set_flags({"FLAGS_stepledger_block_every": 2})
        out = jnp.ones((4,))
        for _ in range(4):
            for entry in ("unit.a", "unit.b"):
                snap = sl.begin()
                sl.end(snap, entry, time.perf_counter(), out=out)
        snap_all = sl.snapshot()
        for entry in ("unit.a", "unit.b"):
            assert snap_all[entry]["steps"] == 4
            assert snap_all[entry]["blocked"] == 2

    def test_mfu_gauge_from_registered_cost(self, ledger_on):
        reg = om.Registry()
        sl.register_cost("unit.mfu", flops=1e9, bytes_accessed=1e6,
                         n_devices=1, peak_flops=1e12, peak_bw=1e11,
                         registry=reg)
        snap = sl.begin()
        time.sleep(0.01)
        sl.end(snap, "unit.mfu", time.perf_counter(), registry=reg)
        mfu = reg.value("stepledger_mfu", entry="unit.mfu")
        a = sl.snapshot()["unit.mfu"]
        expect = 1e9 / (a["wall"] * 1e12)
        assert mfu == pytest.approx(expect, rel=1e-6)


class TestRoofline:
    # golden classification table: (flops, bytes, peak_flops, peak_bw,
    # comm_frac) -> bound. Ridge for the synthetic device = 1e14/1e12
    # = 100 flops/byte.
    GOLDEN = [
        ((1e12, 1e9, 1e14, 1e12, 0.0), "compute-bound"),   # 1000 > 100
        ((1e10, 1e9, 1e14, 1e12, 0.0), "hbm-bound"),       # 10 < 100
        ((1e11, 1e9, 1e14, 1e12, 0.0), "compute-bound"),   # ridge ==
        ((1e12, 1e9, 1e14, 1e12, 0.6), "comms-bound"),     # comm wins
        ((0.0, 1e9, 1e14, 1e12, 0.0), "unknown"),
        ((1e12, 0.0, 1e14, 1e12, 0.0), "unknown"),
        ((1e12, 1e9, 0.0, 1e12, 0.0), "unknown"),
    ]

    def test_classify_golden(self):
        for args, want in self.GOLDEN:
            assert sl.classify(*args) == want, (args, want)

    def test_roofline_row_uses_measured_comm_fraction(self, ledger_on):
        sl.register_cost("unit.roof", flops=1e12, bytes_accessed=1e9,
                         peak_flops=1e14, peak_bw=1e12)
        # a step that is mostly collective wait flips comms-bound
        with sl._lock:
            sl._agg["unit.roof"] = {
                "steps": 1, "wall": 1.0, "tokens": 0, "blocked": 0,
                "buckets": {"compute": 0.3, "host": 0.1,
                            "collective": 0.55, "data_wait": 0.05,
                            "compile": 0.0, "residual": 0.0}}
        row = sl.roofline("unit.roof")
        assert row["bound"] == "comms-bound"
        assert row["comm_fraction"] == pytest.approx(0.55)
        assert row["intensity"] == pytest.approx(1000.0)
        assert row["mfu"] == pytest.approx(1e12 / 1e14)

    def test_device_peaks_single_source_of_truth(self):
        # PerfMeter's table IS the shared table (not a copy)
        from paddle_tpu.profiler import perf_meter

        assert perf_meter.PEAK_FLOPS is dp.PEAK_FLOPS_BF16
        assert perf_meter.detect_peak_flops is dp.detect_peak_flops
        # the corrected public-spec values live exactly once
        assert dp.PEAK_FLOPS_BF16["v5e"] == 197e12
        assert dp.PEAK_HBM_BYTES_PER_S["v5e"] == 819e9
        # bench.py reads the table instead of hardcoding 197e12
        bench_src = open(os.path.join(REPO, "bench.py")).read()
        assert "197e12" not in bench_src
        assert "device_peaks" in bench_src
        # mfu_sweep loads the very same file (importlib, no jax)
        sweep = _load_tool("tools/mfu_sweep.py")
        table = sweep.load_device_peaks()
        assert table.PEAK_FLOPS_BF16 == dp.PEAK_FLOPS_BF16
        assert table.PEAK_HBM_BYTES_PER_S == dp.PEAK_HBM_BYTES_PER_S
        # kind normalization: a generation matches only when the string
        # names it — a bare "TPU v5" is not taken for v5p
        assert dp.normalize_kind("TPU v5 lite") == "v5e"
        assert dp.normalize_kind("TPU v5p") == "v5p"
        assert dp.normalize_kind("TPU v5") is None
        assert dp.normalize_kind("TPU v4") == "v4"
        assert dp.normalize_kind("weird accelerator") is None
        # measurement entry points: an unknown kind is an error, not a
        # default peak (bench.py and chip_smoke.py go through this)
        assert dp.require_kind("TPU v5 lite") == "v5e"
        with pytest.raises(ValueError, match="not in the peak table"):
            dp.require_kind("cpu")
        assert "require_kind" in bench_src


class TestOffPath:
    def test_begin_is_one_flag_read(self):
        assert not sl.enabled()
        assert sl.begin() is None

    def test_serving_off_path_zero_overhead(self):
        assert not sl.enabled()
        reg = om.default_registry()
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(6), max_new_tokens=6)
        eng.run()  # warm
        eng.add_request(np.arange(6), max_new_tokens=6)
        s0 = sl.steps_recorded()
        a0 = reg.allocations
        while eng.has_work():
            eng.step()
        assert sl.steps_recorded() == s0
        assert reg.allocations == a0

    def test_trainer_off_path_zero_overhead(self):
        assert not sl.enabled()
        reg = om.default_registry()
        step = _tiny_train_step()
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randint(0, 97, (2, 16)))
        y = paddle.to_tensor(rng.randint(0, 97, (2, 16)))
        step(x, y)  # warm/compile
        s0 = sl.steps_recorded()
        a0 = reg.allocations
        step(x, y)
        assert sl.steps_recorded() == s0
        assert reg.allocations == a0


class TestFleetRoundTrip:
    def test_ledger_shard_roundtrip(self, ledger_on, tmp_path):
        # a dedicated registry: the process-default one accumulates
        # ledger families across tests in this module
        reg = om.Registry()
        for _ in range(3):
            snap = sl.begin()
            sl.end(snap, "train.step", time.perf_counter(),
                   data_wait=0.001, tokens=32, registry=reg)
        root = str(tmp_path / "fleet")
        exp = fleet_mod.FleetExporter(root, rank=0, world_size=1,
                                      interval=60, registry=reg)
        exp.flush()
        shard = os.path.join(root, "rank_0")
        assert sorted(os.listdir(shard)) == \
            sorted(fleet_mod.SHARD_FILES)
        assert "ledger.prom" in fleet_mod.SHARD_FILES
        text = open(os.path.join(shard, "ledger.prom")).read()
        # ledger families only, every sample rank-labeled
        assert "stepledger_seconds_total" in text
        assert "serving_" not in text
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert 'rank="0"' in line
        rows = fleet_mod.ledger_table({0: shard})
        assert len(rows) == 1 and rows[0]["steps"] == 3
        assert rows[0]["buckets"]["data_wait"] == pytest.approx(
            0.003, abs=1e-4)
        report = fleet_mod.aggregate(root)
        assert report["ledger"] and report["ledger"][0]["rank"] == 0
        txt = fleet_mod.format_report(report)
        assert "step-time ledger per rank" in txt

    def test_rankless_shard_omitted(self, tmp_path):
        # a shard whose run never set FLAGS_stepledger yields no row
        shard = tmp_path / "rank_1"
        shard.mkdir()
        (shard / "ledger.prom").write_text("")
        assert fleet_mod.ledger_table({1: str(shard)}) == []


class TestReportTools:
    def _populated_exposition(self):
        # a dedicated registry keeps this module's other ledger
        # entries out of the exposition under test
        reg = om.Registry()
        for _ in range(2):
            snap = sl.begin()
            time.sleep(0.005)
            sl.end(snap, "train.step", time.perf_counter(),
                   data_wait=0.002, tokens=16, registry=reg)
        return sl.ledger_exposition(reg)

    def test_exposition_roundtrip(self, ledger_on):
        text = self._populated_exposition()
        samples = fleet_mod._parse_prom_samples(text)
        agg = sl.aggregate_from_samples(samples)
        rows = sl.waterfall(agg)
        assert len(rows) == 1 and rows[0]["entry"] == "train.step"
        assert rows[0]["steps"] == 2
        live = sl.waterfall()[0]
        assert rows[0]["wall_s"] == pytest.approx(live["wall_s"],
                                                  rel=1e-6)

    def test_exposition_mfu_matches_gauge_multi_device(self, ledger_on):
        # n_devices must round-trip through the exposition: without the
        # stepledger_n_devices gauge, an MFU recomputed from the .prom
        # ledger is inflated n_devices-fold vs the in-process gauge
        reg = om.Registry()
        sl.register_cost("unit.mfu4", flops=1e9, bytes_accessed=1e6,
                         n_devices=4, peak_flops=1e12, peak_bw=1e11,
                         registry=reg)
        snap = sl.begin()
        time.sleep(0.01)
        sl.end(snap, "unit.mfu4", time.perf_counter(), registry=reg)
        gauge = reg.value("stepledger_mfu", entry="unit.mfu4")
        samples = fleet_mod._parse_prom_samples(
            sl.ledger_exposition(reg))
        agg = sl.aggregate_from_samples(samples)
        cost = agg["unit.mfu4"]["cost"]
        assert cost["n_devices"] == 4
        row = sl.waterfall(agg)[0]
        recomputed = cost["flops"] * row["steps"] / (
            row["wall_s"] * cost["peak_flops"] * cost["n_devices"])
        assert recomputed == pytest.approx(gauge, rel=1e-6)
        # and the CLI report's mfu line uses the same denominator
        text = sl.format_report([row])
        assert f"mfu {recomputed:.3f}" in text

    def test_targets_name_the_roadmap_move(self):
        agg = {"train.step": {
            "steps": 10, "wall": 10.0, "tokens": 0, "blocked": 0,
            "buckets": {"compute": 5.0, "host": 1.0, "collective": 2.2,
                        "data_wait": 1.0, "compile": 0.5,
                        "residual": 0.3},
            "cost": {"flops": 1e10, "bytes_accessed": 1e9,
                     "peak_flops": 1e14, "peak_bw": 1e12,
                     "n_devices": 1}}}
        rows = sl.waterfall(agg)
        tg = sl.targets(rows, top=3)
        assert tg[0]["bucket"] == "compute"
        assert tg[0]["bound"] == "hbm-bound"  # intensity 10 < ridge 100
        assert "ROADMAP item 2" in tg[0]["advice"]
        coll = next(t for t in tg if t["bucket"] == "collective")
        assert coll["share"] == pytest.approx(0.22)
        assert "reduce-scatter" in coll["advice"]
        text = sl.format_report(rows)
        assert "step-time waterfall: train.step" in text
        assert "hbm-bound" in text
        assert "optimization targets" in text

    def test_step_ledger_cli(self, ledger_on, tmp_path, capsys):
        tool = _load_tool("tools/step_ledger.py")
        prom = tmp_path / "metrics.prom"
        prom.write_text(self._populated_exposition())
        assert tool.main([str(prom)]) == 0
        out = capsys.readouterr().out
        assert "step-time waterfall: train.step" in out
        assert "optimization targets" in out
        # --json output parses
        assert tool.main([str(prom), "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["waterfall"][0]["entry"] == "train.step"
        # empty exposition -> exit 2
        empty = tmp_path / "empty.prom"
        empty.write_text("# nothing here\n")
        assert tool.main([str(empty)]) == 2
        # residual gate: a synthetic 50%-unexplained entry fails at 25%
        bad = tmp_path / "bad.prom"
        bad.write_text(
            'stepledger_steps_total{entry="t"} 2\n'
            'stepledger_wall_seconds_total{entry="t"} 1.0\n'
            'stepledger_seconds_total{entry="t",bucket="compute"} 0.5\n'
            'stepledger_seconds_total{entry="t",bucket="residual"} '
            '0.5\n')
        assert tool.main([str(bad), "--max-residual", "0.25"]) == 1
        assert tool.main([str(bad)]) == 0  # no gate, report only
        # a LOST bucket family (partial exposition: wall says 1.0 but
        # the named buckets only account for 0.5, and no residual
        # sample survived) must surface as residual and fail the gate
        # — not silently shrink the waterfall
        lost = tmp_path / "lost.prom"
        lost.write_text(
            'stepledger_steps_total{entry="t"} 2\n'
            'stepledger_wall_seconds_total{entry="t"} 1.0\n'
            'stepledger_seconds_total{entry="t",bucket="compute"} '
            '0.5\n')
        assert tool.main([str(lost), "--max-residual", "0.25"]) == 1

    def test_step_ledger_cli_telemetry_dir(self, ledger_on, tmp_path,
                                           capsys):
        reg = om.Registry()
        snap = sl.begin()
        sl.end(snap, "train.step", time.perf_counter(),
               data_wait=0.001, registry=reg)
        root = str(tmp_path / "fleet")
        fleet_mod.FleetExporter(root, rank=0, world_size=1,
                                interval=60, registry=reg).flush()
        tool = _load_tool("tools/step_ledger.py")
        assert tool.main([root]) == 0
        assert "train.step" in capsys.readouterr().out

    def test_span_bucket_map(self):
        assert sl.bucket_of_span("train.data_wait") == "data_wait"
        assert sl.bucket_of_span("train.step_compute") == "compute"
        assert sl.bucket_of_span("serving.prefill") == "compute"
        assert sl.bucket_of_span("serving.queue") == "host"
        assert sl.bucket_of_span("collective.all_reduce") == \
            "collective"
        assert sl.bucket_of_span("compile.serving.decode") == "compile"
        assert sl.bucket_of_span("dataloader.fetch") == "data_wait"
        assert sl.bucket_of_span("no.such.span") is None

    def test_trace_report_ledger_column(self, ledger_on, tmp_path,
                                        capsys):
        # a train trace + a ledger.prom ALONGSIDE it: the critical path
        # gains the bucket column and the ledger share line
        events = [
            {"name": "train.data_wait", "ph": "X", "ts": 0.0,
             "dur": 100.0, "pid": 1, "tid": 1,
             "args": {"trace_id": 0}},
            {"name": "train.step_compute", "ph": "X", "ts": 100.0,
             "dur": 900.0, "pid": 1, "tid": 1,
             "args": {"trace_id": 0, "step": 1}},
        ]
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps(events))
        (tmp_path / "ledger.prom").write_text(
            self._populated_exposition())
        tool = _load_tool("tools/trace_report.py")
        assert tool.main([str(trace)]) == 0
        out = capsys.readouterr().out
        assert "[compute]" in out
        assert "[data_wait]" in out
        assert "ledger bucket shares" in out
        # a telemetry-dir input: ledgers live in rank_*/ledger.prom
        # (the fleet shard layout) — the bucket column must still
        # appear when the tool is pointed at the ROOT
        root = tmp_path / "telemetry"
        shard = root / "rank_0"
        shard.mkdir(parents=True)
        (shard / "trace.json").write_text(json.dumps(events))
        (shard / "ledger.prom").write_text(
            self._populated_exposition())
        assert tool.main([str(root)]) == 0
        out = capsys.readouterr().out
        assert "[compute]" in out
        assert "ledger bucket shares" in out
        # without the sibling file: unchanged plain output
        bare = tmp_path / "bare"
        bare.mkdir()
        trace2 = bare / "trace.json"
        trace2.write_text(json.dumps(events))
        assert tool.main([str(trace2)]) == 0
        out = capsys.readouterr().out
        assert "[compute]" not in out
