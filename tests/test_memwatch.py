"""Memwatch channel (observability/memwatch.py): HBM watermark gauges,
the live-buffer sweep, static breakdown gauges, the filtered memory
exposition, OOM forensics with serving's preempt-before-poison
degradation, the KV pool histograms, the fleet memory.prom shard +
HBM-skew aggregation, and the zero-overhead off path.
"""
import glob
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import fleet as fleet_mod
from paddle_tpu.observability import memwatch as mw
from paddle_tpu.observability import metrics as om

OOM_MSG = ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
           "123456789 bytes.")


@pytest.fixture
def memwatch_on(tmp_path):
    """FLAGS_memwatch on with dumps routed to tmp; restored after."""
    prev = paddle.get_flags(["FLAGS_memwatch", "FLAGS_memwatch_dump_dir"])
    paddle.set_flags({"FLAGS_memwatch": True,
                      "FLAGS_memwatch_dump_dir": str(tmp_path)})
    yield tmp_path
    paddle.set_flags(prev)


def _tiny_engine(**kw):
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4, seq=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("page_size", 8)
    return ServingEngine(m, **kw), cfg


class TestSampling:
    def test_sample_populates_gauges(self):
        reg = om.Registry()
        out = mw.sample(registry=reg)
        # CPU backend has no allocator stats: the sweep is the source
        assert out["source"] in ("device", "live_sweep")
        names = {f.name for f in reg.families()}
        assert "hbm_bytes_in_use" in names
        assert "hbm_peak_bytes" in names
        assert "live_buffer_bytes" in names
        # peak is monotone across samples (max-of-samples on sweep)
        first_peak = reg.value("hbm_peak_bytes")
        mw.sample(registry=reg)
        assert reg.value("hbm_peak_bytes") >= first_peak

    def test_live_buffer_stats_ranked(self):
        import jax.numpy as jnp

        big = jnp.ones((64, 64), jnp.float32)   # 16 KiB
        small = jnp.ones((4,), jnp.float32)
        lb = mw.live_buffer_stats(top=5)
        assert lb["count"] >= 2
        assert lb["bytes"] >= big.nbytes + small.nbytes
        assert len(lb["top"]) >= 1
        sizes = [r["nbytes"] for r in lb["top"]]
        assert sizes == sorted(sizes, reverse=True)  # largest first
        assert lb["top"][0]["nbytes"] >= 64 * 64 * 4
        del big, small

    def test_breakdown_gauges_and_memory_analysis(self):
        import jax
        import jax.numpy as jnp

        reg = om.Registry()
        mw.record_breakdown(registry=reg, params=1000, kv_pages=500,
                            skipped=None)
        assert reg.value("memwatch_breakdown_bytes",
                         component="params") == 1000
        assert reg.value("memwatch_breakdown_bytes",
                         component="kv_pages") == 500
        # the XLA memory_analysis extraction on a real compiled program
        x = jnp.ones((8, 8), jnp.float32)
        compiled = jax.jit(lambda a: a @ a).lower(x).compile()
        bd = mw.breakdown_from_memory_analysis(compiled)
        assert set(bd) == {"arguments", "outputs", "temps",
                           "generated_code"}
        assert bd["arguments"] == 8 * 8 * 4

    def test_tree_nbytes(self):
        import jax.numpy as jnp

        tree = {"a": jnp.ones((4, 4), jnp.float32),
                "b": [jnp.ones((2,), jnp.float32), 7]}
        assert mw.tree_nbytes(tree) == 4 * 4 * 4 + 2 * 4

    def test_memory_exposition_filtered(self):
        reg = om.Registry()
        mw.sample(registry=reg)
        mw.record_breakdown(registry=reg, params=42)
        reg.counter("serving_tokens_total", "not a memory family").inc()
        text = mw.memory_exposition(reg)
        assert "hbm_bytes_in_use" in text
        assert "memwatch_breakdown_bytes" in text
        assert "serving_tokens_total" not in text
        # const labels stamped (fleet-merge-ready)
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert 'rank="0"' in line

    def test_report_text_shape(self):
        import jax.numpy as jnp

        keep = jnp.ones((32, 32), jnp.float32)
        txt = mw.report_text(top=3)
        assert "live buffers:" in txt
        assert "float32[32x32]" in txt or "top" in txt
        del keep


class TestServingMemwatch:
    def test_kv_histograms_and_breakdown(self, memwatch_on):
        reg = om.default_registry()
        eng, cfg = _tiny_engine()
        # engine construction recorded the static budget
        assert reg.value("memwatch_breakdown_bytes",
                         component="params") > 0
        kv = reg.value("memwatch_breakdown_bytes", component="kv_pages")
        # 2 layers x (k+v) pools of [kvh, n_pages, page, hd] f32
        assert kv == sum(int(p.nbytes)
                         for p in eng.k_pages + eng.v_pages)
        h0 = reg.value("serving_kv_pool_occupancy")
        f0 = reg.value("serving_kv_fragmentation")
        s0 = mw.samples_taken()
        eng.add_request(np.arange(6), max_new_tokens=5)
        eng.run()
        assert reg.value("serving_kv_pool_occupancy") > h0
        assert reg.value("serving_kv_fragmentation") > f0
        assert mw.samples_taken() > s0
        # fragmentation is a ratio
        fam = reg.get("serving_kv_fragmentation")
        _, cell = next(iter(fam.samples()))
        assert 0.0 <= cell.sum <= cell.count

    def test_off_path_zero_overhead(self):
        # FLAGS_memwatch defaults off: a decode loop takes no samples
        # and allocates nothing in the registry (the PR 1 guard pattern)
        assert not mw.enabled()
        reg = om.default_registry()
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(6), max_new_tokens=6)
        eng.run()  # warm
        eng.add_request(np.arange(6), max_new_tokens=6)
        s0 = mw.samples_taken()
        a0 = reg.allocations
        while eng.has_work():
            eng.step()
        assert mw.samples_taken() == s0
        assert reg.allocations == a0


class TestOomForensics:
    def test_is_oom(self):
        assert mw.is_oom(RuntimeError(OOM_MSG))
        assert mw.is_oom(RuntimeError("Out of memory allocating 4 GiB"))

        class ResourceExhaustedError(Exception):
            pass

        assert mw.is_oom(ResourceExhaustedError("boom"))
        assert not mw.is_oom(RuntimeError("INVALID_ARGUMENT: shape"))
        assert not mw.is_oom(ValueError("nope"))

    def test_dump_oom_writes_report(self, memwatch_on):
        reg = om.default_registry()
        d0 = reg.value("memwatch_oom_dumps_total")
        path = mw.dump_oom("unit", exc=RuntimeError(OOM_MSG),
                           extra="== custom section ==\npayload")
        assert os.path.dirname(path) == str(memwatch_on)
        txt = open(path).read()
        assert "OOM forensic dump" in txt
        assert OOM_MSG in txt
        assert "live buffers:" in txt
        assert "== custom section ==" in txt
        assert reg.value("memwatch_oom_dumps_total") == d0 + 1

    @pytest.mark.parametrize("burst", [1, 4])
    def test_transient_oom_preempts_once_and_recovers(self, memwatch_on,
                                                      burst):
        # the graceful-degradation path: first decode OOM -> forensic
        # dump + ONE preemption round; the retry succeeds and the
        # request still completes on the SAME engine (no poison). A
        # single step and a burst are launched by the same code in
        # step(): both are held to it
        reg = om.default_registry()
        p0 = reg.value("serving_preemptions_total")
        eng, cfg = _tiny_engine(decode_burst=burst)
        rid = eng.add_request(np.arange(4), max_new_tokens=6)
        getter = "_get_burst_fn" if burst > 1 else "_get_decode_fn"
        real = getattr(eng, getter)
        state = {"raised": False}

        def flaky(*key):
            fn = real(*key)

            def wrapper(*a, **k):
                if not state["raised"]:
                    state["raised"] = True
                    raise RuntimeError(OOM_MSG)
                return fn(*a, **k)

            return wrapper

        setattr(eng, getter, flaky)
        out = eng.run()
        assert state["raised"]
        assert len(out) == 1 and out[0].request_id == rid
        assert len(out[0].output_ids) == 6
        assert not eng._poisoned
        assert reg.value("serving_preemptions_total") == p0 + 1
        where = "burst_decode" if burst > 1 else "decode"
        dumps = glob.glob(str(memwatch_on / f"oom_serving_{where}_*"))
        assert len(dumps) == 1
        txt = open(dumps[0]).read()
        # the serving dump carries the page-table report
        assert "== kv page table ==" in txt
        assert "pool:" in txt and "slot 0" in txt

    def test_persistent_oom_poisons_after_one_round(self, memwatch_on):
        reg = om.default_registry()
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(4), max_new_tokens=4)

        def always(all_greedy):
            def fn(*a, **k):
                raise RuntimeError(OOM_MSG)

            return fn

        eng._get_decode_fn = always
        # recovery budget 0 = the fail-fast contract: a persistent OOM
        # poisons after ONE preemption round instead of escalating to
        # the drain->rebuild self-heal (README.md "Fault tolerance")
        prev = paddle.get_flags(["FLAGS_serving_max_recoveries"])
        paddle.set_flags({"FLAGS_serving_max_recoveries": 0})
        try:
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                eng.run()
        finally:
            paddle.set_flags(prev)
        # poisoned with the persistence verdict, not a silent crash
        assert eng._poisoned and "preemption round" in eng._poisoned
        assert reg.value("serving_engine_poisoned") == 1.0
        with pytest.raises(RuntimeError, match="poisoned"):
            eng.step()
        # both OOMs produced forensic dumps
        assert len(glob.glob(
            str(memwatch_on / "oom_serving_decode_*"))) == 2

    def test_post_donation_oom_recovers_with_fresh_pools(self,
                                                         memwatch_on):
        # an OOM that already consumed the donated pools cannot retry
        # the dispatch against them: the engine drains, rebuilds the KV
        # pools, and re-admits (README.md "Fault tolerance") — the
        # request completes on the SAME engine, no poison, no raise
        reg = om.default_registry()
        eng, cfg = _tiny_engine()
        rid = eng.add_request(np.arange(4), max_new_tokens=4)
        real = eng._get_decode_fn

        def boom(all_greedy):
            eng._get_decode_fn = real  # re-admit uses the real program

            def fn(params, buffers, k_pages, v_pages, *a, **k):
                for p in list(k_pages) + list(v_pages):
                    p.delete()
                raise RuntimeError(OOM_MSG)

            return fn

        eng._get_decode_fn = boom
        prev = paddle.get_flags(["FLAGS_serving_recovery_backoff_s"])
        paddle.set_flags({"FLAGS_serving_recovery_backoff_s": 0.0})
        try:
            r0 = reg.value("serving_recoveries_total",
                           cause="decode_oom")
            assert eng.step() == []  # drained mid-recovery
            assert not eng._poisoned
            assert eng._recoveries == 1
            assert reg.value("serving_recoveries_total",
                             cause="decode_oom") == r0 + 1
            assert not eng._buffers_deleted(eng.k_pages)
            out = eng.run()  # the drained request re-prefills cleanly
            assert [f.request_id for f in out] == [rid]
            assert len(out[0].output_ids) == 4
        finally:
            paddle.set_flags(prev)
        assert glob.glob(str(memwatch_on / "oom_serving_decode_*"))

    def test_trainer_oom_dump(self, memwatch_on):
        from paddle_tpu.models.trainer import _instrument_step

        def bad_step(x, y):
            raise RuntimeError(OOM_MSG)

        step = _instrument_step(bad_step)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            step(np.zeros((2, 4), np.int64), np.zeros((2, 4), np.int64))
        dumps = glob.glob(str(memwatch_on / "oom_train_step_*"))
        assert len(dumps) == 1
        assert "live buffers:" in open(dumps[0]).read()

    def test_non_oom_failure_keeps_legacy_path(self, memwatch_on):
        # a pre-donation non-OOM failure must NOT preempt or dump — the
        # engine stays live exactly as before this channel existed
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(4), max_new_tokens=4)
        real = eng._get_decode_fn

        def boom_once(all_greedy):
            eng._get_decode_fn = real

            def fn(*a, **k):
                raise RuntimeError("INVALID_ARGUMENT: not a memory issue")

            return fn

        eng._get_decode_fn = boom_once
        with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
            eng.step()
        assert not eng._poisoned
        assert not glob.glob(str(memwatch_on / "oom_*"))
        assert len(eng.run()) == 1


class TestTrainerMemwatch:
    def test_train_step_samples_and_breakdown(self, memwatch_on):
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       build_train_step)

        reg = om.default_registry()
        paddle.seed(0)
        cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                               seq=32)
        m = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters())
        step = build_train_step(m, opt)
        x = paddle.to_tensor(np.random.randint(0, 97, (2, 16)))
        y = paddle.to_tensor(np.random.randint(0, 97, (2, 16)))
        s0 = mw.samples_taken()
        step(x, y)
        step(x, y)
        assert mw.samples_taken() >= s0 + 2
        params_b = reg.value("memwatch_breakdown_bytes",
                             component="params")
        opt_b = reg.value("memwatch_breakdown_bytes",
                          component="optimizer")
        want_params = sum(int(np.prod(p.shape)) * 4
                          for p in m.parameters())
        assert params_b == want_params
        # AdamW: 2 f32 moments per param + scalar state
        assert opt_b >= 2 * want_params


class TestFleetHbm:
    def test_flusher_writes_memory_prom(self, tmp_path):
        reg = om.Registry()
        mw.sample(registry=reg)
        mw.record_breakdown(registry=reg, params=777)
        reg.counter("serving_tokens_total", "full-exposition only").inc()
        exp = fleet_mod.FleetExporter(str(tmp_path), rank=0,
                                      world_size=1, registry=reg)
        exp.flush()
        shard = tmp_path / "rank_0"
        assert sorted(os.listdir(shard)) == sorted(fleet_mod.SHARD_FILES)
        mem = (shard / "memory.prom").read_text()
        assert "hbm_bytes_in_use" in mem
        assert "memwatch_breakdown_bytes" in mem
        assert "serving_tokens_total" not in mem
        full = (shard / "metrics.prom").read_text()
        assert "serving_tokens_total" in full

    def _write_shard(self, root, rank, frac, peak=None, limit=None):
        d = os.path.join(str(root), f"rank_{rank}")
        os.makedirs(d, exist_ok=True)
        lines = ["# HELP hbm_utilization_peak x",
                 "# TYPE hbm_utilization_peak gauge",
                 f'hbm_utilization_peak{{rank="{rank}"}} {frac}']
        if peak is not None:
            lines += ["# TYPE hbm_peak_bytes gauge",
                      f'hbm_peak_bytes{{rank="{rank}"}} {peak}']
        if limit is not None:
            lines += ["# TYPE hbm_bytes_limit gauge",
                      f'hbm_bytes_limit{{rank="{rank}"}} {limit}']
        with open(os.path.join(d, "memory.prom"), "w") as f:
            f.write("\n".join(lines) + "\n")

    def test_hbm_skew_table(self, tmp_path):
        g = 1 << 30
        self._write_shard(tmp_path, 0, 0.70, peak=11 * g, limit=16 * g)
        self._write_shard(tmp_path, 1, 0.71, peak=11 * g, limit=16 * g)
        self._write_shard(tmp_path, 2, 0.92, peak=14 * g, limit=16 * g)
        shards = fleet_mod.discover_shards(str(tmp_path))
        rows = fleet_mod.hbm_table(shards)
        assert [r["rank"] for r in rows] == [0, 1, 2]
        assert rows[2]["peak_frac"] == 0.92
        skew = fleet_mod.hbm_skew(rows)
        assert skew["median_frac"] == 0.71
        assert [r["rank"] for r in skew["skewed"]] == [2]
        # the aggregate + operator report name the skewed rank
        report = fleet_mod.aggregate(str(tmp_path))
        assert report["hbm"]["skewed"][0]["rank"] == 2
        txt = fleet_mod.format_report(report)
        assert "HBM SKEW: rank 2 peak 92.0% vs fleet median 71.0%" in txt
        assert "rank 0: peak 70.0%" in txt

    def test_no_skew_when_balanced(self, tmp_path):
        for r in range(3):
            self._write_shard(tmp_path, r, 0.70)
        skew = fleet_mod.hbm_skew(
            fleet_mod.hbm_table(fleet_mod.discover_shards(str(tmp_path))))
        assert skew["skewed"] == []

    def test_bytes_fallback_without_limit(self, tmp_path):
        # live-sweep-only shards (no device limit): skew compares bytes
        for rank, peak in ((0, 100), (1, 110), (2, 400)):
            d = os.path.join(str(tmp_path), f"rank_{rank}")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "memory.prom"), "w") as f:
                f.write("# TYPE hbm_peak_bytes gauge\n"
                        f"hbm_peak_bytes {peak}\n")
        skew = fleet_mod.hbm_skew(
            fleet_mod.hbm_table(fleet_mod.discover_shards(str(tmp_path))))
        assert [r["rank"] for r in skew["skewed"]] == [2]

    def test_empty_shards_empty_hbm(self, tmp_path):
        d = tmp_path / "rank_0"
        d.mkdir()
        (d / "memory.prom").write_text("\n")
        report = fleet_mod.aggregate(str(tmp_path))
        assert report["hbm"]["skewed"] == []
        # the report renders, without an HBM section for memless shards
        txt = fleet_mod.format_report(report)
        assert "fleet shards" in txt
        assert "HBM" not in txt


class TestWatchdogMemorySection:
    def test_stall_dump_appends_memory_report(self, tmp_path):
        import time

        from paddle_tpu.observability import flight_recorder as fr

        reg = om.Registry()
        wd = fr.Watchdog(deadline=0.15, dump_dir=str(tmp_path),
                         registry=reg, name="memtest",
                         poll_interval=0.02)
        wd.start()
        try:
            time.sleep(0.5)
            assert len(wd.dumps) == 1
            txt = open(wd.dumps[0]).read()
            assert "== memory report ==" in txt
            assert "live buffers:" in txt
        finally:
            wd.stop()


class TestSnapshotToolContract:
    def test_mem_exposition_nonempty_after_serving(self, memwatch_on):
        # what the CI --mem gate asserts: after a serving run with
        # memwatch on, the filtered exposition has sample lines
        eng, cfg = _tiny_engine()
        eng.add_request(np.arange(6), max_new_tokens=4)
        eng.run()
        text = mw.memory_exposition()
        samples = [ln for ln in text.splitlines()
                   if ln and not ln.startswith("#")]
        assert samples
        assert any(ln.startswith("serving_kv_") for ln in samples)
        assert any(ln.startswith("hbm_") for ln in samples)
        json.dumps(mw.live_buffer_stats())  # JSON-serializable
