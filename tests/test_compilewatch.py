"""Compilewatch channel (observability/compilewatch.py): per-callable
compile counting via the jax monitoring listener, shape-signature
tracking, warmup marks + recompile-storm detection with shape-citing
reports, compile spans on the tracer, serving's zero-decode-recompiles
steady state, @to_static attribution, and the zero-overhead off path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.observability import compilewatch as cw
from paddle_tpu.observability import flight_recorder as fr
from paddle_tpu.observability import metrics as om


@pytest.fixture
def cw_on():
    """Fresh watch + FLAGS_compilewatch on; restored after."""
    cw._reset_for_tests()
    prev = paddle.get_flags(["FLAGS_compilewatch",
                             "FLAGS_compilewatch_storm_shapes"])
    paddle.set_flags({"FLAGS_compilewatch": True})
    yield cw.default_watch()
    paddle.set_flags(prev)
    cw._reset_for_tests()


class TestSignatures:
    def test_signature_shapes_and_statics(self):
        sig = cw.signature((jnp.ones((2, 3), jnp.float32), 7), {})
        assert "float32[2,3]" in sig and "7" in sig
        # nested containers + Tensors resolve to their array leaves
        t = paddle.to_tensor(np.ones((4,), np.float32))
        sig2 = cw.signature(({"a": [t]},))
        assert any("float32[4]" in s for s in sig2)
        # tags distinguish sibling variants at identical shapes
        a = (jnp.ones((2,), jnp.float32),)
        assert cw.signature(a, tag=("x",)) != cw.signature(a, tag=("y",))

    def test_format_sig(self):
        sig = cw.signature((jnp.ones((8, 128), jnp.bfloat16),))
        assert "bfloat16[8,128]" in cw.format_sig(sig)
        assert cw.format_sig(("t", "1", "2")) == "(no array args)"


class TestCounting:
    def test_compile_counted_once_per_shape(self, cw_on):
        f = cw.watch_jit("t.f", jax.jit(lambda a: a * 2))
        f(jnp.ones((2, 2)))
        snap = cw.snapshot()["t.f"]
        assert snap["compiles"] >= 1
        assert snap["compile_s"] > 0
        n = snap["compiles"]
        f(jnp.ones((2, 2)))          # cache hit: no new compile
        assert cw.snapshot()["t.f"]["compiles"] == n
        f(jnp.ones((3, 3)))          # new shape: one more
        snap = cw.snapshot()["t.f"]
        assert snap["compiles"] == n + 1
        assert snap["distinct_sigs"] == 2
        assert cw.total_compiles() == snap["compiles"]

    def test_counters_land_in_registry(self, cw_on):
        fresh = om.Registry()
        prev = om.set_default_registry(fresh)
        try:
            f = cw.watch_jit("t.reg", jax.jit(lambda a: a + 1))
            f(jnp.ones((2,)))
            assert fresh.value("compilewatch_compiles_total",
                               callable="t.reg") >= 1
            assert fresh.value("compilewatch_compile_seconds_total",
                               callable="t.reg") > 0
        finally:
            om.set_default_registry(prev)

    def test_attribution_context_nests(self, cw_on):
        # innermost frame wins: an inner region bills to itself, not
        # the outer callable
        with cw.call("outer"):
            with cw.call("inner"):
                jax.jit(lambda a: a - 1)(jnp.ones((5,)))
        snap = cw.snapshot()
        assert snap["inner"]["compiles"] >= 1
        assert snap.get("outer", {"compiles": 0})["compiles"] == 0

    def test_unattributed_compiles_ignored(self, cw_on):
        before = cw.total_compiles()
        jax.jit(lambda a: a * 3)(jnp.ones((7,)))  # no watched entry
        assert cw.total_compiles() == before


class TestWarmupAndStorms:
    def test_recompiles_after_mark(self, cw_on):
        f = cw.watch_jit("w.f", jax.jit(lambda a: a * 2))
        f(jnp.ones((2,)))
        assert cw.snapshot()["w.f"]["recompiles"] == 0
        cw.mark_warmup_done("w.")
        f(jnp.ones((2,)))            # warm shape: still no recompile
        assert cw.recompiles("w.") == 0
        f(jnp.ones((9,), jnp.float32))  # in-traffic compile
        snap = cw.snapshot()["w.f"]
        assert snap["recompiles"] == 1
        assert snap["post_warmup_sigs"][0]["sig"].startswith("float32[9]")

    def test_callable_first_seen_after_mark_inherits(self, cw_on):
        cw.mark_warmup_done("late.")
        g = cw.watch_jit("late.g", jax.jit(lambda a: a + 2))
        g(jnp.ones((3,)))
        # its very first compile is already in-traffic
        assert cw.recompiles("late.") >= 1

    def test_storm_report_cites_shapes(self, cw_on):
        paddle.set_flags({"FLAGS_compilewatch_storm_shapes": 2})
        rec0 = fr.default_recorder()
        f = cw.watch_jit("s.churn", jax.jit(lambda a: a * 2))
        cw.mark_warmup_done("s.")
        for n in (4, 5, 6):          # 3 distinct shapes > threshold 2
            f(jnp.ones((n,), jnp.float32))
        assert "s.churn" in cw.storms()
        snap = cw.snapshot()["s.churn"]
        assert snap["storm"] and snap["recompiles"] == 3
        report = cw.storm_report()
        assert "RECOMPILE STORM: s.churn" in report
        assert "3 distinct" in report
        for shape in ("float32[4]", "float32[5]", "float32[6]"):
            assert shape in report
        # the hint points at shape buckets
        assert "bucket" in report
        # a breadcrumb landed in the flight-recorder ring
        assert any(k == "compilewatch.storm"
                   for _, k, _ in rec0.tail())
        # registry counter
        assert om.default_registry().value(
            "compilewatch_storms_total", callable="s.churn") == 1

    def test_storm_fires_once(self, cw_on):
        paddle.set_flags({"FLAGS_compilewatch_storm_shapes": 1})
        f = cw.watch_jit("s.once", jax.jit(lambda a: a * 2))
        cw.mark_warmup_done("s.once")
        for n in (4, 5, 6, 7):
            f(jnp.ones((n,)))
        assert om.default_registry().value(
            "compilewatch_storms_total", callable="s.once") == 1


class TestTracingSpans:
    def test_compile_span_emitted(self, cw_on):
        from paddle_tpu.observability import tracing

        fresh = tracing.Tracer()
        prev_t = tracing.set_default_tracer(fresh)
        prev_f = paddle.get_flags(["FLAGS_trace_sample"])
        paddle.set_flags({"FLAGS_trace_sample": 1.0})
        try:
            f = cw.watch_jit("tr.f", jax.jit(lambda a: a * 2))
            f(jnp.ones((2, 2), jnp.float32))
            events = fresh.to_chrome_trace()
            names = [e["name"] for e in events if e["ph"] != "M"]
            assert "compile.tr.f" in names
            ev = next(e for e in events if e["name"] == "compile.tr.f")
            assert ev["dur"] > 0
            assert "float32[2,2]" in (ev["args"].get("sig") or "")
        finally:
            paddle.set_flags(prev_f)
            tracing.set_default_tracer(prev_t)


class TestOffPath:
    def test_passthrough_zero_events(self):
        cw._reset_for_tests()
        assert not cw.enabled()
        f = cw.watch_jit("off.f", jax.jit(lambda a: a * 2))
        w = cw.default_watch()
        e0 = w.events
        out = f(jnp.ones((2, 2)))
        assert float(out.sum()) == 8.0
        assert w.events == e0           # no record, no sig walk
        assert cw.snapshot() == {}
        with cw.call("off.ctx"):        # noop singleton
            pass
        assert w.events == e0
        cw.mark_warmup_done()           # one flag read
        assert w.events == e0


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


class TestServingSteadyState:
    def test_warmup_then_zero_decode_recompiles(self, cw_on):
        # the CI steady-state gate's exact shape: warmup prepays the
        # decode program; same-geometry traffic must not recompile it
        eng, cfg = _tiny_engine()
        eng.warmup()                    # marks "serving." done
        assert cw.snapshot()["serving.decode"]["warmup_done"]
        compiles_after_warmup = cw.total_compiles()
        assert compiles_after_warmup > 0
        rng = np.random.RandomState(0)
        for _ in range(2):
            eng.add_request(rng.randint(0, 97, (6,)), max_new_tokens=5)
        assert len(eng.run()) == 2
        assert cw.recompiles("serving.decode") == 0
        # ...while the channel still SEES in-traffic compiles: the
        # nb=2 prefill bucket was never warmed, and that is recorded
        assert cw.recompiles("serving.prefill") >= 1

    def test_decode_shape_churn_is_visible(self, cw_on):
        # construction-time geometry change (a second engine) compiles
        # a distinct decode signature under the same callable name —
        # the channel separates program identity by shape, not object
        eng1, _ = _tiny_engine()
        eng1.add_request(np.arange(4), max_new_tokens=2)
        eng1.run()
        c1 = cw.snapshot()["serving.decode"]["distinct_sigs"]
        eng2, _ = _tiny_engine(max_batch=1, max_seq_len=16)
        eng2.add_request(np.arange(4), max_new_tokens=2)
        eng2.run()
        assert cw.snapshot()["serving.decode"]["distinct_sigs"] > c1


class TestTrainAndToStatic:
    def test_train_step_attributed(self, cw_on):
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       build_train_step)

        paddle.seed(0)
        cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                               seq=32)
        m = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters())
        step = build_train_step(m, opt)
        x = paddle.to_tensor(np.random.randint(0, 97, (2, 16)))
        y = paddle.to_tensor(np.random.randint(0, 97, (2, 16)))
        step(x, y)
        snap = cw.snapshot()
        assert snap["jit.train_step"]["compiles"] >= 1
        n = snap["jit.train_step"]["compiles"]
        step(x, y)                      # steady state: no recompile
        assert cw.snapshot()["jit.train_step"]["compiles"] == n

    def test_to_static_attributed(self, cw_on):
        from paddle_tpu.jit import to_static

        @to_static
        def f(x):
            return x * 2 + 1

        t = paddle.to_tensor(np.ones((2, 3), np.float32))
        f(t)
        snap = cw.snapshot()
        names = [n for n in snap if n.startswith("to_static.")]
        assert names, snap.keys()
        name = names[0]
        assert snap[name]["compiles"] >= 1
        n = snap[name]["compiles"]
        f(t)
        assert cw.snapshot()[name]["compiles"] == n
        # a new input shape is a new program
        f(paddle.to_tensor(np.ones((4, 5), np.float32)))
        assert cw.snapshot()[name]["compiles"] == n + 1
