"""Span tracing (ISSUE 3 tentpole; paddle_tpu/observability/tracing.py).

Covers the acceptance contract: golden Chrome-trace export (stable field
set, valid JSON, monotonic ts), head sampling on/off plus the
always-sample-on-slow escape hatch, serving requests carrying
`FinishedRequest.trace_id` with correctly ordered/nested spans, trainer
step spans, the FLAGS_trace_sample=0 zero-allocation fast path (same
discipline as the metrics alloc-guard), atomic exporter writes, the
watchdog open-span dump, and the trace_report critical path.
"""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import config as _config
from paddle_tpu.observability import flight_recorder as fr
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import tracing as tr


@pytest.fixture
def tracer(monkeypatch):
    """Fresh default tracer with FLAGS_trace_sample=1; restores both."""
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"], "value", 1.0)
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_slow_ms"], "value", 0.0)
    fresh = tr.Tracer()
    prev = tr.set_default_tracer(fresh)
    yield fresh
    tr.set_default_tracer(prev)


@pytest.fixture
def tracer_off(monkeypatch):
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"], "value", 0.0)
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_slow_ms"], "value", 0.0)
    fresh = tr.Tracer()
    prev = tr.set_default_tracer(fresh)
    yield fresh
    tr.set_default_tracer(prev)


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


# the engine's host phases (tracing.phase), as serving.py opens them in
# one step() that admits, and the zero-length marks: one per request
# admitted, one where a launch has copied its arguments, one where a sync
# has its tokens. No name is shared with the per-request spans
# (serving.prefill, ...)
SERVING_PHASES = ("serving.admit", "serving.prefill_batch",
                  "serving.prefill.launch", "serving.kv_scatter",
                  "serving.prefill.sync", "serving.decode.launch",
                  "serving.decode.sync", "serving.emit", "serving.close")
SERVING_MARKS = ("serving.admitted", "serving.dispatch", "serving.fetched")
INSIDE = {"serving.admitted": "serving.admit",
          "serving.dispatch": "serving.decode.launch",
          "serving.fetched": "serving.decode.sync",
          "serving.prefill.launch": "serving.prefill_batch",
          "serving.kv_scatter": "serving.prefill_batch",
          "serving.prefill.sync": "serving.prefill_batch"}


def _profiled(tmp_path, fn):
    """Run fn() under a jax.profiler session (host tracer only) and
    return the program's own events: [(name, start_ns, end_ns, stats)]
    per host line."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith(("serving.", "train.", "jit."))]
            if evs:
                lines.append(evs)
    return lines


def _load_trace_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestChromeExport:
    def test_golden_event_fields(self, tracer):
        with tr.span("outer.phase", x=1):
            with tr.span("outer.child"):
                pass
        tracer.instant("outer.marker", note="hi")
        events = tr.to_chrome_trace()
        # valid JSON round-trip (what Perfetto actually parses)
        events2 = json.loads(json.dumps(events))
        assert events2 == events
        xs = [e for e in events if e["ph"] == "X"]
        instants = [e for e in events if e["ph"] == "i"]
        metas = [e for e in events if e["ph"] == "M"]
        assert len(xs) == 2 and len(instants) == 1
        # STABLE field set — the golden contract the report/viewer rely on
        for e in xs:
            assert set(e.keys()) == {"name", "cat", "ph", "ts", "dur",
                                     "pid", "tid", "args"}
            assert e["dur"] >= 0 and e["ts"] >= 0
            assert e["pid"] == os.getpid()
            assert e["cat"] == "outer"
        for e in instants:
            assert set(e.keys()) == {"name", "cat", "ph", "ts", "pid",
                                     "tid", "args", "s"}
        # thread metadata present for every tid used
        tids = {e["tid"] for e in xs + instants}
        assert tids == {m["tid"] for m in metas}
        assert all(m["name"] == "thread_name" for m in metas)
        # monotonic: non-meta events sorted by ts
        ts = [e["ts"] for e in events if e["ph"] != "M"]
        assert ts == sorted(ts)

    def test_nesting_and_ring_bound(self, tracer):
        with tr.span("a"):
            with tr.span("b"):
                time.sleep(0.001)
        evs = {e["name"]: e for e in tr.to_chrome_trace()
               if e["ph"] == "X"}
        a, b = evs["a"], evs["b"]
        # child contained in parent (same thread track)
        assert a["tid"] == b["tid"]
        assert a["ts"] <= b["ts"]
        assert b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1e-3
        small = tr.Tracer(capacity=4)
        prev = tr.set_default_tracer(small)
        try:
            for i in range(10):
                with tr.span(f"s{i}"):
                    pass
            assert len(small) == 4  # bounded ring
        finally:
            tr.set_default_tracer(prev)

    def test_write_trace_atomic(self, tracer, tmp_path):
        with tr.span("x"):
            pass
        p = tmp_path / "trace.json"
        n = tr.write_trace(str(p))
        assert n == 1
        payload = json.loads(p.read_text())
        assert isinstance(payload, list)  # the trace-event ARRAY form
        assert not list(tmp_path.glob("*.tmp"))  # no torn temp left


class TestSampling:
    def test_off_is_noop_singletons(self, tracer_off):
        assert not tr.enabled()
        assert tr.span("a") is tr.NOOP_SPAN
        assert tr.start_trace("t") is tr.NOOP_TRACE
        tr.emit("e", 0.0, 1.0)
        tr.instant("i")
        assert tracer_off.spans_created == 0
        assert len(tracer_off) == 0

    def test_rate_one_keeps_everything(self, tracer):
        for _ in range(3):
            t = tr.start_trace("t")
            assert t.sampled
            t.emit("p", 0.0, 1.0)
            t.finish()
        assert len(tracer) == 3

    def test_fractional_rate_deterministic(self, tracer, monkeypatch):
        monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"],
                            "value", 0.5)
        kept = sum(1 for _ in range(10) if tracer.sample())
        assert kept == 5  # accumulator sampling is rate-exact, not flaky

    def test_unsampled_trace_dropped_without_escape_hatch(
            self, tracer, monkeypatch):
        monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"],
                            "value", 0.01)
        t = tr.start_trace("t")
        assert t is tr.NOOP_TRACE  # nothing could ever commit it
        assert len(tracer) == 0

    def test_slow_escape_hatch_promotes_and_counts(self, monkeypatch):
        monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"],
                            "value", 0.01)
        monkeypatch.setattr(_config._FLAGS["FLAGS_trace_slow_ms"],
                            "value", 1.0)
        reg = om.Registry()
        tracer = tr.Tracer(registry=reg)
        prev = tr.set_default_tracer(tracer)
        try:
            t = tr.start_trace("slow.req")
            assert t is not tr.NOOP_TRACE and not t.sampled
            with t.span("slow.phase"):
                time.sleep(0.005)  # >> 1 ms threshold
            t.finish()
            assert len(tracer) >= 2  # phase + slow summary committed
            assert reg.value("trace_slow_requests_total") == 1
            names = [e["name"] for e in tr.to_chrome_trace()
                     if e["ph"] == "X"]
            assert "slow.phase" in names and "slow.req" in names
            summary = [e for e in tr.to_chrome_trace()
                       if e["name"] == "slow.req"][0]
            assert summary["args"]["slow"] is True
            # a FAST unsampled trace still drops
            t2 = tr.start_trace("fast.req")
            t2.emit("fast.phase", 0.0, 0.0001)
            t2.finish()
            assert "fast.phase" not in [
                e["name"] for e in tr.to_chrome_trace()]
            assert reg.value("trace_slow_requests_total") == 1
        finally:
            tr.set_default_tracer(prev)


class TestServingTracing:
    def test_finished_request_trace_id_and_span_order(self, tracer):
        eng, cfg = _tiny_engine()
        rng = np.random.RandomState(0)
        rids = [eng.add_request(rng.randint(0, 97, (6,)),
                                max_new_tokens=4) for _ in range(2)]
        finished = eng.run()
        assert len(finished) == 2
        by_rid = {f.request_id: f for f in finished}
        assert all(by_rid[r].trace_id is not None for r in rids)
        assert by_rid[rids[0]].trace_id != by_rid[rids[1]].trace_id
        events = tr.to_chrome_trace()
        for f in finished:
            mine = [e for e in events
                    if e.get("args", {}).get("trace_id") == f.trace_id]
            spans = {e["name"]: e for e in mine if e["ph"] == "X"}
            # the per-request phase timeline is complete…
            for name in ("serving.queue", "serving.prefill",
                         "serving.decode", "serving.request"):
                assert name in spans, (f.trace_id, sorted(spans))
            # …ordered queue -> prefill -> decode…
            q, p, d = (spans["serving.queue"], spans["serving.prefill"],
                       spans["serving.decode"])
            assert q["ts"] <= p["ts"] <= d["ts"]
            assert q["ts"] + q["dur"] <= p["ts"] + 1.0  # µs slack
            # …and NESTED inside the request envelope on its own track
            env = spans["serving.request"]
            for s in (q, p, d):
                assert s["tid"] == env["tid"]
                assert env["ts"] <= s["ts"] + 1.0
                assert s["ts"] + s["dur"] <= env["ts"] + env["dur"] + 1.0
            assert env["args"]["tokens"] == len(f.output_ids)
            assert spans["serving.prefill"]["args"]["bucket"] == 8
            # first-token instant present (TTFT anchor)
            assert any(e["name"] == "serving.first_token"
                       for e in mine if e["ph"] == "i")
        # the engine's own timeline: the host phases on a thread track
        # (they took the place of the after-the-fact serving.decode_step)
        engine = {e["name"] for e in events if e["ph"] == "X"}
        assert set(SERVING_PHASES) | set(SERVING_MARKS) <= engine
        assert "serving.decode_step" not in engine

    def test_trace_id_on_flight_recorder_events(self, tracer):
        rec = fr.default_recorder()
        rec.clear()
        eng, cfg = _tiny_engine()
        rid = eng.add_request(np.arange(4), max_new_tokens=2)
        finished = eng.run()
        tid = finished[0].trace_id
        assert tid is not None
        evs = {kind: fields for _, kind, fields in rec.tail()}
        assert evs["serving.add_request"]["trace_id"] == tid
        assert evs["serving.add_request"]["rid"] == rid
        assert evs["serving.finish"]["trace_id"] == tid

    def test_preempt_annotated_and_requeued(self, tracer):
        eng, cfg = _tiny_engine()
        rid = eng.add_request(np.arange(6), max_new_tokens=6)
        eng.step()
        eng._preempt(0)
        out = eng.run()
        assert len(out) == 1 and out[0].request_id == rid
        mine = [e for e in tr.to_chrome_trace()
                if e.get("args", {}).get("trace_id") == out[0].trace_id]
        assert any(e["name"] == "serving.preempt" for e in mine)
        # the queue phase reopened on requeue: two queue spans total
        queues = [e for e in mine if e["name"] == "serving.queue"]
        assert len(queues) == 2
        assert any(e["args"].get("requeue") for e in queues)
        # trace_report sums repeated phases — a preempted request's
        # queue/decode columns must cover BOTH segments
        rep = _load_trace_report()
        row = [r for r in rep.serving_rows(tr.to_chrome_trace())
               if r["trace_id"] == out[0].trace_id][0]
        assert row["queue_us"] == pytest.approx(
            sum(q["dur"] for q in queues))
        decodes = [e for e in mine if e["name"] == "serving.decode"]
        assert len(decodes) == 2  # pre-preemption segment + final
        assert row["decode_us"] == pytest.approx(
            sum(d["dur"] for d in decodes))

    def test_abort_finishes_trace(self, tracer):
        eng, cfg = _tiny_engine()
        rid = eng.add_request(np.arange(4), max_new_tokens=4)
        assert eng.abort(rid)
        assert rid not in eng._traces  # no leak
        assert any(e["name"] == "serving.abort"
                   for e in tr.to_chrome_trace())

    def test_abort_mid_decode_keeps_decode_span(self, tracer):
        # a slow request aborted by a client timeout spent its life in
        # decode — its trace must show that interval, not decode=0
        eng, cfg = _tiny_engine()
        rid = eng.add_request(np.arange(4), max_new_tokens=8)
        eng.step()  # admit + first token
        eng.step()  # at least one real decode dispatch
        assert eng.abort(rid)
        mine = [e for e in tr.to_chrome_trace() if e["ph"] == "X"]
        decode = [e for e in mine if e["name"] == "serving.decode"]
        assert len(decode) == 1 and decode[0]["dur"] > 0
        # slot doesn't leak the trace id to its next tenant
        assert all(s.trace_id == -1 for s in eng.slots)

    def test_zero_alloc_fast_path_when_off(self, tracer_off):
        # the acceptance guard: with FLAGS_trace_sample=0 a warm decode
        # loop creates ZERO span/trace objects (same discipline as the
        # metrics registry alloc-guard)
        eng, cfg = _tiny_engine()
        rng = np.random.RandomState(2)
        eng.add_request(rng.randint(0, 97, (6,)), max_new_tokens=6)
        eng.run()  # warm
        eng.add_request(rng.randint(0, 97, (6,)), max_new_tokens=6)
        c0 = tracer_off.spans_created
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
        assert steps >= 2
        assert tracer_off.spans_created - c0 == 0
        assert len(tracer_off) == 0
        assert eng._traces == {}


class TestPhases:
    """tracing.phase / mark: the profiler's annotations, whatever the
    flags; the ring only when tracing is enabled."""

    def _count(self, monkeypatch, attrs=False):
        """Every annotation opened from here on: its name, or with
        `attrs` (name, attributes)."""
        opened = []

        class Counting(tr.TraceAnnotation):
            def __init__(self, name, **kw):
                opened.append((name, kw) if attrs else name)
                super().__init__(name, **kw)

        monkeypatch.setattr(tr, "TraceAnnotation", Counting)
        return opened

    def test_off_allocates_nothing_and_a_step_opens_at_most_ten(
            self, tracer_off, monkeypatch):
        eng, _ = _tiny_engine(decode_burst=4)
        eng.add_request(np.arange(6), max_new_tokens=20)
        eng.step()                      # compile outside the count
        opened = self._count(monkeypatch)
        c0 = tracer_off.spans_created
        with tr.phase("x.y", n=1):
            pass
        tr.mark("x.z", seconds=0.5)
        assert opened == ["x.y", "x.z"]
        del opened[:]
        eng.step()                      # decode only: five spans, two marks
        assert opened == ["serving.admit", "serving.decode.launch",
                          "serving.dispatch", "serving.decode.sync",
                          "serving.fetched", "serving.emit",
                          "serving.close"]
        del opened[:]
        eng.add_request(np.arange(5), max_new_tokens=8)
        eng.step()                      # admits one request
        # (a step in which a request also FINISHES re-admits at its end:
        # one more serving.admit, and a prefill's four if it admits)
        spans = [n for n in opened if n not in SERVING_MARKS]
        assert set(spans) == set(SERVING_PHASES)
        assert len(spans) <= 10
        # one mark per request admitted, per launch and per sync, none per
        # token or layer
        assert [n for n in opened if n in SERVING_MARKS] == \
            list(SERVING_MARKS)
        assert tracer_off.spans_created == c0 and len(tracer_off) == 0

    def test_profiler_session_holds_every_phase_nested(self, tracer_off,
                                                       tmp_path):
        eng, _ = _tiny_engine(decode_burst=4)
        rids = []

        def drive():
            rids.append(eng.add_request(np.arange(6), max_new_tokens=6))
            eng.step()
            rids.append(eng.add_request(np.arange(9), max_new_tokens=5))
            while eng.has_work():
                eng.step()

        lines = _profiled(tmp_path, drive)
        evs = max(lines, key=len)       # the thread that ran the engine
        names = {e[0] for e in evs}
        assert set(SERVING_PHASES) | set(SERVING_MARKS) <= names
        # the first steps compiled: each build is marked, with its kind
        builds = [e[3] for line in lines for e in line
                  if e[0] == "jit.build"]
        assert builds and all(b["kind"] == "compile" and b["seconds"] > 0
                              for b in builds)
        for name, start, end, _ in evs:
            outer = INSIDE.get(name)
            if outer is not None:
                assert any(o[0] == outer and o[1] <= start and end <= o[2]
                           for o in evs), name
            elif name in SERVING_PHASES:
                # a top-level phase lies inside no other serving phase
                assert not any(o[0] in SERVING_PHASES and o[0] != name
                               and o[1] <= start and end <= o[2]
                               and (o[1], o[2]) != (start, end)
                               for o in evs), name
        admitted = [e[3] for e in evs if e[0] == "serving.admitted"]
        assert sorted(a["rid"] for a in admitted) == sorted(rids)
        assert all(set(a) == {"rid", "queued_us", "requeue"}
                   and a["queued_us"] >= 0 and a["requeue"] == 0
                   for a in admitted)
        # a phase carries what a reader reads and nothing else: a round the
        # rows it holds up, its launch its true and padded tokens, a sync
        # its blocking reads, a close-out its tokens, and `serving.emit` the
        # burst's own counts of the pages its decode attention reads and
        # maps
        carried = {(e[0], frozenset(e[3])) for e in evs
                   if e[0] in SERVING_PHASES and e[3]}
        assert carried == {
            ("serving.prefill_batch", frozenset({"rows_held"})),
            ("serving.prefill.launch",
             frozenset({"prompt_tokens", "padded_tokens"})),
            ("serving.decode.sync", frozenset({"fetches"})),
            ("serving.close", frozenset({"tokens"})),
            ("serving.emit",
             frozenset({"attn_pages_read", "attn_pages_mapped"}))}
        # exactly one mark inside each sync and each launch
        for mark, outer in (("serving.fetched", "serving.decode.sync"),
                            ("serving.dispatch", "serving.decode.launch")):
            for o in (o for o in evs if o[0] == outer):
                assert sum(e[0] == mark and o[1] <= e[1] and e[2] <= o[2]
                           for e in evs) == 1, outer
        # one prefill round per request, each around its three parts
        assert sum(e[0] == "serving.prefill_batch" for e in evs) == 2
        assert tracer_off.spans_created == 0

    def test_a_round_says_the_rows_it_holds_up_and_its_padding(
            self, tracer_off, monkeypatch):
        # 20 pages a sequence is past the policy's PAGE_BUCKETS_MAX: a
        # prompt a round, padded to the next power of two of its pages
        eng, _ = _tiny_engine(max_batch=4, max_seq_len=40, page_size=2,
                              decode_burst=4)
        opened = self._count(monkeypatch, attrs=True)

        def rounds():
            found = [kw for name, kw in opened
                     if name == "serving.prefill_batch"]
            launches = [kw for name, kw in opened
                        if name == "serving.prefill.launch"]
            del opened[:]
            return found, launches

        eng.add_request(np.arange(5), max_new_tokens=30)
        eng.add_request(np.arange(7), max_new_tokens=30)
        eng.step()
        # the engine was empty; the second round finds the first prompt's
        # slot with its first token pending, which is no row held up
        found, launches = rounds()
        assert found == [{"rows_held": 0}] * 2
        assert launches == [{"prompt_tokens": 5, "padded_tokens": 8},
                            {"prompt_tokens": 7, "padded_tokens": 8}]
        eng.step()
        assert rounds() == ([], [])
        # both rows decode: a third prompt holds up two
        eng.add_request(np.arange(3), max_new_tokens=30)
        eng.step()
        found, launches = rounds()
        assert found == [{"rows_held": 2}]
        assert launches == [{"prompt_tokens": 3, "padded_tokens": 4}]

    def test_a_batched_round_beside_a_decoding_row(self, tracer_off,
                                                   monkeypatch):
        eng, _ = _tiny_engine(max_batch=4, decode_burst=4)
        eng.add_request(np.arange(6), max_new_tokens=20)
        eng.step()
        opened = self._count(monkeypatch, attrs=True)
        for n in (9, 4, 11):
            eng.add_request(np.arange(n), max_new_tokens=20)
        eng.step()
        by_name = {}
        for name, kw in opened:
            by_name.setdefault(name, []).append(kw)
        # one round of three prompts beside one decoding row; the batch
        # pads to 4 rows of 2 pages
        assert by_name["serving.prefill_batch"] == [{"rows_held": 1}]
        launch, = by_name["serving.prefill.launch"]
        assert launch == {"prompt_tokens": 24, "padded_tokens": 4 * 16}
        assert launch["prompt_tokens"] <= launch["padded_tokens"]

    @pytest.mark.parametrize("decode_burst", [4, 1])
    def test_a_sync_counts_its_reads_and_a_close_its_tokens(
            self, tracer_off, monkeypatch, decode_burst):
        eng, _ = _tiny_engine(decode_burst=decode_burst)
        streamed = []
        eng.add_request(np.arange(6), max_new_tokens=10,
                        on_token=lambda rid, tok: streamed.append(tok))
        eng.add_request(np.arange(4), max_new_tokens=6,
                        on_token=lambda rid, tok: streamed.append(tok))
        opened = self._count(monkeypatch, attrs=True)
        asked = []                      # the program each step launched
        for which in ("_get_burst_fn", "_get_decode_fn"):
            monkeypatch.setattr(
                eng, which, lambda *a, _get=getattr(eng, which), _w=which:
                (asked.append(_w), _get(*a))[1])
        while eng.has_work():
            n0 = len(streamed)
            del opened[:]
            eng.step()
            by_name = {}
            for name, kw in opened:
                by_name.setdefault(name, []).append(kw)
            sync, = by_name["serving.decode.sync"]
            close, = by_name["serving.close"]
            counts = [kw for kw in by_name["serving.emit"]
                      if "attn_pages_read" in kw][-1]
            # the tokens, a burst's emits, and a read for each count; the
            # single-step program (every row on its last token, or no
            # bursts at all) has no emits
            burst = asked[-1] == "_get_burst_fn"
            assert sync == {"fetches": (2 if burst else 1) + len(counts)}
            # the first tokens a step commits before its launch are not
            # the burst's
            firsts = 2 if any("attn_pages_read" not in kw
                              for kw in by_name["serving.emit"]) else 0
            assert close["tokens"] == len(streamed) - n0 - firsts
            assert len(by_name["serving.fetched"]) == 1
            assert len(by_name["serving.dispatch"]) == 1
        assert len(streamed) == 10 + 6
        assert set(asked) == ({"_get_burst_fn", "_get_decode_fn"}
                              if decode_burst > 1 else {"_get_decode_fn"})

    def test_second_prefill_of_a_bucket_builds_nothing_in_the_page_write(
            self, tracer_off, tmp_path):
        # the page write is ONE compiled program per (nb, bucket): a second
        # prefill round of that bucket, of another number of requests,
        # compiles nothing inside serving.kv_scatter (an eager operation on
        # a new shape would: every one is a program of its own)
        eng, _ = _tiny_engine(max_batch=4, decode_burst=4)

        def drive():
            for n in (3, 4):            # both pad to nb = 4, bucket 8
                for _ in range(n):
                    eng.add_request(np.arange(6), max_new_tokens=3)
                while eng.has_work():
                    eng.step()

        lines = _profiled(tmp_path, drive)
        evs = max(lines, key=len)
        first, second = sorted((e[1], e[2]) for e in evs
                               if e[0] == "serving.kv_scatter")
        builds = [e[1] for line in lines for e in line
                  if e[0] == "jit.build"]
        assert any(first[0] <= b <= first[1] for b in builds)
        assert not any(second[0] <= b <= second[1] for b in builds)
        assert list(eng._page_write_fns) == [(4, 8, "target")]

    def test_ring_holds_the_same_phases_when_tracing_is_on(self, tracer):
        eng, _ = _tiny_engine(decode_burst=4)
        eng.add_request(np.arange(6), max_new_tokens=6)
        eng.run()
        xs = [e for e in tr.to_chrome_trace() if e["ph"] == "X"]
        names = {e["name"] for e in xs}
        assert set(SERVING_PHASES) | set(SERVING_MARKS) <= names
        adm = next(e for e in xs if e["name"] == "serving.admitted")
        assert adm["args"]["rid"] == 0 and "queued_us" in adm["args"]
        # a build is an interval in the ring, as `emit` would record it
        build = next(e for e in xs if e["name"] == "jit.build")
        assert build["args"]["kind"] == "compile"
        assert build["dur"] == pytest.approx(
            1e6 * build["args"]["seconds"], rel=0.05, abs=50)

    def test_cache_load_is_marked_as_such(self, tracer):
        from paddle_tpu.observability import compilewatch as cw

        cw._on_event_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        cw._on_event_duration(
            "/jax/core/compile/backend_compile_duration", 0.5)
        cw._on_event_duration(
            "/jax/core/compile/backend_compile_duration", 2.0)
        kinds = sorted((e["args"]["kind"], e["args"]["seconds"])
                       for e in tr.to_chrome_trace()
                       if e["name"] == "jit.build")
        assert kinds == [("cache_load", 0.5), ("compile", 2.0)]


class TestScopes:
    """jax.named_scope names in the models: HLO metadata only."""

    def _locs(self, lowered):
        """Every `op_name` of the compiled program's HLO."""
        import re

        return re.findall(r'op_name="([^"]+)"', lowered.compile().as_text())

    def test_programs_carry_the_scopes_and_tokens_are_unchanged(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.jit.api import flatten_call
        from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                       build_train_step)

        assert tr.SCOPES == ("embed", "attn", "mlp", "head", "optimizer")
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig.tiny(vocab=97, seq=32))
        m.eval()
        eng = ServingEngine(m, max_batch=2, max_seq_len=32, page_size=8,
                            decode_burst=4)
        params, buffers = eng._cached_params()
        key = jax.random.key_data(jax.random.key(0))
        pages = tuple(eng.k_pages)
        slot = lambda dt: jnp.zeros((2,), dt)  # noqa: E731
        burst = self._locs(eng._get_burst_fn(True, 4).lower(
            params, buffers, pages, pages, (), (), slot(jnp.int64),
            jnp.asarray(eng.block_tables), slot(jnp.int32),
            slot(jnp.bool_), slot(jnp.int32), slot(jnp.int32), key,
            slot(jnp.bool_), slot(jnp.float32), slot(jnp.int32),
            slot(jnp.float32)))
        for name in ("embed", "attn", "attn/kv_write", "mlp", "head",
                     "head/sample"):
            assert any(f"/while/body/closed_call/{name}/" in loc
                       for loc in burst), name
        prefill = self._locs(eng._get_prefill_fn(1, 8, True).lower(
            params, buffers, jnp.zeros((1, 8), jnp.int64),
            jnp.ones((1,), jnp.int32), key, jnp.ones((1,), jnp.bool_),
            jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32)))
        # (the dense cache's write fills the whole cache in a prefill and
        # is compiled away, so no attn/kv_write survives here)
        for name in ("embed", "attn", "mlp", "head", "head/sample"):
            assert any(f"/{name}/" in loc for loc in prefill), name

        # the served tokens are what generate() gives on the same weights
        prompt = np.arange(5) + 3
        eng.add_request(prompt, max_new_tokens=6)
        served = eng.run()[0].output_ids
        want = m.generate(paddle.to_tensor(prompt[None]), max_new_tokens=6,
                          decode_strategy="greedy_search")[0]
        assert list(served) == list(np.asarray(want.numpy())[0][-6:])

        m.train()
        m.config.use_recompute = True
        for layer in m.gpt.layers:
            layer.use_recompute = True
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters())
        step = build_train_step(m, opt)
        x = paddle.to_tensor(np.random.randint(0, 97, (2, 16)))
        leaves, structure = flatten_call((x, x), {})
        p = m.parameters_pytree()
        train = self._locs(jax.jit(
            step._raw_step._pure_step, static_argnames=("structure",)
        ).lower(p, m.buffers_pytree(), opt.init_state_pytree(p),
                jnp.float32(1e-4), key, leaves, structure=structure))
        import re

        # under a gradient the scope stands inside the transform's name:
        # jvp(attn), transpose(jvp(attn))
        for name in ("embed", "attn", "mlp", "head", "optimizer"):
            assert any(re.search(rf"[/(]{name}[/)]", loc)
                       for loc in train), name
        # backward and recompute keep the forward's scope in op_name
        assert any("transpose(" in loc and re.search(r"[/(]attn[/)]", loc)
                   for loc in train)
        assert any("checkpoint" in loc and re.search(r"[/(]mlp[/)]", loc)
                   for loc in train)


class TestTrainTracing:
    def test_step_spans_recorded(self, tracer):
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       build_train_step)

        paddle.seed(0)
        cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4,
                               seq=32)
        m = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=m.parameters())
        step = build_train_step(m, opt)
        b, s = 2, 16
        x = paddle.to_tensor(np.random.randint(0, 97, (b, s)))
        y = paddle.to_tensor(np.random.randint(0, 97, (b, s)))
        n_steps = 3
        for _ in range(n_steps):
            step(x, y)
        xs = [e for e in tr.to_chrome_trace() if e["ph"] == "X"]
        names = [e["name"] for e in xs]
        assert names.count("train.step_compute") == n_steps
        # data-wait spans cover the gaps BETWEEN steps: n-1 of them
        assert names.count("train.data_wait") == n_steps - 1
        assert names.count("train.step") == n_steps
        comp = [e for e in xs if e["name"] == "train.step_compute"]
        assert all(e["args"]["tokens"] == b * s for e in comp)
        # distinct trace ids, one per step
        ids = {e["args"]["trace_id"] for e in comp}
        assert len(ids) == n_steps

    def test_off_adds_no_spans(self, tracer_off):
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
        step(x, y)  # warm/compile
        c0 = tracer_off.spans_created
        step(x, y)
        assert tracer_off.spans_created == c0
        assert len(tracer_off) == 0


class TestCorrelationChannels:
    def test_watchdog_dump_includes_open_spans(self, tracer, tmp_path):
        reg = om.Registry()
        wd = fr.Watchdog(deadline=60.0, dump_dir=str(tmp_path),
                         registry=reg, name="spans")
        sp = tr.span("serving.prefill", bucket=512)
        sp.__enter__()
        time.sleep(0.01)
        try:
            path = wd.dump()
            txt = open(path).read()
            assert "open spans" in txt
            # "hung somewhere" becomes "inside serving.prefill, N s open"
            assert "serving.prefill" in txt
            assert "s open)" in txt
        finally:
            sp.end()
        # after end() the span leaves the open registry
        assert tr.open_spans() == []
        txt2 = open(wd.dump()).read()
        assert "(none)" in txt2


class TestCollectiveTracing:
    def test_eager_all_reduce_single_span_no_duplicate(self, tracer):
        import paddle_tpu.distributed.collective as coll

        t = paddle.to_tensor(np.ones((8, 4), np.float32))
        coll.all_reduce(t)
        evs = [e for e in tr.to_chrome_trace()
               if e["name"] == "collective.all_reduce"]
        # ONE real-duration span, not a span + a same-named instant
        assert len(evs) == 1 and evs[0]["ph"] == "X"
        assert evs[0]["args"]["bytes"] == 8 * 4 * 4

    def test_jit_helper_emits_instant(self, tracer):
        # jit-path helpers (psum & co) funnel through _count_collective
        # with instant=True — a trace-time emission marker, no duration
        import paddle_tpu.distributed.collective as coll

        coll._count_collective("psum", np.ones((4,), np.float32))
        evs = [e for e in tr.to_chrome_trace()
               if e["name"] == "collective.psum"]
        assert len(evs) == 1 and evs[0]["ph"] == "i"
        assert evs[0]["args"]["bytes"] == 16.0


class TestAtomicExporters:
    def test_write_prometheus_atomic(self, tmp_path):
        reg = om.Registry()
        reg.counter("c_total", "h").inc(3)
        p = tmp_path / "m.prom"
        om.write_prometheus(str(p), reg)
        # samples carry the fleet-merge const labels (rank/world_size)
        assert 'c_total{rank="0",world_size="1"} 3' in p.read_text()
        assert not list(tmp_path.glob("*.tmp"))

    def test_write_jsonl_append_atomic(self, tmp_path):
        reg = om.Registry()
        reg.counter("c_total", "h").inc()
        p = tmp_path / "m.jsonl"
        om.write_jsonl(str(p), reg)
        om.write_jsonl(str(p), reg)  # append preserved across replaces
        rows = [json.loads(ln) for ln in p.read_text().splitlines()]
        assert len(rows) == 2
        om.write_jsonl(str(p), reg, append=False)  # truncate mode
        assert len(p.read_text().splitlines()) == 1
        assert not list(tmp_path.glob("*.tmp"))

    def test_atomic_write_never_leaves_temp_on_error(self, tmp_path):
        bad = tmp_path / "missing_dir" / "f.txt"
        with pytest.raises(OSError):
            om.atomic_write(str(bad), "x")
        assert not list(tmp_path.glob("**/*.tmp"))


class TestTraceReport:
    def test_report_on_serving_trace(self, tracer, tmp_path):
        eng, cfg = _tiny_engine()
        rng = np.random.RandomState(3)
        for _ in range(2):
            eng.add_request(rng.randint(0, 97, (6,)), max_new_tokens=3)
        finished = eng.run()
        assert len(finished) == 2
        p = tmp_path / "trace.json"
        tr.write_trace(str(p))
        rep = _load_trace_report()
        events = rep.load_events(str(p))
        text, ok = rep.build_report(events)
        assert ok
        assert "critical path" in text
        assert "serving.prefill" in text and "serving.decode" in text
        assert "ttft_ms" in text
        # per-request rows: one line per traced request
        assert text.count("\n") > 8

    def test_report_rejects_empty_trace(self, tmp_path):
        p = tmp_path / "empty.json"
        p.write_text("[]")
        rep = _load_trace_report()
        text, ok = rep.build_report(rep.load_events(str(p)))
        assert not ok
        assert rep.main([str(p)]) == 2

    def test_report_object_form_accepted(self, tmp_path):
        p = tmp_path / "obj.json"
        p.write_text(json.dumps({"traceEvents": []}))
        rep = _load_trace_report()
        assert rep.load_events(str(p)) == []


class TestContextPropagation:
    """The X-PT-Trace context (ISSUE 16 tentpole): inject/extract
    roundtrip, thread-local adoption, the sampled-at-router verdict
    riding the wire, and the KVHandoff carry across a disaggregated
    prefill -> decode boundary."""

    def test_header_roundtrip(self, tracer):
        t = tracer.start_trace("router.request", own_track=True)
        ctx = tr.parse_context(tr.inject(t))
        assert ctx is not None
        assert ctx.trace_id == t.trace_id
        assert ctx.span == "router.request"
        assert ctx.sampled
        t.finish()

    def test_inject_noop_and_malformed_headers(self, tracer_off):
        assert tr.inject(tr.NOOP_TRACE) is None
        for bad in (None, "", 42, "zzz-1-x", "deadbeef", b"abc-1"):
            assert tr.parse_context(bad) is None, bad

    def test_extract_installs_then_clear_drops(self, tracer):
        hdr = tr.TraceContext(0xabc, "router.request", True).header()
        tr.set_pending(hdr)
        try:
            ctx = tr.extract()
            assert ctx is not None and ctx.trace_id == 0xabc
            assert tr.current_context() is ctx
        finally:
            tr.clear_context()
        assert tr.current_context() is None
        assert tr.extract() is None   # pending header dropped too

    def test_extract_is_inert_when_tracing_off(self, tracer_off):
        tr.set_pending("abc-1-router.request")
        try:
            assert tr.extract() is None
            assert tr.current_context() is None
        finally:
            tr.clear_context()

    def test_child_adopts_inherited_trace_id(self, tracer):
        parent = tracer.start_trace("router.request", own_track=True)
        ctx = tr.parse_context(tr.inject(parent))
        child = tracer.start_trace("serving.request", own_track=True,
                                   parent=ctx)
        assert child.trace_id == parent.trace_id
        child.finish()
        parent.finish()

    def test_thread_context_adopted_without_explicit_parent(
            self, tracer):
        ctx = tr.TraceContext(0x77, "router.request", True)
        prev = tr.set_current(ctx)
        try:
            t = tracer.start_trace("serving.request", own_track=True)
            assert t.trace_id == 0x77
            t.finish()
        finally:
            tr.set_current(prev)

    def test_sampled_verdict_overrides_local_sampler(
            self, tracer, monkeypatch):
        # the router sampled this request; a replica at a 1% local
        # rate must STILL record its hops — the verdict is fleet-wide,
        # decided once where the request entered
        monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"],
                            "value", 0.01)
        assert tracer.start_trace("local") is tr.NOOP_TRACE
        ctx = tr.TraceContext(0x5, "router.request", True)
        child = tracer.start_trace("serving.request", parent=ctx)
        assert child is not tr.NOOP_TRACE
        assert child.trace_id == 0x5
        child.finish()

    def test_unsampled_verdict_suppresses_local_spans(self, tracer):
        # ...and an UNSAMPLED verdict wins over a local rate of 1.0,
        # so no shard holds orphan fragments of a dropped trace
        c0 = tracer.spans_created
        ctx = tr.TraceContext(0x6, "router.request", False)
        child = tracer.start_trace("serving.request", parent=ctx)
        assert child is tr.NOOP_TRACE
        assert tracer.spans_created == c0

    def test_handoff_carries_context_across_engines(self, tracer):
        from paddle_tpu.inference import DisaggregatedServing

        pe, cfg = _tiny_engine()
        de, _ = _tiny_engine()
        rng = np.random.RandomState(5)
        out = DisaggregatedServing(pe, de).generate(
            rng.randint(0, cfg.vocab_size, (6,)), max_new_tokens=3)
        assert out["ok"]
        events = tracer.to_chrome_trace()
        by_name = {}
        for e in events:
            if e.get("ph") == "X" and "trace_id" in e.get("args", {}):
                by_name.setdefault(e["name"],
                                   set()).add(e["args"]["trace_id"])
        # prefill (engine A), the handoff attach, and decode (engine B)
        # all land under ONE trace_id: one request, one timeline
        assert by_name["serving.prefill"] == by_name["serving.attach"]
        assert by_name["serving.attach"] == by_name["serving.decode"]
        assert len(by_name["serving.prefill"]) == 1

    def test_off_path_context_calls_add_no_spans(self, tracer_off):
        c0 = tracer_off.spans_created
        assert tr.inject(tr.NOOP_TRACE) is None
        assert tr.extract("abc-1-x") is None
        assert tracer_off.spans_created == c0


class TestStitchReport:
    """tools/trace_report.py --stitch: cross-shard grouping by
    trace_id, per-hop table, network derivation, orphan detection."""

    @staticmethod
    def _ev(name, ts, dur, pid, trace_id):
        return {"ph": "X", "name": name, "ts": float(ts),
                "dur": float(dur), "pid": pid, "tid": 1,
                "args": {"trace_id": trace_id}}

    def _events(self):
        ev = self._ev
        return [
            # trace 5: router (pid 1) + serving (pid 2) — stitched
            ev("router.queue", 0, 100, 1, 5),
            ev("router.route", 100, 900, 1, 5),
            ev("serving.queue", 200, 50, 2, 5),
            ev("serving.prefill", 250, 300, 2, 5),
            ev("serving.decode", 550, 400, 2, 5),
            # trace 9: router only — the context died on the wire
            ev("router.queue", 0, 10, 1, 9),
            ev("router.route", 10, 50, 1, 9),
            # unrelated span: never grouped
            ev("train.step", 0, 10, 1, None),
        ]

    def test_stitch_rows_hops_and_orphan(self):
        rep = _load_trace_report()
        rows = rep.stitch_rows(self._events())
        assert [r["trace_id"] for r in rows] == [5, 9]
        joined = rows[0]
        assert joined["n_procs"] == 2 and joined["pids"] == [1, 2]
        assert not joined["orphan"]
        assert joined["router_queue_us"] == 100
        assert joined["route_us"] == 900
        # network = route wall minus the serving side's own wall
        # (200..950 = 750 us) -> 150 us of HTTP round trip
        assert joined["network_us"] == pytest.approx(150.0)
        assert joined["replica_queue_us"] == 50
        assert joined["prefill_us"] == 300
        assert joined["decode_us"] == 400
        assert joined["handoff_us"] == 0
        orphan = rows[1]
        assert orphan["orphan"] and orphan["network_us"] is None

    def test_format_stitch_table_and_orphan_flag(self):
        rep = _load_trace_report()
        text = rep.format_stitch(rep.stitch_rows(self._events()))
        assert "stitched distributed traces (2)" in text
        assert "ORPHAN (injected but never extracted)" in text
        assert "network_ms" in text and "handoff_ms" in text
        assert "1 trace(s) span >=2 processes; 1 orphan(s)" in text
