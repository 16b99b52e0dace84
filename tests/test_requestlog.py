"""Per-request accounting plane (ISSUE 19:
observability/requestlog.py): the zero-alloc-when-off ledger ring,
tenant normalization + thread-parked X-PT-Tenant adoption, the
cost-breakdown record the engine emits at _finish (one per finished
request, none for aborts), tenant identity surviving the
disaggregated prefill->decode handoff under ONE trace_id, OpenMetrics
exemplars on the latency histograms (and the fleet scraper's strict
parser surviving them), the /debug/requests endpoint, requests.jsonl
through the fleet flusher + scraper, the per-tenant fleet-report
rollup behind `fleet_report --require-accounting`, and the fleet_top
dashboard frame."""
import json
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import config as _config
from paddle_tpu.observability import fleet as fleet_mod
from paddle_tpu.observability import httpd
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import requestlog as rl
from paddle_tpu.observability import slo
from paddle_tpu.observability import timeseries as ts
from paddle_tpu.observability import tracing as tr


@pytest.fixture(autouse=True)
def _clean():
    rl._reset_for_tests()
    rl.clear_pending_tenant()
    httpd._reset_for_tests()
    slo._reset_for_tests()
    ts._reset_for_tests()
    yield
    rl._reset_for_tests()
    rl.clear_pending_tenant()
    httpd._reset_for_tests()
    slo._reset_for_tests()
    ts._reset_for_tests()


@pytest.fixture
def reqlog_on(monkeypatch):
    monkeypatch.setattr(_config._FLAGS["FLAGS_requestlog"], "value",
                        True)


@pytest.fixture
def tracer(monkeypatch):
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"], "value",
                        1.0)
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_slow_ms"], "value",
                        0.0)
    fresh = tr.Tracer()
    prev = tr.set_default_tracer(fresh)
    yield fresh
    tr.set_default_tracer(prev)


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


# ---------------------------------------------------------------------------
# the ledger ring (no engine)
# ---------------------------------------------------------------------------


def test_off_is_one_flag_read_nothing_allocated():
    # the channel contract every observability PR holds: default-off
    # costs a flag read and allocates nothing
    assert not rl.enabled()
    assert rl.ensure_log() is None
    assert rl.log() is None
    rl.record({"rid": 1, "tenant": "x"})    # swallowed, not stored
    assert rl.log() is None
    assert rl.history() == []
    assert rl.usage() == {}
    assert rl.records_taken() == 0


def test_normalize_tenant_collapses_empty_to_default():
    assert rl.normalize_tenant(None) == rl.DEFAULT_TENANT
    assert rl.normalize_tenant("") == rl.DEFAULT_TENANT
    assert rl.normalize_tenant("   ") == rl.DEFAULT_TENANT
    assert rl.normalize_tenant("  acme ") == "acme"
    assert rl.normalize_tenant(7) == "7"


def test_pending_tenant_parks_per_thread():
    rl.set_pending_tenant("acme")
    assert rl.pending_tenant() == "acme"
    seen = {}

    def worker():
        seen["other"] = rl.pending_tenant()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen["other"] is None    # thread-local, like X-PT-Trace
    rl.clear_pending_tenant()
    assert rl.pending_tenant() is None


def test_ring_bound_oldest_out_counter_keeps_counting():
    lg = rl.RequestLog(capacity=3)
    for i in range(5):
        lg.record({"rid": i, "tenant": "t"})
    assert len(lg) == 3
    assert [r["rid"] for r in lg.history()] == [2, 3, 4]  # oldest first
    assert lg.records_created == 5      # counts minted, not retained
    lg.clear()
    assert len(lg) == 0 and lg.records_created == 5


def test_history_tenant_filter_and_trailing_n():
    lg = rl.RequestLog(capacity=16)
    for i in range(6):
        lg.record({"rid": i, "tenant": "a" if i % 2 else "b"})
    assert [r["rid"] for r in lg.history(tenant="a")] == [1, 3, 5]
    assert [r["rid"] for r in lg.history(last=2)] == [4, 5]
    assert [r["rid"] for r in lg.history(tenant="a", last=1)] == [5]
    assert lg.history(last=99) == lg.history()   # over-ask is fine


def test_usage_rolls_up_tokens_latency_and_errors():
    lg = rl.RequestLog(capacity=16)
    lg.record({"tenant": "a", "prompt_tokens": 10, "output_tokens": 4,
               "ttft_s": 0.5, "total_s": 1.0, "outcome": "ok"})
    lg.record({"tenant": "a", "prompt_tokens": 6, "output_tokens": 2,
               "outcome": "error"})
    lg.record({"tenant": "b", "prompt_tokens": 3, "output_tokens": 1,
               "ttft_s": 0.1, "total_s": 0.2})
    u = lg.usage()
    assert u["a"]["requests"] == 2
    assert u["a"]["prompt_tokens"] == 16
    assert u["a"]["output_tokens"] == 6
    assert u["a"]["errors"] == 1
    assert u["a"]["ttft_sum_s"] == pytest.approx(0.5)
    assert u["a"]["ttft_n"] == 1        # no ttft on the error row
    assert u["b"]["total_sum_s"] == pytest.approx(0.2)


def test_capacity_flag_sizes_the_ring(monkeypatch, reqlog_on):
    monkeypatch.setattr(_config._FLAGS["FLAGS_requestlog_capacity"],
                        "value", 4)
    lg = rl.ensure_log()
    assert lg is not None and lg._ring.maxlen == 4
    for i in range(9):
        rl.record({"rid": i})
    assert len(rl.history()) == 4
    assert rl.records_taken() == 9
    # records are wall-clock stamped on the way in
    assert all("ts" in r for r in rl.history())


# ---------------------------------------------------------------------------
# engine emission at _finish
# ---------------------------------------------------------------------------


def test_finish_emits_one_record_with_cost_breakdown(reqlog_on):
    eng, cfg = _tiny_engine()
    rng = np.random.RandomState(0)
    eng.add_request(rng.randint(0, cfg.vocab_size, (6,)),
                    max_new_tokens=3, tenant="acme-emit")
    eng.add_request(rng.randint(0, cfg.vocab_size, (9,)),
                    max_new_tokens=4)     # no tenant -> "default"
    eng.run()
    rows = rl.history()
    assert len(rows) == 2               # ONE record per request
    by_tenant = {r["tenant"]: r for r in rows}
    acme = by_tenant["acme-emit"]
    dflt = by_tenant[rl.DEFAULT_TENANT]
    assert acme["prompt_tokens"] == 6 and acme["output_tokens"] == 3
    assert dflt["prompt_tokens"] == 9 and dflt["output_tokens"] == 4
    for r in rows:
        assert r["outcome"] == "ok"
        assert r["queue_s"] >= 0.0
        assert r["ttft_s"] > 0.0
        assert r["total_s"] >= r["ttft_s"]
        assert r["itl_s"] >= 0.0        # n_out > 1 -> ITL derivable
        assert "ts" in r
    # the same emission point feeds the tenant metric families
    samples = fleet_mod._parse_prom_samples(om.to_prometheus())
    usage = {(lab["tenant"], lab["kind"]): v
             for lab, v in samples.get("usage_tokens_total", [])}
    assert usage[("acme-emit", "prompt")] >= 6.0
    assert usage[("acme-emit", "output")] >= 3.0
    ttfts = {lab["tenant"]: v
             for lab, v in samples.get("tenant_ttft_seconds_count", [])}
    assert ttfts["acme-emit"] >= 1.0


def test_off_engine_finish_allocates_nothing():
    assert not rl.enabled()
    eng, cfg = _tiny_engine()
    rng = np.random.RandomState(1)
    eng.add_request(rng.randint(0, cfg.vocab_size, (6,)),
                    max_new_tokens=2)
    eng.run()                           # warm every family/cell
    reg = om.default_registry()
    a0 = reg.allocations
    eng.add_request(rng.randint(0, cfg.vocab_size, (6,)),
                    max_new_tokens=2)
    eng.run()
    assert reg.allocations == a0        # no tenant cells minted
    assert eng._tenant_cells == {}
    assert rl.records_taken() == 0 and rl.log() is None


def test_abort_emits_no_record(reqlog_on):
    eng, cfg = _tiny_engine()
    rng = np.random.RandomState(2)
    rid = eng.add_request(rng.randint(0, cfg.vocab_size, (6,)),
                          max_new_tokens=4)
    assert eng.abort(rid)
    eng.run()
    assert rl.history() == []           # vLLM semantics: finished
    # requests are billed, aborted ones simply vanish


# ---------------------------------------------------------------------------
# tenant identity across the disaggregated handoff
# ---------------------------------------------------------------------------


def test_disagg_handoff_keeps_tenant_and_trace(reqlog_on, tracer):
    from paddle_tpu.inference import DisaggregatedServing

    pe, cfg = _tiny_engine()
    de, _ = _tiny_engine()
    rng = np.random.RandomState(5)
    out = DisaggregatedServing(pe, de).generate(
        rng.randint(0, cfg.vocab_size, (6,)), max_new_tokens=3,
        tenant="acme-disagg")
    assert out["ok"]
    rows = rl.history()
    assert len(rows) == 1               # ONE record fleet-wide: the
    rec = rows[0]                       # decode engine emits, the
    assert rec["tenant"] == "acme-disagg"   # prefill engine does not
    assert rec["attached"] is True
    assert rec["prompt_tokens"] == 6 and rec["output_tokens"] == 3
    # the record's trace_id IS the stitched trace: prefill spans on
    # engine A carry the same id the ledger row links to
    prefill_ids = {e["args"]["trace_id"]
                   for e in tracer.to_chrome_trace()
                   if e.get("ph") == "X"
                   and e["name"] == "serving.prefill"}
    assert prefill_ids == {int(rec["trace_id"], 16)}


@pytest.mark.slow
def test_http_handoff_keeps_tenant_from_body(reqlog_on):
    """Tenant rides KVHandoff.req_params over the real /v1/kv_handoff
    wire: prefill host -> HTTP -> decode replica, one record."""
    from paddle_tpu.inference import DisaggregatedServing
    from paddle_tpu.inference.replica import ReplicaServer

    pe, cfg = _tiny_engine(max_seq_len=64)
    de, _ = _tiny_engine(max_seq_len=64)
    pe.warmup(prompt_len=10)
    de.warmup(prompt_len=10)
    rng = np.random.RandomState(23)
    srv = httpd.start_server(port=0, host="127.0.0.1")
    server = ReplicaServer(de).start()
    try:
        dis = DisaggregatedServing(pe, f"http://127.0.0.1:{srv.port}")
        (out,) = dis.generate_many([dict(
            prompt_ids=rng.randint(0, cfg.vocab_size, (10,)),
            max_new_tokens=4, tenant="acme-wire")])
        assert out["ok"], out.get("error")
    finally:
        server.stop()
        httpd.stop_server()
    rows = rl.history()
    assert len(rows) == 1
    assert rows[0]["tenant"] == "acme-wire"
    assert rows[0]["attached"] is True
    assert rows[0]["output_tokens"] == 4


@pytest.mark.slow
def test_replica_adopts_x_pt_tenant_header(reqlog_on):
    """No body field at all: the raw X-PT-Tenant header parked by the
    httpd is adopted by add_request on the handler thread."""
    from paddle_tpu.inference.replica import ReplicaServer

    eng, cfg = _tiny_engine()
    srv = httpd.start_server(port=0, host="127.0.0.1")
    server = ReplicaServer(eng).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/generate",
            data=json.dumps({"prompt_ids": [3, 5, 7],
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     rl.TENANT_HEADER: "hdr-tenant"})
        out = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert out["ok"]
    finally:
        server.stop()
        httpd.stop_server()
    rows = rl.history()
    assert len(rows) == 1 and rows[0]["tenant"] == "hdr-tenant"


# ---------------------------------------------------------------------------
# OpenMetrics exemplars + the strict exposition parser
# ---------------------------------------------------------------------------


def test_histogram_exemplar_renders_and_parser_survives():
    reg = om.Registry()
    h = reg.histogram("demo_seconds", "Demo latency.")
    h.observe(0.004, exemplar={"trace_id": "deadbeef"})
    h.observe(0.004)                    # same bucket, no exemplar
    text = om.to_prometheus(reg)
    (ex_line,) = [ln for ln in text.splitlines()
                  if "# {" in ln and "demo_seconds_bucket" in ln]
    assert ex_line.rstrip().endswith('# {trace_id="deadbeef"} 0.004')
    # the scraper's strict parser must read the CUMULATIVE COUNT, not
    # the exemplar value trailing it (the greedy-brace hazard)
    samples = fleet_mod._parse_prom_samples(text)
    bucket = [v for lab, v in samples["demo_seconds_bucket"]
              if lab.get("le") == "0.005"]
    assert bucket == [2.0]


def test_exemplar_off_path_allocates_nothing():
    h = om.Registry().histogram("plain_seconds", "No exemplars.")
    h.observe(0.1)
    assert h._ex is None                # lazy: no dict until the
    assert h.exemplars() == {}          # first exemplared observe


def test_ttft_exemplar_links_trace_to_histogram(reqlog_on, tracer):
    eng, cfg = _tiny_engine()
    rng = np.random.RandomState(3)
    eng.add_request(rng.randint(0, cfg.vocab_size, (6,)),
                    max_new_tokens=2)
    eng.run()
    (rec,) = rl.history()
    text = om.to_prometheus()
    ttft_ex = [ln for ln in text.splitlines()
               if "serving_ttft_seconds_bucket" in ln and "# {" in ln]
    assert ttft_ex, "TTFT observation carried no exemplar"
    # the exemplar names the SAME trace the ledger record links to (on the
    # bucket this observation fell into: the registry is the process's, so
    # another bucket may still hold an earlier test's exemplar, and a first
    # token that waited for a slow compile lands past 0.5 s)
    assert any(f'trace_id="{rec["trace_id"]}"' in ln for ln in ttft_ex)
    # and the fleet parser still reads every ttft bucket as a count
    parsed = fleet_mod._parse_prom_samples(text)
    for _lab, v in parsed["serving_ttft_seconds_bucket"]:
        assert v == float(int(v))   # counts, never the exemplar value


# ---------------------------------------------------------------------------
# /debug/requests
# ---------------------------------------------------------------------------


def test_debug_requests_endpoint_filters_and_reports(reqlog_on):
    for i in range(4):
        rl.record({"rid": i, "tenant": "a" if i % 2 else "b",
                   "prompt_tokens": i, "output_tokens": 1})
    srv = httpd.start_server(port=0, host="127.0.0.1")
    base = f"http://127.0.0.1:{srv.port}"
    with urllib.request.urlopen(base + "/debug/requests", timeout=10) \
            as r:
        doc = json.loads(r.read())
    assert doc["enabled"] is True
    assert [x["rid"] for x in doc["records"]] == [0, 1, 2, 3]
    assert doc["usage"]["a"]["requests"] == 2
    with urllib.request.urlopen(
            base + "/debug/requests?tenant=a&last=1", timeout=10) as r:
        doc = json.loads(r.read())
    assert [x["rid"] for x in doc["records"]] == [3]
    assert doc["tenant"] == "a"


def test_debug_requests_endpoint_off(monkeypatch):
    srv = httpd.start_server(port=0, host="127.0.0.1")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/requests",
            timeout=10) as r:
        doc = json.loads(r.read())
    assert doc["enabled"] is False and doc["records"] == []


# ---------------------------------------------------------------------------
# fleet: flush, scrape, usage table, report gate
# ---------------------------------------------------------------------------


def _flush_sources():
    from paddle_tpu import observability as obs

    return dict(registry=obs.Registry(), tracer=obs.Tracer(),
                recorder=obs.FlightRecorder(),
                log=fleet_mod.CollectiveLog())


def _seed_records():
    rl.record({"rid": 0, "tenant": "acme", "prompt_tokens": 10,
               "output_tokens": 5, "ttft_s": 0.2, "total_s": 0.9,
               "outcome": "ok"})
    rl.record({"rid": 1, "tenant": "acme", "prompt_tokens": 4,
               "output_tokens": 2, "outcome": "error"})
    rl.record({"rid": 2, "tenant": "beta", "prompt_tokens": 3,
               "output_tokens": 1, "ttft_s": 0.1, "total_s": 0.3,
               "outcome": "ok"})


def test_flush_writes_requests_jsonl(reqlog_on, tmp_path):
    _seed_records()
    exp = fleet_mod.FleetExporter(str(tmp_path), rank=0, world_size=1,
                                  interval=60, **_flush_sources())
    exp.flush()
    rows = [json.loads(ln) for ln in
            (tmp_path / "rank_0" / "requests.jsonl")
            .read_text().splitlines()]
    assert [r["rid"] for r in rows] == [0, 1, 2]
    assert rows[0]["tenant"] == "acme"


def test_flush_off_still_writes_empty_shard_file(tmp_path):
    exp = fleet_mod.FleetExporter(str(tmp_path), rank=0, world_size=1,
                                  interval=60, **_flush_sources())
    exp.flush()
    # the shard always holds the full SHARD_FILES set, so usage_table
    # and the doctor bundle never guess whether the channel ran
    assert "requests.jsonl" in fleet_mod.SHARD_FILES
    assert (tmp_path / "rank_0" / "requests.jsonl").read_text() == ""


def test_usage_table_ranks_hot_tenants(reqlog_on, tmp_path):
    _seed_records()
    exp = fleet_mod.FleetExporter(str(tmp_path), rank=0, world_size=1,
                                  interval=60, **_flush_sources())
    exp.flush()
    table = fleet_mod.usage_table({0: str(tmp_path / "rank_0")})
    assert table["requests"] == 3
    acme, beta = table["tenants"]       # sorted by total tokens desc
    assert acme["tenant"] == "acme" and beta["tenant"] == "beta"
    assert acme["tokens"] == 21 and beta["tokens"] == 4
    assert acme["errors"] == 1
    assert acme["ttft_mean_ms"] == pytest.approx(200.0)
    assert table["ranks"] == [{"rank": 0, "requests": 3}]


def test_usage_table_empty_when_no_records(tmp_path):
    (tmp_path / "rank_0").mkdir()
    (tmp_path / "rank_0" / "requests.jsonl").write_text("")
    assert fleet_mod.usage_table({0: str(tmp_path / "rank_0")}) == {}


def test_report_renders_usage_section_and_gate(reqlog_on, tmp_path):
    _seed_records()
    exp = fleet_mod.FleetExporter(str(tmp_path), rank=0, world_size=1,
                                  interval=60, **_flush_sources())
    exp.flush()
    report = fleet_mod.aggregate(str(tmp_path))
    assert report["usage"]["requests"] == 3
    text = fleet_mod.format_report(report)
    assert "usage per tenant" in text
    assert "hot tenants (by total tokens): acme" in text
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "fleet_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "fleet_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([str(tmp_path), "--require-accounting"]) == 0


def test_require_accounting_gate_fails_without_records(tmp_path):
    exp = fleet_mod.FleetExporter(str(tmp_path), rank=0, world_size=1,
                                  interval=60, **_flush_sources())
    exp.flush()                         # shard exists, ledger empty
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "fleet_report", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "fleet_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([str(tmp_path), "--require-accounting"]) == 2


def test_scrape_pulls_live_ledger_into_shard(reqlog_on, tmp_path):
    _seed_records()
    srv = httpd.start_server(port=0, host="127.0.0.1")
    scraped = fleet_mod.scrape_to_shards(
        [f"127.0.0.1:{srv.port}"], str(tmp_path))
    assert "shard" in scraped[0]
    rows = [json.loads(ln) for ln in
            (tmp_path / "rank_0" / "requests.jsonl")
            .read_text().splitlines()]
    assert [r["rid"] for r in rows] == [0, 1, 2]


# ---------------------------------------------------------------------------
# fleet_top
# ---------------------------------------------------------------------------


def _load_fleet_top():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "fleet_top", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "fleet_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_top_sparkline_shapes():
    ftop = _load_fleet_top()
    assert ftop.sparkline([]) == "-"
    assert ftop.sparkline([0.0, 0.0]) == "  "
    line = ftop.sparkline([0.0, 0.5, 1.0], vmax=1.0)
    assert line[0] == " " and line[-1] == "█"
    assert len(ftop.sparkline(list(range(100)), width=24)) == 24


def test_fleet_top_once_frame_over_http(reqlog_on):
    _seed_records()
    srv = httpd.start_server(port=0, host="127.0.0.1")
    ftop = _load_fleet_top()
    ep = f"127.0.0.1:{srv.port}"
    polled = {0: ftop.poll_rank(fleet_mod, ep, 5.0, 60.0, 100)}
    text, usage = ftop.render_frame(polled, {}, 1000.0, None)
    assert "fleet-top" in text and "ranks: 1" in text
    assert "acme" in text and "beta" in text
    assert usage["acme"]["tokens"] == 21
    # second frame: token rates appear from the usage delta
    prev = {t: dict(u, tokens=u["tokens"] - 10) for t, u in
            usage.items()}
    text2, _ = ftop.render_frame(polled, prev, 1002.0, 1000.0)
    assert "5.0" in text2               # 10 tokens / 2 s
    # a dead endpoint renders as a DOWN row, never a crash
    polled[1] = ftop.poll_rank(fleet_mod, "127.0.0.1:9", 0.3, 60.0, 10)
    text3, _ = ftop.render_frame(polled, {}, 1000.0, None)
    assert "DOWN" in text3


def test_fleet_top_main_requires_endpoints(capsys):
    ftop = _load_fleet_top()
    assert ftop.main([]) == 2
