"""Black-box canary prober (ISSUE 18: observability/canary.py):
register/probe lifecycle, golden self-anchoring and explicit goldens,
mismatch / timeout / error classification with the anomaly verdicts
they raise, /healthz degradation, the statusz block, the
always-sampled canary trace, the background prober thread, and the
FLAGS_canary_interval_s off-path alloc guard."""
import json
import urllib.request

import pytest

from paddle_tpu.framework import config as _config
from paddle_tpu.observability import anomaly, canary, httpd, slo
from paddle_tpu.observability import metrics as om
from paddle_tpu.observability import tracing


@pytest.fixture(autouse=True)
def _clean():
    canary._reset_for_tests()
    anomaly._reset_for_tests()
    httpd._reset_for_tests()
    slo._reset_for_tests()
    # the poison gauge leaks from other suites through the process's
    # default registry (test_memwatch and test_faults poison engines on
    # purpose): without this /healthz reads 503 whenever such a file ran
    # first on the same worker (as test_telemetry_httpd.py's fixture says)
    om.default_registry().gauge("serving_engine_poisoned").set(0.0)
    yield
    canary._reset_for_tests()
    anomaly._reset_for_tests()
    httpd._reset_for_tests()
    slo._reset_for_tests()


def _send_ok(tokens):
    def send(prompt_ids, max_new, timeout_s):
        return {"ok": True, "output_ids": list(tokens),
                "ttft_s": 0.001}
    return send


# ---------------------------------------------------------------------------
# probe lifecycle
# ---------------------------------------------------------------------------


def test_probe_without_target_is_noop():
    assert canary.probe_once() == {"result": "no_target"}
    assert canary.healthy() is None


def test_probe_ok_self_anchors_golden():
    canary.register_target("t", _send_ok([7, 8, 9]))
    assert canary.golden() is None
    out = canary.probe_once()
    assert out["result"] == "ok" and out["tokens"] == [7, 8, 9]
    assert canary.golden() == [7, 8, 9]     # first green probe anchors
    assert canary.probe_once()["result"] == "ok"
    assert canary.healthy() is True
    st = canary.status()
    assert st["probes"] == 2 and st["failures"] == 0
    assert st["last_result"] == "ok" and st["golden_len"] == 3
    reg = om.default_registry()
    cells = {lbl["result"]: c.value
             for lbl, c in reg.get("canary_probes_total").samples()}
    assert cells["ok"] == 2.0
    ok_cells = [c for _, c in reg.get("canary_ok").samples()]
    assert ok_cells[0].value == 1.0


def test_probe_mismatch_raises_verdict_then_clears():
    tokens = [1, 2, 3]

    def send(prompt_ids, max_new, timeout_s):
        return {"ok": True, "output_ids": list(tokens)}

    canary.register_target("t", send)
    assert canary.probe_once()["result"] == "ok"   # anchors [1,2,3]
    tokens[:] = [1, 2, 4]                          # silent divergence
    out = canary.probe_once()
    assert out["result"] == "mismatch"
    assert canary.healthy() is False
    v = [v for v in anomaly.latest() if v["kind"] == "canary_mismatch"]
    assert v and v[0]["severity"] == 0.9
    assert canary.status()["consecutive_failures"] == 1
    tokens[:] = [1, 2, 3]                          # green again
    assert canary.probe_once()["result"] == "ok"
    assert canary.healthy() is True
    assert anomaly.latest() == []                  # verdict cleared


def test_explicit_golden_mismatches_immediately():
    canary.register_target("t", _send_ok([9, 9]), golden=[1, 2])
    assert canary.probe_once()["result"] == "mismatch"
    assert canary.golden() == [1, 2]   # explicit golden never re-anchors


def test_probe_timeout_and_error_raise_canary_timeout(monkeypatch):
    canary.register_target("t", _send_ok([1]))
    monkeypatch.setattr(_config._FLAGS["FLAGS_canary_timeout_s"],
                        "value", 0.0)   # any real probe overruns
    out = canary.probe_once()
    assert out["result"] == "timeout"
    v = [v for v in anomaly.latest() if v["kind"] == "canary_timeout"]
    assert v and v[0]["severity"] == 0.7
    monkeypatch.setattr(_config._FLAGS["FLAGS_canary_timeout_s"],
                        "value", 10.0)

    def send_err(prompt_ids, max_new, timeout_s):
        return {"ok": False, "error": "replica is down"}

    canary.register_target("t2", send_err)
    assert canary.probe_once()["result"] == "error"
    assert canary.healthy() is False
    v = [v for v in anomaly.latest() if v["kind"] == "canary_timeout"]
    assert v and v[0]["evidence"]["reason"] == "error"


def test_probe_exception_is_a_verdict_not_a_crash():
    def send_boom(prompt_ids, max_new, timeout_s):
        raise RuntimeError("socket exploded")

    canary.register_target("t", send_boom)
    out = canary.probe_once()
    assert out["result"] == "error"
    assert "socket exploded" in out["error"]
    assert canary.healthy() is False


def test_canary_trace_is_always_sampled(monkeypatch):
    # head sampling at ~0 would drop every normal trace; the canary
    # installs a pre-sampled context so its probe timeline always lands
    monkeypatch.setattr(_config._FLAGS["FLAGS_trace_sample"],
                        "value", 1e-9)
    canary.register_target("t", _send_ok([1, 2]))
    tracer = tracing.default_tracer()
    base = tracer.spans_created
    canary.probe_once()
    assert tracer.spans_created > base


# ---------------------------------------------------------------------------
# health / statusz / endpoint surfacing
# ---------------------------------------------------------------------------


def test_healthz_degrades_on_canary_failure():
    code, payload = httpd.health_payload()
    assert "canary_ok" not in payload          # canary never ran
    canary.register_target("t", _send_ok([5]), golden=[6])
    canary.probe_once()                        # mismatch
    code, payload = httpd.health_payload()
    assert code == 200                         # alive — not a liveness fail
    assert payload["status"] == "degraded"
    assert payload["canary_ok"] is False
    canary.register_target("t", _send_ok([6]), golden=[6])
    canary.probe_once()
    code, payload = httpd.health_payload()
    assert payload["canary_ok"] is True
    assert payload["status"] == "ok"


def test_statusz_and_debug_anomalies_carry_canary_block():
    srv = httpd.start_server(port=0, host="127.0.0.1")
    base = f"http://127.0.0.1:{srv.port}"
    canary.register_target("t", _send_ok([5]), golden=[6])
    canary.probe_once()
    with urllib.request.urlopen(base + "/statusz", timeout=10) as r:
        st = json.loads(r.read())
    assert st["canary"]["target"] == "t"
    assert st["canary"]["last_result"] == "mismatch"
    assert st["canary"]["probes"] == 1
    with urllib.request.urlopen(base + "/debug/anomalies",
                                timeout=10) as r:
        doc = json.loads(r.read())
    assert doc["canary"]["last_result"] == "mismatch"
    assert [v["kind"] for v in doc["verdicts"]] == ["canary_mismatch"]


# ---------------------------------------------------------------------------
# background prober + off-path contract
# ---------------------------------------------------------------------------


def test_ensure_prober_runs_on_interval(monkeypatch):
    import time as _time

    canary.register_target("t", _send_ok([3, 4]))
    monkeypatch.setattr(_config._FLAGS["FLAGS_canary_interval_s"],
                        "value", 0.02)
    th = canary.ensure_prober()
    assert th is not None
    assert canary.ensure_prober() is th        # idempotent
    deadline = _time.monotonic() + 10.0
    while canary.status()["probes"] < 2 and _time.monotonic() < deadline:
        _time.sleep(0.02)
    assert canary.status()["probes"] >= 2
    assert canary.healthy() is True


def test_off_path_allocates_nothing():
    assert not canary.enabled()
    assert canary.ensure_prober() is None      # no target, no thread
    canary.register_target("t", _send_ok([1]))
    reg = om.default_registry()
    base_alloc = reg.allocations
    for _ in range(5):
        assert canary.ensure_prober() is None  # flag off: one flag read
    assert canary.probes == 0
    assert reg.allocations == base_alloc
    assert canary.healthy() is None
