"""Scheduler-policy extraction: golden parity + SLO-aware choices.

The golden trace (tests/data/serving_golden_trace.json) holds scripted
traffic exercising all four extracted decisions — staggered FIFO
admission, recompute preemption under a withheld (tight) page pool,
prefill bucketing across mixed prompt lengths, and {1, decode_burst}
burst sizing — and the token streams the default policy produced for it.
The default policy must reproduce those streams bit-identically (ISSUE 13
acceptance). tools/capture_serving_golden_trace.py records the file (and
names the jax it was recorded under: the streams follow the weights
`paddle.seed` draws, which follow jax's random streams) and owns the
replay loop used here."""
import json

import numpy as np
import pytest

from conftest import load_repo_script
from paddle_tpu.framework import config as _cfg
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.scheduler import (FifoSchedulerPolicy,
                                            SchedulerPolicy,
                                            SloAwareSchedulerPolicy,
                                            available_policies,
                                            resolve_policy)

_capture = load_repo_script("tools/capture_serving_golden_trace.py")

with open(_capture.GOLDEN) as f:
    _TRACE = json.load(f)


def _tiny_model():
    return _capture.tiny_model(_TRACE["model"])


def _replay(scenario, scheduler=None):
    return _capture.replay(_TRACE["scenarios"][scenario], _tiny_model(),
                           scheduler=scheduler)


def test_golden_trace_was_recorded_under_this_jax():
    import jax

    assert _TRACE["jax_version"] == jax.__version__, (
        "re-record with tools/capture_serving_golden_trace.py: the token "
        "streams follow jax's random streams through the seeded weights")


# marked per-scenario: single_step is the tier-1 canary; the rest ride
# in the full (slow-inclusive) CI run
@pytest.mark.parametrize("scenario", [
    "single_step",
    pytest.param("burst4", marks=pytest.mark.slow),
    pytest.param("preempt", marks=pytest.mark.slow),
    pytest.param("mixed_sampling", marks=pytest.mark.slow),
])
def test_default_policy_matches_golden_trace(scenario):
    sc = _TRACE["scenarios"][scenario]
    outputs, preemptions = _replay(scenario)
    assert outputs == sc["outputs"], (
        f"{scenario}: refactored default policy diverged from the "
        f"pre-refactor engine's token streams")
    assert preemptions == sc["preemptions"]


def test_golden_trace_exercises_preemption():
    # the trace is only a refactor guard if the victim decision runs
    assert any(sc["preemptions"] > 0
               for sc in _TRACE["scenarios"].values())


# ---------------------------------------------------------------------------
# registry / resolution
# ---------------------------------------------------------------------------


def test_policy_registry_and_resolution():
    assert "fifo" in available_policies()
    assert "slo" in available_policies()
    assert isinstance(resolve_policy(), FifoSchedulerPolicy)  # flag default
    assert isinstance(resolve_policy("slo"), SloAwareSchedulerPolicy)
    inst = FifoSchedulerPolicy()
    assert resolve_policy(inst) is inst
    with pytest.raises(ValueError, match="unknown scheduler policy"):
        resolve_policy("nope")


def test_engine_resolves_policy_from_flag():
    old = _cfg.get_flag("FLAGS_scheduler_policy")
    _cfg.set_flags({"FLAGS_scheduler_policy": "slo"})
    try:
        eng = ServingEngine(_tiny_model(), max_batch=2, max_seq_len=32,
                            page_size=8)
        assert isinstance(eng.scheduler, SloAwareSchedulerPolicy)
    finally:
        _cfg.set_flags({"FLAGS_scheduler_policy": old})


# ---------------------------------------------------------------------------
# SLO-aware choices (pure policy units over a fake engine)
# ---------------------------------------------------------------------------


class _FakeSlot:
    def __init__(self, admit_seq, tokens=0, max_new=0):
        self.admit_seq = admit_seq
        self.tokens = [0] * tokens
        self.max_new_tokens = max_new


class _FakeEngine:
    def __init__(self, slots=(), pending=(), free_pages=64, page_size=8):
        self.slots = list(slots)
        self._pending = list(pending)
        self._free_pages = list(range(free_pages))
        self.page_size = page_size


def _pending_entry(rid, prompt_len, prior_len=0):
    return (rid, np.zeros((prompt_len,), np.int64), 8,
            [0] * prior_len)


def test_default_victim_is_youngest():
    eng = _FakeEngine(slots=[_FakeSlot(5), _FakeSlot(9), _FakeSlot(2)])
    pol = FifoSchedulerPolicy()
    assert pol.select_victim(eng, [0, 1, 2], "page_stall") == 1
    assert pol.select_victim(eng, [0, 2], "decode_oom") == 0


def test_slo_victim_is_most_remaining_budget():
    # slot 0: 2 of 10 done (rem 8); slot 1: 9 of 10 done (rem 1);
    # slot 2: 4 of 12 done (rem 8, younger than slot 0)
    eng = _FakeEngine(slots=[
        _FakeSlot(admit_seq=0, tokens=2, max_new=10),
        _FakeSlot(admit_seq=1, tokens=9, max_new=10),
        _FakeSlot(admit_seq=2, tokens=4, max_new=12),
    ])
    pol = SloAwareSchedulerPolicy(firing_fn=lambda: [])
    # never the nearly-finished slot; ties on remaining go youngest
    assert pol.select_victim(eng, [0, 1, 2], "page_stall") == 2
    assert pol.select_victim(eng, [0, 1], "decode_oom") == 0


def test_slo_admission_fifo_when_not_burning():
    eng = _FakeEngine(pending=[_pending_entry(0, 9),
                               _pending_entry(1, 3)])
    pol = SloAwareSchedulerPolicy(firing_fn=lambda: [])
    assert pol.select_admission(eng) == 0


def test_slo_admission_shortest_first_when_ttft_burns():
    eng = _FakeEngine(pending=[_pending_entry(0, 9),
                               _pending_entry(1, 3),
                               _pending_entry(2, 6)])
    pol = SloAwareSchedulerPolicy(firing_fn=lambda: ["ttft_p95"])
    assert pol.select_admission(eng) == 1
    # prior (preempted) tokens count toward the context length
    eng2 = _FakeEngine(pending=[_pending_entry(0, 4, prior_len=9),
                                _pending_entry(1, 6)])
    pol2 = SloAwareSchedulerPolicy(firing_fn=lambda: ["ttft_p95"])
    assert pol2.select_admission(eng2) == 1


def test_slo_admission_skips_unfitting_heads_under_burn():
    # head needs 2 pages but only 1 is free; the shorter fit wins
    eng = _FakeEngine(pending=[_pending_entry(0, 12),
                               _pending_entry(1, 5)],
                      free_pages=1, page_size=8)
    pol = SloAwareSchedulerPolicy(firing_fn=lambda: ["ttft_p95"])
    assert pol.select_admission(eng) == 1
    # nothing fits -> None (engine stops the admission round)
    eng2 = _FakeEngine(pending=[_pending_entry(0, 12)],
                       free_pages=1, page_size=8)
    pol2 = SloAwareSchedulerPolicy(firing_fn=lambda: ["ttft_p95"])
    assert pol2.select_admission(eng2) is None


def test_slo_admission_hol_blocks_like_fifo_when_head_too_big():
    # not burning + head doesn't fit -> FIFO head-of-line contract
    eng = _FakeEngine(pending=[_pending_entry(0, 12),
                               _pending_entry(1, 5)],
                      free_pages=1, page_size=8)
    pol = SloAwareSchedulerPolicy(firing_fn=lambda: [])
    assert pol.select_admission(eng) is None


def test_slo_firing_cache_ttl():
    calls = []
    t = [0.0]
    pol = SloAwareSchedulerPolicy(
        firing_fn=lambda: calls.append(1) or ["ttft_p95"],
        clock=lambda: t[0])
    eng = _FakeEngine(pending=[_pending_entry(0, 3)])
    pol.select_admission(eng)
    pol.select_admission(eng)
    assert len(calls) == 1  # within TTL: one evaluation
    t[0] += 1.0
    pol.select_admission(eng)
    assert len(calls) == 2


def test_slo_broken_firing_fn_does_not_stop_admission():
    def _boom():
        raise RuntimeError("slo plane down")

    eng = _FakeEngine(pending=[_pending_entry(0, 3)])
    pol = SloAwareSchedulerPolicy(firing_fn=_boom)
    assert pol.select_admission(eng) == 0  # falls back to FIFO


def test_base_policy_burst_bucketing():
    class _E:
        decode_burst = 4
        max_batch = 4
        page_size = 8

    pol = SchedulerPolicy()
    assert pol.burst_k(_E(), [0, 1], {0: 5, 1: 1}) == 4
    assert pol.burst_k(_E(), [0, 1], {0: 1, 1: 1}) == 1
    _E.decode_burst = 1
    assert pol.burst_k(_E(), [0], {0: 9}) == 1


def test_base_policy_prefill_bucket():
    class _E:
        max_batch = 8
        page_size = 16
        max_seq_len = 256

    pol = SchedulerPolicy()
    ids = lambda n: list(range(n))  # noqa: E731
    assert pol.prefill_bucket(_E(), [(0, ids(5))]) == (1, 16)
    assert pol.prefill_bucket(_E(), [(0, ids(5)), (1, ids(17)),
                                     (2, ids(3))]) == (4, 32)
    assert pol.prefill_bucket(
        _E(), [(i, ids(4)) for i in range(7)]) == (8, 16)
