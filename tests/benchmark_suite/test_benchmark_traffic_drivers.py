"""The traffic generator, the serving window's arithmetic on a scripted
clock, and every driver end to end at a tiny configuration on the CPU."""
import numpy as np
import pytest

from benchmark_suite_helpers import (Clock, ScriptedEngine, tiny_cell,
                                     tiny_measure)

from benchmark import program, serve_loop, traffic
from benchmark.hostlog import HostLog

MIX = {"arrivals": {"process": "poisson"},
       "prompt_tokens": {"dist": "log_uniform", "lo": 8, "hi": 40},
       "output_tokens": {"dist": "log_uniform", "lo": 3, "hi": 12}}


def _key(requests):
    return [(round(r.due, 9), r.prompt.tolist(), r.max_new_tokens)
            for r in requests]


def test_a_mix_is_a_pure_function_of_the_seed():
    a = traffic.serve_requests(MIX, 5, 10.0, 128, rate=4.0)
    b = traffic.serve_requests(MIX, 5, 10.0, 128, rate=4.0)
    c = traffic.serve_requests(MIX, 6, 10.0, 128, rate=4.0)
    assert _key(a) == _key(b) != _key(c)
    big = traffic.serve_requests(MIX, 3_000_000_123, 10.0, 128, rate=4.0)
    assert len(big) == 40


def test_every_seed_gets_the_same_lengths_and_gaps_in_another_order():
    a = traffic.serve_requests(MIX, 1, 10.0, 128, rate=4.0)
    b = traffic.serve_requests(MIX, 2, 10.0, 128, rate=4.0)
    assert len(a) == len(b) == 40
    for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(field, a)) == sorted(map(field, b))
        assert list(map(field, a)) != list(map(field, b))
    gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs]), 9))  # noqa: E731
    assert gaps(a)[1:] == pytest.approx(gaps(b)[1:], abs=1e-6) \
        or sum(gaps(a)) == pytest.approx(sum(gaps(b)), rel=0.05)
    assert all(0 <= r.due < 10.0 for r in a + b)
    lens = [len(r.prompt) for r in a]
    assert min(lens) >= 8 and max(lens) <= 40
    assert all(0 <= t < 128 for r in a for t in r.prompt)


def test_a_schedule_seed_fixes_the_order_and_leaves_the_ids_to_the_seed():
    mix = dict(MIX, schedule_seed=24)
    a = traffic.serve_requests(mix, 1, 10.0, 128, rate=4.0)
    b = traffic.serve_requests(mix, 2, 10.0, 128, rate=4.0)
    assert [(r.due, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due, len(r.prompt), r.max_new_tokens) for r in b]
    assert _key(a) != _key(b)
    assert _key(a) == _key(traffic.serve_requests(mix, 1, 10.0, 128,
                                                  rate=4.0))


def test_closed_mix_deals_a_pool_to_clients_and_train_rows_all_differ():
    mix = dict(MIX, arrivals={"process": "closed", "clients": 3, "pool": 30})
    reqs = traffic.serve_requests(mix, 4, 10.0, 128)
    assert len(reqs) == 30 and {r.client for r in reqs} == {0, 1, 2}
    rng = np.random.default_rng(0)
    x, y = traffic.train_batch({"batch_rows": 4, "seq_len": 16}, rng, 128)
    assert x.shape == y.shape == (4, 16)
    assert len({tuple(r) for r in x}) == 4 and not (x == y).all()


def test_other_distributions_and_shared_prefixes():
    mix = {"arrivals": {"process": "gamma", "cv": 3.0},
           "prompt_tokens": {"dist": "uniform", "lo": 20, "hi": 30},
           "output_tokens": {"dist": "fixed", "value": 5},
           "shared_prefix": {"tokens": {"dist": "fixed", "value": 12},
                             "pool": 2}}
    reqs = traffic.serve_requests(mix, 9, 20.0, 128, rate=3.0)
    assert len(reqs) == 60 and all(r.max_new_tokens == 5 for r in reqs)
    heads = {tuple(r.prompt[:12]) for r in reqs}
    assert len(heads) == 2
    assert traffic.length_support(mix["output_tokens"]) == (5, 5)
    assert traffic.length_support(mix["prompt_tokens"]) == (20, 30)


def _window(engine, clock, requests, seconds, **kw):
    log = HostLog(clock=clock)
    records = serve_loop.run_window(
        engine, requests, seconds, log, clock=clock, sleep=clock.sleep, **kw)
    return serve_loop.end_to_end(records, seconds, log), records, log


def _requests(n, gap, tokens=5):
    return [traffic.Request(i, i * gap, np.full(4, i % 5), tokens)
            for i in range(n)]


def test_window_arithmetic_on_a_scripted_clock():
    clock = Clock()
    engine = ScriptedEngine(clock, slots=4, step_s=0.01)
    (m, attempted, failed), records, log = _window(
        engine, clock, _requests(20, 0.045), 1.0)
    assert attempted == 20 and failed == 0
    # the program receives only generated inputs
    assert engine.seen == [([i % 5] * 4, 5) for i in range(20)]
    # 20 requests x 5 tokens, all inside the window
    assert m["out_tokens_per_s"] == pytest.approx(100.0)
    # every first token comes one step after its arrival's loop turn
    assert 10.0 <= m["ttft_p95_ms"] <= 25.0
    assert m["tpot_p95_ms"] == pytest.approx(10.0, abs=1e-6)
    lags = [v[1] for v in log.samples["gen_lag_s"]]
    assert max(lags) <= 0.011 and min(lags) >= 0.0


def test_a_stall_in_the_window_moves_all_three_metrics():
    base_clock, stall_clock = Clock(), Clock()
    (base, _, _), _, _ = _window(
        ScriptedEngine(base_clock, slots=4, step_s=0.01), base_clock,
        _requests(20, 0.045, tokens=12), 1.0)
    (stalled, attempted, failed), records, _ = _window(
        ScriptedEngine(stall_clock, slots=4, step_s=0.01, stall_at=30,
                       stall_s=0.8), stall_clock,
        _requests(20, 0.045, tokens=12), 1.0)
    assert attempted == 20 and failed == 0
    # requests that became due during the stall are timed from when they
    # were DUE, not from when the stalled loop handed them over
    assert stalled["ttft_p95_ms"] > base["ttft_p95_ms"] + 400
    assert stalled["tpot_p95_ms"] > base["tpot_p95_ms"] + 50
    # tokens committed after the window closed do not count
    assert stalled["out_tokens_per_s"] < base["out_tokens_per_s"]
    late = [r for r in records if r.last > 1.0]
    assert late and all(r.done for r in late)


def test_unanswered_requests_count_as_failed():
    clock = Clock()
    engine = ScriptedEngine(clock, slots=4, step_s=0.01, drop_token=3)
    (m, attempted, failed), records, _ = _window(
        engine, clock, _requests(20, 0.045), 1.0, drain_seconds=2.0)
    assert attempted == 20 and failed == 4
    assert sum(1 for r in records if r.first is None) == 4


def test_closed_loop_sends_the_next_request_when_the_last_is_answered():
    clock = Clock()
    engine = ScriptedEngine(clock, slots=2, step_s=0.01)
    reqs = [traffic.Request(i, 0.0, np.full(4, 1), 5, client=i % 3)
            for i in range(300)]
    (m, attempted, failed), records, _ = _window(
        engine, clock, reqs, 1.0, clients=3)
    assert failed == 0 and 30 <= attempted <= 45
    # three clients on two slots: a backlog at all times, 2 tokens a step
    assert m["out_tokens_per_s"] == pytest.approx(200.0, rel=0.05)
    in_flight = max(sum(1 for r in records if r.added <= t and
                        (r.last is None or r.last > t))
                    for t in np.linspace(0.1, 0.9, 9))
    assert in_flight <= 3


def test_warm_up_covers_every_bucket_the_mix_can_produce():
    cell = tiny_cell("tiny-gpt.tiny-open")
    model = program.build_model(cell.config, 3, train=False)
    engine = program.build_engine(model, cell.config["engine"])
    lo, hi = traffic.length_support(cell.mix["prompt_tokens"])
    rounds = program.prefill_rounds(engine, lo, hi)
    keys = {k for k, _ in rounds}
    # what real admissions of this mix can ask for, from the policy itself
    for n in range(1, engine.max_batch + 1):
        for length in (lo, 16, 17, 32, 33, hi):
            nb, bucket = engine.scheduler.prefill_bucket(
                engine, [(i, range(length)) for i in range(n)])
            assert (n, nb, bucket) in keys
    for (n, nb, bucket), length in rounds:
        assert lo <= length <= hi and length <= bucket and n <= nb


@pytest.mark.parametrize("name,metric", [
    ("tiny-gpt.tiny-open", "ttft_p95_ms"),
    ("tiny-gpt.tiny-closed", "out_tokens_per_s"),
    ("tiny-gpt.tiny-train", "train_tokens_per_s")])
def test_each_driver_runs_end_to_end_on_the_cpu(name, metric):
    result = tiny_measure(name, seed=3_000_000_007, seconds=1.5)
    assert list(result)[-1] == "checks"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"][metric]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"  # never a device metric
    for row in result["checks"].values():
        assert row["limit"] is None or row["value"] <= row["limit"]
