"""The `pangu_ultra_moe` family's part of the benchmark: its weight table
against the program's parameters, its operations and bytes against
hand-worked numbers for the configuration BENCHMARK.json runs, the
`serve_family` driver on a scripted engine and end to end at a tiny size,
and each new reader on a hand-made trace written with xplane_writer.py."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark_suite_helpers import (DATA, REPO, TEST_PEAKS, Clock,
                                     ScriptedEngine, traced)  # noqa: F401
from benchmark_suite_helpers import pangu_host as _host_log
from benchmark_suite_helpers import pangu_raw as _raw

from benchmark import families, flops, manifest, run, serve_loop, \
    trace_reduce, traffic
from benchmark.drivers import serve, serve_family
from benchmark.families import pangu_ultra_moe as family
from benchmark.hostlog import HostLog

MS = 1_000_000  # ns
CELL = "openpangu-ultra-moe-ep16-l5.decode-closed"


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real():
    """The configuration the benchmark runs."""
    return manifest.load_cell(CELL).config


@pytest.fixture(scope="module")
def tiny():
    return _read(DATA, "configs", "tiny-pangu.json")


def tiny_cell(tiny, seconds_of_answers=(3, 6)):
    """A cell of the tiny configuration, made by hand: a Cell is data."""
    mix = {"kind": "serve_family",
           "arrivals": {"process": "closed", "clients": 6, "pool": 60},
           "prompt_tokens": {"dist": "log_uniform", "lo": 4, "hi": 16},
           "output_tokens": {"dist": "log_uniform",
                             "lo": seconds_of_answers[0],
                             "hi": seconds_of_answers[1]},
           "drain_seconds": 30, "check_requests": 3, "trace_seconds": 1,
           "schedule_seed": 3}
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tpot_p95_ms", "ms"), ("out_tokens_per_s", "tokens/s"),
        ("setup_s", "s"))]
    return manifest.Cell(
        name="tiny-pangu.tiny-closed", chips=1, config_name="tiny-pangu",
        config=tiny, mix_name="tiny-closed", mix=mix,
        params={"limits": {"logit_gap_mean": 1e-4, "logit_gap_p99": 1e-3,
                           "logit_gap_max": None}},
        end_to_end=e2e,
        per_layer=[], peaks=dict(TEST_PEAKS))


# -- the weight table ---------------------------------------------------------


@pytest.mark.parametrize("sandwich", [True, False])
def test_the_table_names_the_programs_parameters_in_order(tiny, sandwich):
    from paddle_tpu.models import LatentMoEForCausalLM

    cfg = dict(tiny, sandwich_norm=sandwich)
    model = LatentMoEForCausalLM(family.model_config(cfg))
    want = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    got = [(n, tuple(s)) for n, s, _, _ in family.leaf_specs(cfg)]
    assert got == want
    assert family.param_count(cfg) == sum(
        int(np.prod(s)) for _, s in want)


def test_weights_are_a_function_of_the_seed_and_gains_stand_off_one(tiny):
    a = family.make_weights(tiny, 5, "float32")
    b = family.make_weights(tiny, 5, "float32")
    c = family.make_weights(tiny, 2**31 + 11, "float32")
    name = "model.layers.1.mlp.experts.w_up"
    np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
    assert np.abs(np.asarray(a[name]) - np.asarray(c[name])).max() > 0.01
    assert abs(float(np.std(np.asarray(a[name]))) - 0.02) < 0.002
    gain = np.asarray(a["model.layers.0.post_mlp_layernorm.weight"])
    assert abs(gain.mean() - 1) < 0.02 and gain.std() > 0.005
    assert family.make_weights(tiny, 5)[name].dtype == jax.numpy.bfloat16


def test_the_built_model_holds_the_tables_weights(tiny):
    model = family.build_model(tiny, 9)
    assert not model.training
    w = family.make_weights(tiny, 9, tiny["dtype"])
    for name, p in model.named_parameters():
        np.testing.assert_array_equal(np.asarray(p._data),
                                      np.asarray(w[name]))
    cfg = model.config
    assert (cfg.n_routed_experts, cfg.ep_degree, cfg.ep_rank) == (16, 4, 0)
    assert model.model.layers[2].mlp.experts.held == 4


# -- operations and bytes, worked by hand for the benchmark's configuration ---


def test_parameters_and_bytes_of_the_cut(real):
    mla = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 \
        + 512 * 128 * 256 + 16384 * 7680
    assert family.attn_matrix_params(real) == mla == 196_575_232
    assert family.expert_params(real) == 3 * 7680 * 2048 == 47_185_920
    expert_layer = 17 * 47_185_920 + 256 * 7680          # 804.1 M
    matrices = 5 * mla + 3 * 7680 * 18432 + 4 * expert_layer \
        + 2 * 19200 * 7680
    assert matrices == 4_918_968_320                     # 4.92 B
    gains = 5 * (4 * 7680 + 1536 + 512) + 7680
    assert family.param_count(real) == matrices + gains
    assert round(family.weight_bytes(real) / 1e9, 2) == 9.84
    assert family.cache_bytes_per_token(real) == 1152
    assert family.expert_layers(real) == 4


def test_what_a_token_and_a_pair_cost(real):
    assert family.pairs_per_token(real) == 0.5
    per_token = 5 * 196_575_232 + 3 * 7680 * 18432 \
        + 4 * (1.5 * 47_185_920 + 256 * 7680)
    assert family.matmul_params_per_token(real, head=False) == per_token
    assert family.matmul_params_per_token(real) \
        == per_token + 19200 * 7680
    assert family.pair_flops(real) == 2 * 128 * (192 + 128)
    n = 1000
    assert family.prefill_flops(real, n) == pytest.approx(
        2 * per_token * n + 81_920 * 5 * n * (n + 1) // 2
        + 2 * 19200 * 7680)
    assert family.decode_flops(real, 700) == pytest.approx(
        2 * (per_token + 19200 * 7680) + 81_920 * 5 * 700)


def test_a_decode_step_reads_the_experts_hit_not_the_experts_held(real):
    # 16 rows: each of 16 held experts is missed with (1 - 8/256)^16
    hit = 16 * (1 - (31 / 32) ** 16)
    assert family.experts_hit(real, 16) == pytest.approx(hit)
    assert 6.3 < hit < 6.5
    outside = family.param_count(real) - 4 * 16 * 47_185_920 \
        - (19200 - 16) * 7680
    want = 2 * (outside + 4 * hit * 47_185_920) + 1152 * 5 * 9000
    assert family.decode_bytes(real, 9000, 16) == pytest.approx(want)
    assert 5.9e9 < want < 6.1e9
    # all sixteen read, as the program does today, is 9.7 GB
    assert family.decode_bytes(real, 0, 16, hit=16) == pytest.approx(
        2 * (family.param_count(real) - (19200 - 16) * 7680))
    assert 9.4e9 < family.decode_bytes(real, 0, 16, hit=16) < 9.7e9


def test_needs_dispatches_on_the_family_key(real):
    assert families.needs(real) is family
    gpt = _read(DATA, "configs", "tiny-gpt.json")
    need = families.needs(gpt)
    assert need.prefill_flops(gpt, 20) == flops.prefill_flops(gpt, 20)
    assert need.decode_flops(gpt, 25) == flops.decode_flops(gpt, 25)
    assert need.decode_bytes(gpt, 40, 2) == flops.decode_bytes(gpt, 40)


# -- the driver ---------------------------------------------------------------


def test_the_driver_is_serves_but_for_build_check_and_release():
    for name in ("warm", "window", "end_to_end", "sample_for_check"):
        assert getattr(serve_family, name) is getattr(serve, name)
    for name in ("build", "check", "release", "logit_gaps"):
        assert getattr(serve_family, name) is not getattr(serve, name)
    assert manifest.load_driver(
        manifest.load_cell(CELL).mix["kind"]) is serve_family


def test_the_closed_loop_on_a_scripted_engine(tiny):
    """24 clients over 16 slots: 8 requests always wait inside the engine,
    and a client's next request follows its last answer."""
    mix = manifest.load_cell(CELL).mix
    assert mix["arrivals"] == {"process": "closed", "clients": 24,
                               "pool": 1200}
    requests = traffic.serve_requests(mix, 5, 51, 19200)
    assert len(requests) == 1200
    assert {r.client for r in requests} == set(range(24))
    assert min(len(r.prompt) for r in requests) == 256
    assert 1020 <= max(len(r.prompt) for r in requests) <= 1024
    assert min(r.max_new_tokens for r in requests) == 128
    assert 380 <= max(r.max_new_tokens for r in requests) <= 384
    assert max(int(r.prompt.max()) for r in requests) < 19200
    clock = Clock()
    engine = ScriptedEngine(clock, slots=16, step_s=0.02)
    log = HostLog(clock=clock)
    records = serve_loop.run_window(
        engine, requests, 20.0, log, drain_seconds=60, clients=24,
        clock=clock, sleep=clock.sleep)
    values, attempted, failed = serve_family.end_to_end(
        type("S", (), {"records": records}), 20.0, log)
    assert failed == 0 and attempted >= 24 + 16
    assert values["tpot_p95_ms"] == pytest.approx(20.0, rel=0.05)
    # a client sends its next request only when its last is answered: 24
    # are in the engine at any time, 8 of them waiting for a slot
    answered = sum(1 for r in records if r.done)
    assert len(records) <= answered + 24
    assert len(engine.pending) + len(engine.active) == 0


def test_the_driver_end_to_end_on_the_cpu(tiny):
    cell = tiny_cell(tiny)
    result = run.measure(cell, 2**31 + 5, 1.5, 0, jax.devices(),
                         t_start=time.perf_counter())
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 6
    assert set(result["metrics"]) == {"tpot_p95_ms", "out_tokens_per_s",
                                      "setup_s"}
    checks = result["checks"]
    assert checks["logit_gap_max"]["value"] <= 1e-3
    assert checks["logit_gap_max"]["limit"] is None  # printed, not compared
    assert checks["logit_gap_mean"]["value"] <= checks["logit_gap_p99"][
        "value"] <= checks["logit_gap_max"]["value"]
    assert checks["picks_differ_share"]["limit"] is None
    assert 0 <= checks["picks_differ_share"]["value"] <= 1
    assert checks["tokens_out_of_vocab"]["value"] == 0


def test_release_frees_the_pools_and_the_weights(tiny):
    system = serve_family.build(tiny_cell(tiny), 3)
    engine, model = system.engine, system.model
    assert engine.k_pages and not engine.v_pages
    serve_family.release(system)
    assert system.engine is None and engine.k_pages is None
    assert all(p._data is None for p in model.parameters())


class _Rec:
    def __init__(self, prompt, tokens):
        self.request = type("R", (), {"prompt": prompt,
                                      "max_new_tokens": len(tokens)})
        self.tokens, self.done = list(tokens), True


def test_the_control_in_lower_precision_reads_wider_than_the_program(tiny):
    """The comparison that decides `correct`, at the tiny size: tokens the
    float32 reference itself puts first read 0; the bf16 control reads
    wider; a token altered where it is produced reads wider still."""
    from benchmark.reference import pangu_ultra_moe as reference

    w = family.make_weights(tiny, 4, "float32")
    rng = np.random.default_rng(0)
    sample = []
    for n in (7, 12):
        ids = list(rng.integers(0, tiny["vocab_size"], n))
        for _ in range(5):
            logits = reference.logits_at(w, tiny, ids, [len(ids) - 1])
            ids.append(int(np.asarray(logits).argmax()))
        sample.append(_Rec(np.asarray(ids[:n]), ids[n:]))
    gaps, flipped = serve_family.logit_gaps(tiny, w, sample, 24, 8)
    assert gaps.max() == 0.0 and len(gaps) == 10 and 0 <= flipped <= 1
    control, _ = serve_family.logit_gaps(tiny, w, sample, 24, 8, "fp8")
    assert control.max() > 1e-3 and control.mean() > 1e-4
    sample[0].tokens[2] = (sample[0].tokens[2] + 1) % tiny["vocab_size"]
    altered, _ = serve_family.logit_gaps(tiny, w, sample, 24, 8)
    assert altered.max() > control.max()


# -- the readers --------------------------------------------------------------


@pytest.mark.parametrize("metric, value", [
    ("decode_sub_ms.latent_attn", 3 / 4), ("decode_sub_ms.router", 1 / 4),
    ("decode_sub_ms.experts", 6 / 4), ("decode_sub_ms.shared_expert", 2 / 4),
    ("expert_pairs_per_step", 64 / 16), ("experts_hit_pct", 100 * 48 / 128)])
def test_what_the_program_says_of_its_expert_layers(tiny, traced, metric,
                                                    value):
    cell = tiny_cell(tiny)
    read = manifest.load_reader(metric)
    assert read(traced(_raw()), _host_log(), cell) == pytest.approx(value)
    assert read(None, HostLog(), cell) is None
    # a program without the finer scopes and the counts: nothing, no raise
    plain = _raw()
    for ev in plain["planes"][0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("/latent", "").replace("/router", "") \
            .replace("/experts", "").replace("/shared", "")
    for ev in plain["planes"][1]["lines"][0]["events"]:
        ev[3] = {}
    assert read(traced(plain), _host_log(), cell) is None


def test_the_finer_scope_is_the_name_right_behind_the_top_level_one():
    from benchmark import program_trace

    of = program_trace.path_of
    assert of("jit(pure_burst)/while/body/closed_call/mlp/experts/dot") \
        == "mlp/experts"
    assert of("jit(f)/transpose(jvp(attn))/latent/dot;x/y") == "attn/latent"
    assert of("jit(f)/attn") is None and of("jit(f)/while/dot") is None
    seconds = program_trace.program_side(_raw())["path_seconds"]
    assert seconds["jit_pure_burst"] == pytest.approx({
        "attn/latent": 0.003, "attn/dot_general": 0.002,
        "mlp/router": 0.001, "mlp/experts": 0.006, "mlp/shared": 0.002})


def test_the_family_shares_of_peak_and_roofline(tiny, traced):
    cell = tiny_cell(tiny)
    reduced, host = traced(_raw()), _host_log()
    peak = TEST_PEAKS["bf16_flops_per_s"]

    def read(name):
        return manifest.load_reader(name)(reduced, host, cell)

    ops = family.prefill_flops(tiny, 12) + family.prefill_flops(tiny, 20)
    assert read("mfu.prefill") == pytest.approx(
        100 * ops / peak / 0.100)
    assert read("prefill_roofline") == pytest.approx(
        100 * ops / peak / 0.020)
    ops += 8 * family.decode_flops(tiny, 20) + 4 * family.decode_flops(
        tiny, 30)
    assert read("mfu.serve") == pytest.approx(
        100 * ops / peak / 0.100)
    least = flops.roofline_seconds(
        1.5 * family.decode_flops(tiny, 35 / 1.5),
        family.decode_bytes(tiny, 35, 1.5), TEST_PEAKS)
    assert read("decode_roofline") == pytest.approx(
        100 * 4 * least / 0.020)
    assert 0 < read("decode_roofline")
    for name in ("mfu.serve", "mfu.prefill", "decode_roofline",
                 "prefill_roofline"):
        assert manifest.load_reader(name)(None, HostLog(), cell) is None
        assert manifest.load_reader(name)(
            trace_reduce.reduce({"planes": []}), HostLog(), cell) is None


def test_the_cell_reports_what_the_issue_lists():
    m = manifest.load_manifest(REPO)
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.mix_name == "decode-closed"
    assert {"tpot_p95_ms", "out_tokens_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    names = {e["name"] for e in cell.per_layer}
    assert {"mfu.serve", "decode_roofline", "mfu.prefill",
            "prefill_roofline", "experts_read_pct",
            "prefill_expert_rows_per_pair", "decode_sub_ms.experts",
            "decode_sub_ms.router",
            "decode_sub_ms.shared_expert", "decode_sub_ms.latent_attn",
            "expert_pairs_per_step", "experts_hit_pct", "queue_wait_p50_ms",
            "decode_step_ms", "decode_ms.attn", "decode_ms.mlp",
            "decode_ms.head", "decode_ms.other", "device_idle_pct.serve",
            "builds_in_trace"} <= names
    limits = cell.params["limits"]
    assert set(limits) == {"logit_gap_mean", "logit_gap_p99", "logit_gap_max"}
    assert limits["logit_gap_max"] is None  # flips on rounding: PERF.md 6
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_p99"] < 1
    entry = next(c for c in m["configs"] if c["name"] == cell.config_name)
    assert entry["source"] == cell.config["source"]
    assert cell.config["router_experts"] == 256
    assert cell.config["engine"] == {"max_batch": 16, "max_seq_len": 2048,
                                     "page_size": 256, "decode_burst": 16}
