"""The `jamba` family's part of the benchmark (AI21-Jamba2-3B): its weight
table against the program's parameters, its operations and bytes against
hand-worked numbers for the configuration BENCHMARK.json runs, the catalog
row's keys in the configuration's file, the engine's pools against the
table, the traffic mix, the `serve_family` driver end to end at a tiny
size, the control, the `serve_state` driver's comparison of the states a
slot holds, and both new readers on the hand-made trace."""
import json
import math
import os
import time

import jax
import numpy as np
import pytest

from benchmark_suite_helpers import (DATA, REPO, TEST_PEAKS,  # noqa: F401
                                     hand_made, traced)

from benchmark import families, manifest, run, traffic
from benchmark.drivers import serve_family, serve_state
from benchmark.families import jamba as family
from benchmark.hostlog import HostLog

CELL = "jamba2-3b.docs-closed"


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real():
    """The configuration the benchmark runs."""
    return manifest.load_cell(CELL).config


@pytest.fixture(scope="module")
def tiny():
    return _read(DATA, "configs", "tiny-jamba.json")


def tiny_cell(tiny):
    """A cell of the tiny configuration, made by hand: a Cell is data."""
    mix = {"kind": "serve_state",
           "arrivals": {"process": "closed", "clients": 6, "pool": 60},
           "prompt_tokens": {"dist": "log_uniform", "lo": 4, "hi": 40},
           "output_tokens": {"dist": "log_uniform", "lo": 3, "hi": 6},
           "drain_seconds": 30, "check_requests": 3, "trace_seconds": 1,
           "schedule_seed": 3}
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tpot_p95_ms", "ms"), ("out_tokens_per_s", "tokens/s"),
        ("setup_s", "s"))]
    return manifest.Cell(
        name="tiny-jamba.tiny-closed", chips=1, config_name="tiny-jamba",
        config=tiny, mix_name="tiny-closed", mix=mix,
        params={"limits": {"logit_gap_mean": 1e-4, "logit_gap_p99": 1e-3,
                           "logit_gap_max": None, "state_gap_prefill": 1e-4,
                           "state_gap_decode": 1e-4}},
        end_to_end=e2e, per_layer=[], peaks=dict(TEST_PEAKS))


# -- the configuration --------------------------------------------------------


# the source's config.json (model_type jamba), key for key
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}


def test_the_file_holds_every_key_of_the_published_config(real):
    entry = next(c for c in manifest.load_manifest(REPO)["configs"]
                 if c["name"] == "jamba2-3b")
    assert entry["source"] == real["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    assert entry["reduced"] == real["reduced"] == []
    assert {k: real[k] for k in PUBLISHED} == PUBLISHED


def test_the_file_states_its_cut(real):
    """Nothing is cut: all 28 layers, every width, the whole vocabulary;
    every assumption is listed and the deployment is stated."""
    assert real["reduced"] == [] and real["published"] == {}
    assert (real["num_hidden_layers"], real["hidden_size"],
            real["vocab_size"]) == (28, 2560, 65536)
    assert (family.attention_layers(real), family.mamba_layers(real)) \
        == (2, 26)
    assert [i for i in range(28) if family.is_attention(real, i)] == [7, 21]
    assert {"position", "layer_order", "dense_mlp", "mixer_norms", "A_log",
            "D", "dt_bias", "weights", "dtype", "state", "unread_keys",
            "page_size"} <= set(real["assumed"])
    assert "one chip serves the whole model" in real["deployment"]
    assert "nothing is sharded" in real["deployment"]
    assert real["dtype"] == "bfloat16" and real["family"] == "jamba"
    assert real["engine"] == {"max_batch": 8, "max_seq_len": 17408,
                              "page_size": 256, "decode_burst": 16}


# -- the weight table ---------------------------------------------------------


def test_the_table_names_the_programs_parameters(tiny):
    from paddle_tpu.models import JambaForCausalLM

    model = JambaForCausalLM(family.model_config(tiny))
    want = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert list(family.leaf_specs(tiny)) == want
    assert family.param_count(tiny) == sum(
        int(np.prod(s)) for _, s in want)


def test_weights_follow_the_seed_and_mambas_initialisation(tiny):
    a = family.make_weights(tiny, 5, "float32")
    b = family.make_weights(tiny, 5, "float32")
    c = family.make_weights(tiny, 2**31 + 11, "float32")
    name = "model.layers.0.self_attn.in_proj.weight"
    np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
    assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))
    p = "model.layers.1.self_attn."
    np.testing.assert_allclose(np.asarray(a[p + "A_log"]),
                               np.log(np.tile(np.arange(1, 9), (128, 1))),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a[p + "D"]), np.ones(128))
    dt = np.log1p(np.exp(np.asarray(a[p + "dt_bias"], np.float64)))
    assert 0.001 * 0.999 < dt.min() and dt.max() < 0.1 * 1.001
    # log-uniform: about half of the channels below the geometric middle
    assert 0.3 < (dt < 0.01).mean() < 0.7
    gain = np.asarray(a["model.layers.0.input_layernorm.weight"])
    assert abs(gain.mean() - 1) < 0.05
    bias = np.asarray(a[p + "conv_bias"])
    assert abs(bias.mean()) < 0.02 and 0 < np.abs(bias).max() < 0.1
    assert family.make_weights(tiny, 5)[name].dtype == jax.numpy.bfloat16
    model = family.build_model(tiny, 9)
    w = family.make_weights(tiny, 9, tiny["dtype"])
    for n, prm in model.named_parameters():
        np.testing.assert_array_equal(np.asarray(prm._data),
                                      np.asarray(w[n]))


def test_the_engines_pools_are_the_tables(tiny, real):
    """`kv_pool_bytes()` of a built engine equals the table's
    `pool_bytes`, and the configuration the benchmark runs by hand."""
    model = family.build_model(tiny, 3)
    engine = family.build_engine(model, tiny["engine"])
    assert engine.kv_pool_bytes() == family.pool_bytes(
        tiny, tiny["engine"], itemsize=4)
    e = real["engine"]
    # 2 attention layers x K and V of one head of 128 in bf16, 8 slots of
    # 17,408 tokens; 26 Mamba layers x (16 x 5,120 float32 + 3 x 5,120
    # bf16) a slot
    assert family.cache_bytes_per_token(real) == 1024
    assert family.state_bytes_per_seq(real) \
        == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    assert family.pool_bytes(real, e) == {
        "kv_pool_bytes_full": 8 * 17408 * 1024,
        "kv_pool_bytes_window": 0, "kv_pool_bytes_state": 8 * 9_318_400}


# -- what the algorithm needs -------------------------------------------------


def test_sizes_of_the_configuration_the_benchmark_runs(real):
    hid, e, n, r, k, f = 2560, 5120, 16, 160, 4, 8192
    projections = hid * 2 * e + e * (r + 2 * n) + r * e + e * hid
    mixer = projections + k * e + e + e + e * n + e + r + 2 * n
    assert family.mamba_matrix_params(real) == projections
    assert round(mixer / 1e6, 2) == 41.24
    ffn = 3 * hid * f
    attn = hid * hid + 2 * hid * 128 + hid * hid
    assert family.attn_matrix_params(real) == attn
    assert round((mixer + ffn + 2 * hid) / 1e6, 2) == 104.16
    assert round((attn + ffn + 2 * hid) / 1e6, 2) == 76.68
    want = 26 * (mixer + ffn + 2 * hid) + 2 * (attn + ffn + 2 * hid) \
        + 65536 * hid + hid
    assert family.param_count(real) == want
    assert round(family.param_count(real) / 1e9, 2) == 3.03
    assert round(family.weight_bytes(real) / 1e9, 2) == 6.06
    per_token = 26 * projections + 2 * attn + 28 * ffn
    assert family.matmul_params_per_token(real, head=False) == per_token
    assert family.pair_flops(real) == 2 * 20 * 256
    m = 5000
    assert family.full_attn_flops(real, m) \
        == 10240 * 2 * (m * (m + 1) // 2)
    assert family.prefill_flops(real, m) == pytest.approx(
        2 * per_token * m + family.full_attn_flops(real, m)
        + 2 * 65536 * 2560)
    assert family.decode_flops(real, 6000) == pytest.approx(
        2 * (per_token + 65536 * 2560) + 10240 * 2 * 6000)
    # a step of 8 rows at a mean context of 9k: every weight once, K and V
    # of 72k tokens in 2 layers, 8 states read and written
    step = family.decode_bytes(real, 8 * 9000, 8)
    assert step == pytest.approx(2 * want + 1024 * 72000
                                 + 2 * 8 * 9_318_400)
    assert 6.2e9 < step < 6.4e9
    # a mean prompt of 8,860 tokens: some 51 TFLOP
    assert 48e12 < family.prefill_flops(real, 8860) < 54e12
    # the scan's least bytes: u', dt, y at 2 B and B, C at 4 B a token and
    # layer; the state at 4 B a prompt and layer
    assert family.ssm_scan_bytes(real, 1000, 2) == 26 * (
        1000 * (3 * 2 * 5120 + 2 * 4 * 16) + 2 * 16 * 5120 * 4)


def test_needs_dispatches_on_the_family_key(real):
    assert families.needs(real) is family


# -- the traffic --------------------------------------------------------------


def test_the_mix_letter_for_letter():
    cell = manifest.load_cell(CELL)
    mix = cell.mix
    assert mix["kind"] == "serve_state" and cell.mix_name == "docs-closed"
    assert mix["arrivals"] == {"process": "closed", "clients": 12,
                               "pool": 600}
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "lo": 4096,
                                    "hi": 16384}
    assert mix["output_tokens"] == {"dist": "log_uniform", "lo": 256,
                                    "hi": 1024}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"], mix["schedule_seed"]) == (60, 6, 10, 39)
    assert "shared_prefix" not in mix
    for conceded in ("8 slots is the warm-up's limit", "2.4 %"):
        assert conceded in mix["what"]
    requests = traffic.serve_requests(mix, 2**31 + 7, 51, 65536)
    assert len(requests) == 600
    assert {r.client for r in requests} == set(range(12))
    assert 4096 <= min(len(r.prompt) for r in requests) <= 4110
    assert 16300 <= max(len(r.prompt) for r in requests) <= 16384
    assert max(len(r.prompt) + r.max_new_tokens for r in requests) <= 17408
    assert max(int(r.prompt.max()) for r in requests) < 65536
    # every prompt is past the prefill kernel's first length, and the
    # scan's kernel takes the configuration's d_inner in blocks of 1,024
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import selective_scan as ss
    assert min(len(r.prompt) for r in requests) >= fa.GQA_MIN_SEQ
    assert ss.scan_block(family.d_inner(cell.config)) == 1024


def test_the_prompts_meet_three_prefill_programs_and_108_rounds():
    """68 pages a sequence is past the policy's `PAGE_BUCKETS_MAX`: a
    prompt pads to the next power of two of its pages and is prefilled a
    prompt a round; the warm-up makes 8 x 9 / 2 prefills a bucket."""
    from paddle_tpu.inference import scheduler

    from benchmark import program

    engine = type("E", (), {"max_batch": 8, "page_size": 256,
                            "pages_per_seq": 68, "max_seq_len": 17408,
                            "scheduler": scheduler.SchedulerPolicy()})()
    rounds = program.prefill_rounds(engine, 4096, 16384)
    assert {(nb, b) for (_, nb, b), _ in rounds} \
        == {(1, 4096), (1, 8192), (1, 16384)}
    assert sum(n for (n, _, _), _ in rounds) == 3 * 8 * 9 // 2 == 108


# -- the driver ---------------------------------------------------------------


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
    # a float32 program's states are the reference's to round-off
    assert checks["state_gap_prefill"]["value"] <= 1e-5
    assert checks["state_gap_decode"]["value"] <= 1e-5
    assert checks["logit_gap_mean"]["value"] <= checks["logit_gap_p99"][
        "value"] <= checks["logit_gap_max"]["value"]
    assert checks["picks_differ_share"]["value"] == 0.0
    assert checks["tokens_out_of_vocab"]["value"] == 0


def test_release_frees_the_pools_and_the_weights(tiny):
    system = serve_family.build(tiny_cell(tiny), 3)
    engine, model = system.engine, system.model
    assert len(engine.k_pages) == len(engine.v_pages) == 8
    serve_family.release(system)
    assert system.engine is None and engine.k_pages is None
    assert all(p._data is None for p in model.parameters())


def test_the_replay_reads_the_states_the_slot_holds(tiny):
    """The longest checked request served again alone: after its first
    token the slot holds its prefill's states, after its whole answer the
    states of the prompt and every token but the last; both are the
    reference's, and the tokens are the window's."""
    from benchmark.reference import jamba as reference

    system = serve_state.build(tiny_cell(tiny), 11)
    prompt = np.random.default_rng(4).integers(0, tiny["vocab_size"], 29)
    tokens, after = serve_state.served_states(system.engine, family, tiny,
                                              prompt, 5)
    _, first = serve_state.served_states(system.engine, family, tiny,
                                         prompt, 1)
    assert len(tokens) == 5 and sorted(after) == [0, 1, 3, 4, 5, 7]
    assert after[0].shape == (8, 128) and after[0].dtype == np.float32
    w = family.make_weights(tiny, 11, "float32")
    ids = np.concatenate([prompt, tokens[:-1]])
    want = reference.states_at(w, tiny, ids, [29, 33])
    for i in after:
        np.testing.assert_allclose(first[i], want[i][0], atol=1e-5)
        np.testing.assert_allclose(after[i], want[i][1], atol=1e-5)
    pre, dec, layers = serve_state.state_gaps(tiny, w, prompt, tokens,
                                              first, after, 40)
    assert pre <= 1e-5 and dec <= 1e-5 and sorted(layers) == [0, 1, 3, 4, 5, 7]
    assert (pre, dec) == layers[0]


def test_a_state_kept_in_bf16_reads_a_wider_gap(tiny):
    """States rounded to bf16 once read about bf16's rounding in every
    channel, where the reference's own float32 states read float32's."""
    w = family.make_weights(tiny, 11, "float32")
    prompt = np.random.default_rng(4).integers(0, tiny["vocab_size"], 29)
    tokens = [1, 2, 3]
    from benchmark.reference import jamba as reference

    ids = np.concatenate([prompt, tokens[:-1]])
    ref = reference.states_at(w, tiny, ids, [29, 31])
    first = {i: v[0] for i, v in ref.items()}
    last = {i: np.asarray(v[1], jax.numpy.bfloat16).astype(np.float32)
            for i, v in ref.items()}
    pre, dec, _ = serve_state.state_gaps(tiny, w, prompt, tokens, first,
                                         last, 40)
    assert pre < 1e-6
    assert 2 ** -12 < dec < 2 ** -8


class _Rec:
    def __init__(self, prompt, tokens):
        self.request = type("R", (), {"prompt": prompt,
                                      "max_new_tokens": len(tokens)})
        self.tokens, self.done = list(tokens), True


def test_the_control_in_lower_precision_moves_the_logits(tiny):
    """The comparison that decides `correct`, at the tiny size: tokens the
    float32 reference itself puts first read 0; the fp8 control's logits
    lie off the float32 ones (at this size its first token does not
    change: the best logit stands far above the next); a token altered
    where it is produced reads a gap."""
    from benchmark.reference import jamba as reference

    w = family.make_weights(tiny, 7, "float32")
    rng = np.random.default_rng(0)
    sample = []
    for n in (7, 30):
        ids = list(rng.integers(0, tiny["vocab_size"], n))
        for _ in range(5):
            logits = reference.logits_at(w, tiny, ids, [len(ids) - 1])
            ids.append(int(np.asarray(logits).argmax()))
        sample.append(_Rec(np.asarray(ids[:n]), ids[n:]))
    gaps, flipped = serve_family.logit_gaps(tiny, w, sample, 40, 8)
    assert gaps.max() == 0.0 and len(gaps) == 10 and flipped == 0.0
    ids = np.concatenate([sample[1].request.prompt, sample[1].tokens])
    at = np.arange(len(ids))
    f32 = np.asarray(reference.logits_at(w, tiny, ids, at))
    fp8 = np.asarray(reference.logits_at(w, tiny, ids, at, "fp8"))
    assert np.abs(fp8 - f32).max() > 1e-3
    sample[0].tokens[2] = (sample[0].tokens[2] + 1) % tiny["vocab_size"]
    altered, _ = serve_family.logit_gaps(tiny, w, sample, 40, 8)
    assert altered.max() > 1e-2


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "benchmark", "reference", "jamba.py")) as f:
        source = f.read()
    assert "paddle_tpu" not in source.split('"""', 2)[2]


def test_the_reference_scan_is_the_recurrence_step_by_step(tiny):
    """The reference's one sequential scan against numpy's loop, a token a
    step in float64, and the states it keeps after 5 and 23 tokens."""
    from benchmark.reference import jamba as reference

    rng = np.random.default_rng(2)
    s, e, n = 23, 6, 4
    u, dt = rng.standard_normal((s, e)), 0.1 * rng.random((s, e))
    a = -np.tile(np.arange(1.0, n + 1), (e, 1))
    b, c = rng.standard_normal((s, n)), rng.standard_normal((s, n))
    got = np.asarray(reference.scan(*(jax.numpy.asarray(x, np.float32)
                                      for x in (u, dt, a, b, c))))
    _, kept = reference.scan(*(jax.numpy.asarray(x, np.float32)
                               for x in (u, dt, a, b, c)),
                             ends=jax.numpy.asarray([5, 23]))
    h = np.zeros((e, n))
    for t in range(s):
        h = np.exp(dt[t][:, None] * a) * h + (dt[t] * u[t])[:, None] * b[t]
        np.testing.assert_allclose(got[t], h @ c[t], atol=1e-5)
        if t + 1 in (5, 23):
            np.testing.assert_allclose(kept[(5, 23).index(t + 1)], h,
                                       atol=1e-5)


# -- the readers --------------------------------------------------------------


def test_the_scan_against_its_roofline_by_bytes(tiny, traced):
    """Prompts of 12 and 40 tokens, 6 ms under `attn/ssm` in the one
    prefill program of the hand-made trace: the least bytes of 6 Mamba
    layers' scans at the test's HBM peak."""
    cell = tiny_cell(tiny)
    log = HostLog()
    log.samples = {"prefill": [(0.0, 12), (0.0, 40)]}
    want = 6 * (52 * (3 * 2 * 128 + 2 * 4 * 8) + 2 * 4 * 8 * 128)
    assert family.ssm_scan_bytes(tiny, 52, 2) == want
    read = manifest.load_reader("ssm_scan_prefill_roofline")
    raw, _ = hand_made(cell)
    assert read(traced(raw), log, cell) == pytest.approx(
        100 * want / TEST_PEAKS["hbm_bytes_per_s"] / 0.006)
    assert read(None, log, cell) is None
    assert read(traced(hand_made(cell)[0]), HostLog(), cell) is None


def test_the_decode_steps_state_update(tiny, traced):
    """5 ms under `attn/ssm` in a burst of 4 steps: 1.25 ms a step; a
    program without the scope (another family's) reads nothing."""
    cell = tiny_cell(tiny)
    read = manifest.load_reader("decode_sub_ms.ssm")
    assert read(traced(hand_made(cell)[0]), HostLog(), cell) \
        == pytest.approx(5.0 / 4)
    other = tiny_cell(_read(DATA, "configs", "tiny-mimo-v2.json"))
    raw, _ = hand_made(other)
    assert read(traced(raw), HostLog(), other) is None


def test_the_decode_attention_roofline_needs_a_window_path(tiny, traced):
    """`cache_attn_decode_roofline` reads `attn/window` AND `attn/full` in
    the burst and answers None where either is missing: a program of full
    layers alone, this one, has nothing it reads, so the cell does not
    list it (PERF.md section 7)."""
    cell = tiny_cell(tiny)
    read = manifest.load_reader("cache_attn_decode_roofline")
    assert read(traced(hand_made(cell)[0]), HostLog(), cell) is None


def test_the_cell_reports_what_it_lists():
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert {"tpot_p95_ms", "out_tokens_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    names = {e["name"] for e in cell.per_layer}
    assert {"decode_sub_ms.ssm", "ssm_scan_prefill_roofline",
            "decode_sub_ms.full_attn", "full_prefill_attn_roofline",
            "mfu.serve", "decode_roofline", "mfu.prefill",
            "prefill_roofline", "kv_pages_used_pct", "decode_step_ms",
            "decode_ms.attn", "decode_ms.mlp", "decode_ms.head",
            "decode_ms.other", "device_idle_pct.serve", "builds_in_trace",
            "gen_lag_p95_ms", "batch_occupancy_pct", "idle_pct.prefill",
            "idle_pct.kv_scatter", "idle_pct.decode_launch", "idle_pct.emit",
            "idle_pct.outside", "compiles_in_window",
            "queue_wait_p50_ms"} <= names
    # nothing of the expert or window layers: this program has neither
    assert not any(n.startswith(("experts_", "expert_", "window_"))
                   or n in ("decode_sub_ms.experts", "decode_sub_ms.router",
                            "decode_sub_ms.window_attn",
                            "cache_attn_decode_roofline")
                   for n in names)
    limits = cell.params["limits"]
    assert set(limits) == {"logit_gap_mean", "logit_gap_p99", "logit_gap_max",
                           "state_gap_prefill", "state_gap_decode"}
    assert 0 < limits["state_gap_prefill"] < 1
    assert 0 < limits["state_gap_decode"] < 1
    assert limits["logit_gap_max"] is None
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_p99"] < 1
    assert math.isfinite(limits["logit_gap_p99"])
