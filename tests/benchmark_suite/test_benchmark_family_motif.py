"""The `motif` family's part of the benchmark (Motif-3-Beta): its weight
table against the program's parameters, its operations and bytes against
hand-worked numbers for the configuration BENCHMARK.json runs, the
published widths in the configuration's file and the bytes it states, the
engine's pools against the table, the traffic mix, the `serve_family`
driver end to end at a tiny size, the control, and the four new readers on
a hand-made trace written with xplane_writer.py."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark_suite_helpers import (DATA, REPO, TEST_PEAKS,  # noqa: F401
                                     hand_made, traced)

from benchmark import families, manifest, run, traffic
from benchmark.drivers import serve_family
from benchmark.families import motif as family
from benchmark.hostlog import HostLog

CELL = "motif-3-beta-ep8-l7.reason-closed"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real():
    """The configuration the benchmark runs."""
    return manifest.load_cell(CELL).config


@pytest.fixture(scope="module")
def tiny():
    return _read(DATA, "configs", "tiny-motif.json")


def tiny_cell(tiny):
    """A cell of the tiny configuration, made by hand: a Cell is data."""
    mix = {"kind": "serve_family",
           "arrivals": {"process": "closed", "clients": 6, "pool": 60},
           "prompt_tokens": {"dist": "log_uniform", "lo": 4, "hi": 30},
           "output_tokens": {"dist": "log_uniform", "lo": 3, "hi": 6},
           "drain_seconds": 30, "check_requests": 3, "trace_seconds": 1,
           "schedule_seed": 3}
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tpot_p95_ms", "ms"), ("out_tokens_per_s", "tokens/s"),
        ("setup_s", "s"))]
    return manifest.Cell(
        name="tiny-motif.tiny-closed", chips=1, config_name="tiny-motif",
        config=tiny, mix_name="tiny-closed", mix=mix,
        params={"limits": {"logit_gap_mean": 1e-4, "logit_gap_p99": 1e-3,
                           "logit_gap_max": None}},
        end_to_end=e2e, per_layer=[], peaks=dict(TEST_PEAKS))


# -- the configuration --------------------------------------------------------


def test_the_file_holds_the_published_widths(real):
    """Every width of Motif-3-Beta's published config.json as it stands
    there; what the cut changed is `reduced`, with the published values
    beside it."""
    entry = next(c for c in manifest.load_manifest(REPO)["configs"]
                 if c["name"] == "motif-3-beta-ep8-l7")
    assert entry["source"] == real["source"] == (
        "https://huggingface.co/Motif-Technologies/Motif-3-Beta/blob/main/"
        "config.json")
    assert entry["reduced"] == real["reduced"] == REDUCED
    assert real["published"] == {"num_hidden_layers": 53, "num_experts": 384,
                                 "vocab_size": 220160,
                                 "num_nextn_predict_layers": 1}
    published = dict(
        hidden_size=4096, intermediate_size=12288, moe_intermediate_size=1280,
        head_dim=192, qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=1024,
        kv_lora_rank=512, num_attention_heads=80, num_key_value_heads=16,
        num_noise_heads=16, experts_top_k=8, num_shared_experts=1,
        sliding_window=128, sliding_window_period=4, n_dense_first_layers=2,
        mhc_expansion_rate=4, mhc_sinkhorn_iters=20, route_scale=2,
        polynorm_output_scale=0.5, polynorm_bias_clamp=0.5,
        hidden_clamp=1000000, rms_norm_eps=1e-05, rope_theta=10000,
        swa_rope_theta=10000, max_position_embeddings=262144,
        tie_word_embeddings=False, model_type="Motif", attention_cls="gdla",
        hidden_act="poly_norm", diff_v2=True,
        elementwise_attn_output_gate=True)
    for key, value in published.items():
        assert real[key] == value, key
    assert real["rope_scaling"] == {
        "original_max_position_embeddings": 4096, "factor": 64, "mscale": 1,
        "rope_type": "yarn", "rope_theta": 10000, "beta_fast": 32,
        "beta_slow": 1, "apply_yarn_scaling": False}


def test_the_file_states_its_cut_and_its_bytes(real):
    assert (real["num_hidden_layers"], real["num_experts"],
            real["router_experts"], real["vocab_size"],
            real["num_nextn_predict_layers"]) == (7, 48, 384, 27520, 0)
    assert real["vocab_size"] * 8 == real["published"]["vocab_size"]
    assert (real["ep_rank"], real["ep_degree"], real["first_layer"]) \
        == (0, 8, 1)
    assert [family.kind_of(real, i) for i in range(7)] == [
        "window", "window", "full", "window", "window", "window", "full"]
    assert (family.dense_layers(real), family.expert_layers(real)) == (1, 6)
    assert {"router_experts", "first_layer", "window_layers", "diff_v2",
            "head_split", "output_gate", "mhc", "streams", "polynorm",
            "rope", "unread", "mtp", "weights", "dtype", "page_size"} \
        <= set(real["assumed"])
    assert "8 chips share each layer" in real["deployment"]
    assert "other 46 layers lie on further pipeline stages" \
        in real["deployment"]
    assert real["dtype"] == "bfloat16" and real["family"] == "motif"
    assert real["engine"] == {"max_batch": 8, "max_seq_len": 6144,
                              "page_size": 256, "decode_burst": 16}
    # the bytes the file states are the built model's
    assert real["weight_bytes"] == family.weight_bytes(real)
    for kind in family.KINDS:
        assert real["kv_pool_bytes"][kind] == family.pool_bytes(
            real, real["engine"], kind)


# -- the weight table ---------------------------------------------------------


def test_the_table_names_the_programs_parameters(tiny):
    from paddle_tpu.models import MotifForCausalLM

    model = MotifForCausalLM(family.model_config(tiny))
    want = {n: tuple(p.shape) for n, p in model.named_parameters()}
    got = {n: tuple(s) for n, s, _, _ in family.leaf_specs(tiny)}
    assert got == want
    assert list(got) == list(want)
    assert family.param_count(tiny) == sum(
        int(np.prod(s)) for s in want.values())


def test_weights_follow_the_seed_and_draw_each_kind_of_leaf(tiny):
    a = family.make_weights(tiny, 5, "float32")
    b = family.make_weights(tiny, 5, "float32")
    c = family.make_weights(tiny, 2**31 + 11, "float32")
    name = "model.layers.3.self_attn.q_b_proj.weight"
    np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
    assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))
    poly = np.asarray(a["model.layers.2.mlp.experts.act_weight"])
    assert poly.shape == (4, 3) and abs(poly.mean() - 1 / 3) < 0.1
    alpha = np.asarray(a["model.layers.2.attn_hc.alpha"])
    assert alpha.shape == (3,) and abs(alpha.mean() - 0.3) < 0.1
    gain = np.asarray(a["model.layers.0.input_layernorm.weight"])
    assert gain.shape == (48,) and abs(gain.mean() - 1) < 0.05
    assert family.make_weights(tiny, 5)[name].dtype == jax.numpy.bfloat16
    model = family.build_model(tiny, 9)
    w = family.make_weights(tiny, 9, tiny["dtype"])
    for n, p in model.named_parameters():
        np.testing.assert_array_equal(np.asarray(p._data), np.asarray(w[n]))


def test_the_engines_pools_are_the_tables(tiny, real):
    model = family.build_model(tiny, 3)
    engine = family.build_engine(model, tiny["engine"])
    got = engine.kv_pool_bytes()
    for kind in family.KINDS:
        assert got["kv_pool_bytes_" + kind] == family.pool_bytes(
            tiny, tiny["engine"], kind, itemsize=4) > 0
    # the configuration the benchmark runs, by hand: 2 full layers of 8
    # slots x 24 pages, 5 window layers of 8 rings x 2 pages, pages of 256
    # latent rows of 576 numbers in bf16
    e = real["engine"]
    assert family.pool_bytes(real, e, "full") \
        == 2 * 8 * 24 * 256 * 576 * 2 == 113_246_208
    assert family.pool_bytes(real, e, "window") \
        == 5 * 8 * 2 * 256 * 576 * 2 == 23_592_960


# -- what the algorithm needs -------------------------------------------------


def test_sizes_of_the_configuration_the_benchmark_runs(real):
    attn = 4096 * 1024 + 1024 * 80 * 192 + 4096 * 576 + 512 * 16 * 256 \
        + 64 * 128 * 4096 + 4096 * 64 + 4096 * 64 * 128
    assert family.attn_matrix_params(real) == attn == 91_750_400
    assert family.hc_matrix_params(real) == 16384 * 24 == 393_216
    assert family.expert_params(real) == 3 * 4096 * 1280 == 15_728_640
    hc = 2 * (16384 * 24 + 3 + 24)
    attn_gains = 1024 + 512
    dense_layer = attn + attn_gains + 3 * 4096 * 12288 + 4 + hc + 2 * 4096
    expert_layer = attn + attn_gains + 48 * (15_728_640 + 4) \
        + 4096 * 384 + 15_728_640 + 4 + hc + 2 * 4096
    assert round(dense_layer / 1e6, 1) == 243.5
    assert round(expert_layer / 1e6, 1) == 864.8
    want = dense_layer + 6 * expert_layer + 2 * 27520 * 4096 + 4096
    assert family.param_count(real) == want == 5_657_927_702
    assert round(family.weight_bytes(real) / 1e9, 2) == 11.32
    assert family.cache_bytes_per_token(real) == 1152
    assert family.page_bytes(real, 256) == 294_912
    assert family.pairs_per_token(real) == 1.0
    assert family.pair_flops(real) == 2 * 80 * (192 + 128)
    n = 2000
    per_token = 7 * (attn + 2 * 393_216) + 3 * 4096 * 12288 \
        + 6 * (2 * 15_728_640 + 4096 * 384)
    assert family.matmul_params_per_token(real, head=False) == per_token
    assert family.full_attn_flops(real, n) == 51200 * 2 * (n * (n + 1) // 2)
    assert family.window_attn_flops(real, n) \
        == 51200 * 5 * family.window_pairs(n, 128)
    head = 27520 * 4096
    assert family.prefill_flops(real, n) == pytest.approx(
        2 * per_token * n + family.full_attn_flops(real, n)
        + family.window_attn_flops(real, n) + 2 * head)
    assert family.decode_flops(real, 3000) == pytest.approx(
        2 * (per_token + head) + 51200 * (2 * 3000 + 5 * 128))
    hit = 48 * (1 - (1 - 8 / 384) ** 8)
    assert family.experts_hit(real, 8) == pytest.approx(hit)
    outside = family.param_count(real) - 6 * 48 * 15_728_640 \
        - (27520 - 8) * 4096
    assert family.decode_bytes(real, 8 * 3000, 8) == pytest.approx(
        2 * (outside + 6 * hit * 15_728_640)
        + 1152 * (2 * 8 * 3000 + 5 * 8 * 128))
    # a prefill token's mixes: 14 sublayers x (3 x 4 x 4,096 + 2 x 4,096)
    assert family.hyper_bytes(real, 1) == 2 * 14 * (3 * 16384 + 8192)
    assert family.expert_pair_flops(real) == 2 * 15_728_640
    assert family.expert_read_bytes(real, 3) == 2 * 3 * (15_728_640 + 4)


def test_needs_dispatches_on_the_family_key(real):
    assert families.needs(real) is family


# -- the traffic --------------------------------------------------------------


def test_the_mix_is_the_cells_traffic():
    cell = manifest.load_cell(CELL)
    mix = cell.mix
    assert mix["kind"] == "serve_family" and cell.mix_name == "reason-closed"
    assert mix["arrivals"] == {"process": "closed", "clients": 12,
                               "pool": 600}
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "lo": 512,
                                    "hi": 2048}
    assert mix["output_tokens"] == {"dist": "log_uniform", "lo": 512,
                                    "hi": 4096}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"]) == (60, 6, 10)
    assert mix["schedule_seed"] not in {
        manifest.load_cell(w["name"]).mix.get("schedule_seed")
        for w in manifest.load_manifest(REPO)["workloads"]
        if w["name"] != CELL}
    assert "shared_prefix" not in mix
    for conceded in ("warm-up's limit", "0.17 pairs a step", "1.33",
                     "7 of 53 layers"):
        assert conceded in mix["what"]
    requests = traffic.serve_requests(mix, 2**31 + 7, 51, 27520)
    assert len(requests) == 600
    assert {r.client for r in requests} == set(range(12))
    assert 512 <= min(len(r.prompt) for r in requests) <= 520
    assert 2000 <= max(len(r.prompt) for r in requests) <= 2048
    assert 512 <= min(r.max_new_tokens for r in requests) <= 530
    assert 4000 <= max(r.max_new_tokens for r in requests) <= 4096
    assert max(len(r.prompt) + r.max_new_tokens for r in requests) <= 6144
    assert max(int(r.prompt.max()) for r in requests) < 27520
    again = traffic.serve_requests(mix, 5, 51, 27520)
    assert [len(r.prompt) for r in again] == [len(r.prompt)
                                              for r in requests]
    assert not np.array_equal(again[0].prompt, requests[0].prompt)


def test_the_prompts_meet_three_prefill_programs():
    """24 pages a sequence is past the policy's `PAGE_BUCKETS_MAX`: a
    prompt pads to the next power of two of its pages and is prefilled a
    prompt a round."""
    from paddle_tpu.inference import scheduler

    engine = type("E", (), {"max_batch": 8, "page_size": 256,
                            "pages_per_seq": 24, "max_seq_len": 6144})()
    policy = scheduler.SchedulerPolicy()
    seen = set()
    for n in (512, 513, 1024, 1500, 2048):
        seen.add(policy.prefill_bucket(engine, [(0, range(n))]))
    assert seen == {(1, 512), (1, 1024), (1, 2048)}


# -- the driver ---------------------------------------------------------------


def test_the_driver_end_to_end_on_the_cpu(tiny):
    cell = tiny_cell(tiny)
    assert manifest.load_driver(manifest.load_cell(CELL).mix["kind"]) \
        is serve_family
    result = run.measure(cell, 2**31 + 5, 1.5, 0, jax.devices(),
                         t_start=time.perf_counter())
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 6
    assert set(result["metrics"]) == {"tpot_p95_ms", "out_tokens_per_s",
                                      "setup_s"}
    checks = result["checks"]
    assert checks["logit_gap_max"]["value"] <= 1e-3
    assert 0 <= checks["picks_differ_share"]["value"] <= 1
    assert checks["tokens_out_of_vocab"]["value"] == 0


class _Rec:
    def __init__(self, prompt, tokens):
        self.request = type("R", (), {"prompt": prompt,
                                      "max_new_tokens": len(tokens)})
        self.tokens, self.done = list(tokens), True


def test_the_control_in_lower_precision_reads_wider_than_the_program(tiny):
    """The comparison that decides `correct`, at the tiny size: tokens the
    float32 reference itself puts first read 0; the fp8 control reads
    wider; a token altered where it is produced reads wider still."""
    from benchmark.reference import motif as reference

    w = family.make_weights(tiny, 7, "float32")
    rng = np.random.default_rng(0)
    sample = []
    # the control flips about one token in ten at this size: 30 of them;
    # the reference is causal, so one padded length serves every call
    for n in (7, 25, 16, 11, 20, 9):
        ids = list(rng.integers(0, tiny["vocab_size"], n))
        for _ in range(5):
            logits = reference.logits_at(w, tiny, np.pad(ids, (0, 35 - len(
                ids))), [len(ids) - 1])
            ids.append(int(np.asarray(logits).argmax()))
        sample.append(_Rec(np.asarray(ids[:n]), ids[n:]))
    gaps, flipped = serve_family.logit_gaps(tiny, w, sample, 35, 8)
    assert gaps.max() == 0.0 and len(gaps) == 30 and 0 <= flipped <= 1
    control, _ = serve_family.logit_gaps(tiny, w, sample, 35, 8, "fp8")
    assert control.max() > 1e-3 and control.mean() > 1e-4
    sample[0].tokens[2] = (sample[0].tokens[2] + 1) % tiny["vocab_size"]
    altered, _ = serve_family.logit_gaps(tiny, w, sample, 35, 8)
    assert altered.max() > control.max()


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "benchmark", "reference",
                           "motif.py")) as f:
        source = f.read()
    assert "paddle_tpu" not in source.split('"""', 2)[2]


def test_the_reference_in_blocks_is_the_reference_whole(tiny, monkeypatch):
    """Blocks of queries and of tokens change no number beyond round-off."""
    from benchmark.reference import motif as reference

    w = family.make_weights(tiny, 6, "float32")
    ids = np.random.default_rng(1).integers(0, tiny["vocab_size"], 50)
    whole = np.asarray(reference.logits_at(w, tiny, ids, np.arange(50)))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    reference._programs.cache_clear()
    try:
        blocked = np.asarray(reference.logits_at(w, tiny, ids,
                                                 np.arange(50)))
    finally:
        reference._programs.cache_clear()
    np.testing.assert_allclose(blocked, whole, atol=2e-6)


# -- the readers --------------------------------------------------------------


def _trace_of(traced, real):
    cell = manifest.load_cell(CELL)
    cell.peaks = dict(TEST_PEAKS)
    raw, host = hand_made(cell)
    return cell, traced(raw), host


def _bare(raw):
    """The same trace of a program with one residual stream and no counts
    (the parent's, or another family's)."""
    for ev in raw["planes"][0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("/mhc", "").replace("/experts", "")
    for ev in raw["planes"][1]["lines"][0]["events"]:
        if ev[0] == "serving.emit":
            ev[3] = {}
    return raw


def test_the_mixes_decode_time_reads_both_scopes(traced, real):
    """1 ms under `attn/mhc` and 1 under `mlp/mhc` in one burst of 16
    steps."""
    cell, trace, host = _trace_of(traced, real)
    read = manifest.load_reader("decode_sub_ms.hyper_residual")
    assert read(trace, host, cell) == pytest.approx(2.0 / 16)
    assert read(None, host, cell) is None
    raw, _ = hand_made(cell)
    assert read(traced(_bare(raw)), host, cell) is None


def test_the_mixes_prefill_roofline_by_bytes(traced, real):
    """Prompts of 20 and 30 tokens (the hand-made host's), 2 ms under the
    two `mhc` scopes of the one prefill program."""
    cell, trace, host = _trace_of(traced, real)
    read = manifest.load_reader("hyper_residual_prefill_roofline")
    least = family.hyper_bytes(real, 50) / TEST_PEAKS["hbm_bytes_per_s"]
    assert read(trace, host, cell) == pytest.approx(100 * least / 0.002)
    assert read(trace, HostLog(), cell) is None
    raw, _ = hand_made(cell)
    assert read(traced(_bare(raw)), host, cell) is None


def test_the_hit_kernels_roofline_by_the_experts_read(traced, real):
    """24 experts read a burst (the two bursts' emits), 1 ms under
    `mlp/experts` in the one burst."""
    cell, trace, host = _trace_of(traced, real)
    read = manifest.load_reader("hit_poly_roofline")
    least = 2 * 24 * (15_728_640 + 4) / TEST_PEAKS["hbm_bytes_per_s"]
    assert read(trace, host, cell) == pytest.approx(100 * least / 0.001)
    raw, _ = hand_made(cell)
    assert read(traced(_bare(raw)), host, cell) is None


def test_the_grouped_kernels_roofline_by_its_pairs_and_weights(traced,
                                                               real):
    """20 pairs in the one prefill, 4 ms under `mlp/experts`: at these
    counts the read of every held expert of the 6 layers bounds it."""
    cell, trace, host = _trace_of(traced, real)
    read = manifest.load_reader("grouped_poly_roofline")
    ops = 20 * 2 * 15_728_640 / TEST_PEAKS["bf16_flops_per_s"]
    nbytes = 2 * 6 * 48 * (15_728_640 + 4) / TEST_PEAKS["hbm_bytes_per_s"]
    assert nbytes > ops
    assert read(trace, host, cell) == pytest.approx(100 * nbytes / 0.004)
    raw, _ = hand_made(cell)
    assert read(traced(_bare(raw)), host, cell) is None


def test_the_cell_reports_what_it_lists():
    m = manifest.load_manifest(REPO)
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert {"tpot_p95_ms", "out_tokens_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    names = {e["name"] for e in cell.per_layer}
    assert {"decode_sub_ms.hyper_residual",
            "hyper_residual_prefill_roofline", "hit_poly_roofline",
            "grouped_poly_roofline", "decode_sub_ms.latent_attn",
            "decode_sub_ms.window_attn", "window_pages_live_pct",
            "window_pages_read_pct", "window_prefill_attn_roofline",
            "mfu.serve", "decode_roofline", "mfu.prefill",
            "prefill_roofline", "decode_sub_ms.experts",
            "decode_sub_ms.router", "decode_sub_ms.shared_expert",
            "expert_pairs_per_step", "experts_hit_pct", "experts_read_pct",
            "prefill_expert_rows_per_pair", "kv_pages_used_pct"} <= names
    # what this model lacks: no `attn/full`
    assert not names & {"cache_attn_decode_roofline",
                        "full_prefill_attn_roofline",
                        "decode_sub_ms.full_attn"}
    for e in m["per_layer"]:
        if CELL in e.get("workloads", []):
            assert e["moves"] in ("tpot_p95_ms", "out_tokens_per_s")
    limits = cell.params["limits"]
    assert set(limits) == {"logit_gap_mean", "logit_gap_p99", "logit_gap_max"}
    assert limits["logit_gap_max"] is None
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_p99"] < 1
