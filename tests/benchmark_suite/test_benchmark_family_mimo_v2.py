"""The `mimo_v2` family's part of the benchmark (MiMo-V2.5): its weight
table against the program's parameters, its operations and bytes against
hand-worked numbers for the configuration BENCHMARK.json runs, the catalog
row's keys in the configuration's file, the engine's pools against the
table, the traffic mix, the `serve_family` driver end to end at a tiny
size, the control, and both new readers on a hand-made trace written with
xplane_writer.py."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark_suite_helpers import (DATA, REPO, TEST_PEAKS,  # noqa: F401
                                     traced, without_scopes_and_counts)
from benchmark_suite_helpers import mimo_raw as _raw

from benchmark import families, manifest, run, traffic
from benchmark.drivers import serve_family
from benchmark.families import mimo_v2 as family
from benchmark.hostlog import HostLog

MS = 1_000_000  # ns
CELL = "mimo-v2.5-ep16-l11.long-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real():
    """The configuration the benchmark runs."""
    return manifest.load_cell(CELL).config


@pytest.fixture(scope="module")
def tiny():
    return _read(DATA, "configs", "tiny-mimo-v2.json")


def tiny_cell(tiny):
    """A cell of the tiny configuration, made by hand: a Cell is data."""
    mix = {"kind": "serve_family",
           "arrivals": {"process": "closed", "clients": 6, "pool": 60},
           "prompt_tokens": {"dist": "log_uniform", "lo": 4, "hi": 40},
           "output_tokens": {"dist": "log_uniform", "lo": 3, "hi": 6},
           "drain_seconds": 30, "check_requests": 3, "trace_seconds": 1,
           "schedule_seed": 3}
    e2e = [{"name": n, "unit": u} for n, u in (
        ("tpot_p95_ms", "ms"), ("out_tokens_per_s", "tokens/s"),
        ("setup_s", "s"))]
    return manifest.Cell(
        name="tiny-mimo-v2.tiny-closed", chips=1, config_name="tiny-mimo-v2",
        config=tiny, mix_name="tiny-closed", mix=mix,
        params={"limits": {"logit_gap_mean": 1e-4, "logit_gap_p99": 1e-3,
                           "logit_gap_max": None}},
        end_to_end=e2e, per_layer=[], peaks=dict(TEST_PEAKS))


# -- the configuration --------------------------------------------------------


def test_the_file_holds_every_key_of_the_catalog_row(real):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    entry = next(c for c in manifest.load_manifest(REPO)["configs"]
                 if c["name"] == "mimo-v2.5-ep16-l11")
    assert entry["source"] == row["source_url"] == real["source"]
    assert entry["reduced"] == real["reduced"] == REDUCED
    differ = {k for k, v in row["config"].items() if real.get(k) != v}
    assert differ == set(REDUCED)
    assert real["published"] == {k: row["config"][k] for k in REDUCED}
    # the two lists are the published ones cut to the depth
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert real[key] == row["config"][key][:11]
    # every width is as published
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
                "num_attention_heads", "num_key_value_heads",
                "swa_num_key_value_heads", "num_experts_per_tok",
                "sliding_window", "partial_rotary_factor"):
        assert real[key] == row["config"][key], key


def test_the_file_states_its_cut(real):
    assert (real["num_hidden_layers"], real["n_routed_experts"],
            real["router_experts"], real["vocab_size"]) \
        == (11, 16, 256, 19072)
    assert real["vocab_size"] * 8 == real["published"]["vocab_size"]
    assert (real["ep_rank"], real["ep_degree"]) == (0, 16)
    assert real["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1]
    assert real["moe_layer_freq"] == [0] + [1] * 10
    assert (family.full_layers(real), family.window_layers(real),
            family.expert_layers(real)) == (2, 9, 10)
    assert {"router_experts", "towers_and_mtp", "sink", "value_scale",
            "qk_norm", "rope", "norms", "attention_chunk_size", "experts",
            "weights", "dtype", "page_size", "key_pool_width"} \
        <= set(real["assumed"])
    assert "16 chips share each layer" in real["deployment"]
    assert real["dtype"] == "bfloat16" and real["family"] == "mimo_v2"
    assert real["engine"] == {"max_batch": 8, "max_seq_len": 17408,
                              "page_size": 256, "decode_burst": 16}


# -- the weight table ---------------------------------------------------------


def test_the_table_names_the_programs_parameters(tiny):
    from paddle_tpu.models import MiMoV2ForCausalLM

    model = MiMoV2ForCausalLM(family.model_config(tiny))
    want = {n: tuple(p.shape) for n, p in model.named_parameters()}
    got = {n: tuple(s) for n, s, _, _ in family.leaf_specs(tiny)}
    assert got == want
    assert family.param_count(tiny) == sum(
        int(np.prod(s)) for s in want.values())


def test_weights_follow_the_seed_and_sink_and_bias_are_small(tiny):
    a = family.make_weights(tiny, 5, "float32")
    b = family.make_weights(tiny, 5, "float32")
    c = family.make_weights(tiny, 2**31 + 11, "float32")
    name = "model.layers.3.self_attn.q_proj.weight"
    np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
    assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))
    bias = np.asarray(a["model.layers.4.mlp.experts.gate.expert_bias"])
    assert bias.shape == (16,) and 0 < np.abs(bias).max() < 0.06
    sink = np.asarray(a["model.layers.4.self_attn.attention_sink_bias"])
    assert sink.shape == (4,) and 0 < np.abs(sink).max() < 0.6
    assert "model.layers.5.self_attn.attention_sink_bias" not in a
    gain = np.asarray(a["model.layers.0.input_layernorm.weight"])
    assert gain.shape == (48,) and abs(gain.mean() - 1) < 0.05
    assert family.make_weights(tiny, 5)[name].dtype == jax.numpy.bfloat16
    model = family.build_model(tiny, 9)
    w = family.make_weights(tiny, 9, tiny["dtype"])
    for n, p in model.named_parameters():
        np.testing.assert_array_equal(np.asarray(p._data), np.asarray(w[n]))


def test_the_engines_pools_are_the_tables(tiny, real):
    """`kv_pool_bytes_*` of a built engine equal the table's `pool_bytes`:
    per kind, at that kind's kv heads, a key stored `pool_width` wide."""
    model = family.build_model(tiny, 3)
    engine = family.build_engine(model, tiny["engine"])
    got = engine.kv_pool_bytes()
    for kind in family.KINDS:
        assert got["kv_pool_bytes_" + kind] == family.pool_bytes(
            tiny, tiny["engine"], kind, itemsize=4) > 0
    # the configuration the benchmark runs, by hand: 2 full layers of 8
    # slots x 68 pages, 9 window layers of 8 rings x 2 pages, pages of 256
    # tokens of (256 stored + 128) numbers a kv head in bf16
    e = real["engine"]
    assert family.pool_bytes(real, e, "full") \
        == 2 * 8 * 68 * 256 * 4 * (256 + 128) * 2 == 855_638_016
    assert family.pool_bytes(real, e, "window") \
        == 9 * 8 * 2 * 256 * 8 * (256 + 128) * 2 == 226_492_416
    assert family.pool_width(192) == 256 and family.pool_width(128) == 128


# -- what the algorithm needs -------------------------------------------------


def test_sizes_of_the_configuration_the_benchmark_runs(real):
    q, o = 4096 * 64 * 192, 64 * 128 * 4096
    full = q + 4096 * 4 * 192 + 4096 * 4 * 128 + o
    window = q + 4096 * 8 * 192 + 4096 * 8 * 128 + o
    assert family.attn_matrix_params(real, "full") == full == 89_128_960
    assert family.attn_matrix_params(real, "window") == window == 94_371_840
    assert family.expert_params(real) == 3 * 4096 * 2048 == 25_165_824
    expert_layer = 16 * 25_165_824 + 4096 * 256 + 256
    gains = 11 * 2 * 4096 + 4096 + 9 * 64
    want = 2 * full + 9 * window + 3 * 4096 * 16384 + 10 * expert_layer \
        + 2 * 19072 * 4096 + gains
    assert family.param_count(real) == want
    assert round(family.param_count(real) / 1e9, 2) == 5.42
    assert round(family.weight_bytes(real) / 1e9, 2) == 10.84
    # a cache row, and a page, are their KIND's
    assert family.cache_bytes_per_token(real, "full") == 2560
    assert family.cache_bytes_per_token(real, "window") == 5120
    assert family.page_bytes(real, 256, "full") == 655_360
    assert family.page_bytes(real, 256, "window") == 1_310_720
    assert family.pairs_per_token(real) == 0.5
    assert family.pair_flops(real, "full") \
        == family.pair_flops(real, "window") == 2 * 64 * (192 + 128)
    assert family.window_pairs(5, 128) == 15
    assert family.window_pairs(4096, 128) == 128 * 129 // 2 + 3968 * 128
    n = 5000
    per_token = 2 * full + 9 * window + 3 * 4096 * 16384 \
        + 10 * (0.5 * 25_165_824 + 4096 * 256)
    assert family.matmul_params_per_token(real, head=False) == per_token
    assert family.full_attn_flops(real, n) == 40960 * 2 * (n * (n + 1) // 2)
    assert family.window_attn_flops(real, n) \
        == 40960 * 9 * family.window_pairs(n, 128)
    assert family.prefill_flops(real, n) == pytest.approx(
        2 * per_token * n + family.full_attn_flops(real, n)
        + family.window_attn_flops(real, n) + 2 * 19072 * 4096)
    head = 19072 * 4096
    assert family.decode_flops(real, 6000) == pytest.approx(
        2 * (per_token + head) + 40960 * (2 * 6000 + 9 * 128))
    assert family.decode_flops(real, 100) == pytest.approx(
        2 * (per_token + head) + 40960 * 11 * 100)
    hit = 16 * (1 - (1 - 8 / 256) ** 8)
    assert family.experts_hit(real, 8) == pytest.approx(hit)
    outside = family.param_count(real) - 10 * 16 * 25_165_824 \
        - (19072 - 8) * 4096
    assert family.decode_bytes(real, 8 * 7200, 8) == pytest.approx(
        2 * (outside + 10 * hit * 25_165_824)
        + 2 * 2560 * 8 * 7200 + 9 * 5120 * 8 * 128)
    # ISSUE 33's reckoning: a step of 8 rows at a mean context of 7.2k
    assert 4.6e9 < family.decode_bytes(real, 8 * 7200, 8) < 4.9e9
    # holding every position at 8 heads in all 11 layers would add 3 GB
    assert 11 * 5120 * 8 * 7200 - family.attn_cache_bytes(
        real, 8 * 7200, 8 * 128) > 2.8e9


def test_needs_dispatches_on_the_family_key(real):
    assert families.needs(real) is family


# -- the traffic --------------------------------------------------------------


def test_the_mix_is_the_issues_letter_for_letter():
    cell = manifest.load_cell(CELL)
    mix = cell.mix
    assert mix["kind"] == "serve_family" and cell.mix_name == "long-closed"
    assert mix["arrivals"] == {"process": "closed", "clients": 12,
                               "pool": 600}
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "lo": 2048,
                                    "hi": 16384}
    assert mix["output_tokens"] == {"dist": "log_uniform", "lo": 256,
                                    "hi": 1024}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"], mix["schedule_seed"]) == (60, 6, 10, 33)
    assert "shared_prefix" not in mix
    for conceded in ("0.25 a step", "half a row", "11 layers",
                     "exchange is absent"):
        assert conceded in mix["what"]
    requests = traffic.serve_requests(mix, 2**31 + 7, 51, 19072)
    assert len(requests) == 600
    assert {r.client for r in requests} == set(range(12))
    assert 2048 <= min(len(r.prompt) for r in requests) <= 2060
    assert 16300 <= max(len(r.prompt) for r in requests) <= 16384
    assert 256 <= min(r.max_new_tokens for r in requests) <= 257
    assert 1020 <= max(r.max_new_tokens for r in requests) <= 1024
    assert max(len(r.prompt) + r.max_new_tokens for r in requests) <= 17408
    assert max(int(r.prompt.max()) for r in requests) < 19072
    # every prompt is past the prefill kernel's first length
    from paddle_tpu.kernels import flash_attention as fa
    assert min(len(r.prompt) for r in requests) >= fa.GQA_MIN_SEQ
    # the order of lengths is the schedule's, the ids the seed's
    again = traffic.serve_requests(mix, 5, 51, 19072)
    assert [len(r.prompt) for r in again] == [len(r.prompt)
                                              for r in requests]
    assert not np.array_equal(again[0].prompt, requests[0].prompt)


def test_the_prompts_meet_four_prefill_programs():
    """68 pages a sequence is past the policy's `PAGE_BUCKETS_MAX`: a
    prompt pads to the next power of two of its pages and is prefilled a
    prompt a round."""
    from paddle_tpu.inference import scheduler

    engine = type("E", (), {"max_batch": 8, "page_size": 256,
                            "pages_per_seq": 68, "max_seq_len": 17408})()
    policy = scheduler.SchedulerPolicy()
    seen = set()
    for n in (2048, 2049, 4096, 5000, 8192, 8193, 16384):
        seen.add(policy.prefill_bucket(engine, [(0, range(n))]))
    assert seen == {(1, 2048), (1, 4096), (1, 8192), (1, 16384)}


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
    assert checks["logit_gap_mean"]["value"] <= checks["logit_gap_p99"][
        "value"] <= checks["logit_gap_max"]["value"]
    assert 0 <= checks["picks_differ_share"]["value"] <= 1
    assert checks["tokens_out_of_vocab"]["value"] == 0


def test_release_frees_the_pools_and_the_weights(tiny):
    system = serve_family.build(tiny_cell(tiny), 3)
    engine, model = system.engine, system.model
    assert len(engine.k_pages) == len(engine.v_pages) == 12
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
    float32 reference itself puts first read 0; the fp8 control reads
    wider; a token altered where it is produced reads wider still."""
    from benchmark.reference import mimo_v2 as reference

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
    assert gaps.max() == 0.0 and len(gaps) == 10 and 0 <= flipped <= 1
    control, _ = serve_family.logit_gaps(tiny, w, sample, 40, 8, "fp8")
    assert control.max() > 1e-3 and control.mean() > 1e-4
    sample[0].tokens[2] = (sample[0].tokens[2] + 1) % tiny["vocab_size"]
    altered, _ = serve_family.logit_gaps(tiny, w, sample, 40, 8)
    assert altered.max() > control.max()


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(REPO, "benchmark", "reference",
                           "mimo_v2.py")) as f:
        source = f.read()
    assert "paddle_tpu" not in source.split('"""', 2)[2]


def test_the_reference_in_blocks_is_the_reference_whole(tiny, monkeypatch):
    """Blocks of queries, of tokens and an expert at a time change no
    number beyond round-off."""
    from benchmark.reference import mimo_v2 as reference

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
    np.testing.assert_allclose(blocked, whole, atol=2e-5)


# -- the readers --------------------------------------------------------------


def _without_scopes_and_counts():
    return without_scopes_and_counts(_raw())


def test_the_decode_attention_against_its_roofline_a_page_by_its_kind(
        tiny, traced):
    """(90 + 90) live window pages at a window page's bytes and (80 + 80)
    full pages at a full page's, at the test's HBM peak, over the 6 ms
    under the two scopes."""
    cell = tiny_cell(tiny)
    size = tiny["engine"]["page_size"]
    window = family.page_bytes(tiny, size, "window")
    full = family.page_bytes(tiny, size, "full")
    assert (window, full) == (8 * 2 * (24 + 16) * 2, 8 * 1 * (24 + 16) * 2)
    read = manifest.load_reader("cache_attn_decode_roofline")
    assert read(traced(_raw()), HostLog(), cell) == pytest.approx(
        100 * (180 * window + 160 * full)
        / TEST_PEAKS["hbm_bytes_per_s"] / 0.006)
    assert read(None, HostLog(), cell) is None
    assert read(traced(_without_scopes_and_counts()), HostLog(), cell) \
        is None
    # a family whose table says ONE page size: both kinds' pages at it
    afmoe = _read(DATA, "configs", "tiny-afmoe.json")
    other = tiny_cell(tiny)
    other.config = afmoe
    page = families.needs(afmoe).page_bytes(afmoe,
                                            afmoe["engine"]["page_size"])
    assert read(traced(_raw()), HostLog(), other) == pytest.approx(
        100 * 340 * page / TEST_PEAKS["hbm_bytes_per_s"] / 0.006)


def test_the_full_layers_prefill_attention_against_its_roofline(tiny,
                                                                traced):
    """Prompts of 12 and 40 tokens, 8 ms under `attn/full` in the one
    prefill program of the trace: 3 full layers, 4 heads, keys of 24 over
    values of 16."""
    cell = tiny_cell(tiny)
    log = HostLog()
    log.samples = {"prefill": [(0.0, 12), (0.0, 40)]}
    pairs = 12 * 13 // 2 + 40 * 41 // 2
    assert family.full_attn_flops(tiny, 12) + family.full_attn_flops(
        tiny, 40) == 2 * 4 * (24 + 16) * 3 * pairs
    read = manifest.load_reader("full_prefill_attn_roofline")
    assert read(traced(_raw()), log, cell) == pytest.approx(
        100 * 2 * 4 * 40 * 3 * pairs / TEST_PEAKS["bf16_flops_per_s"]
        / 0.008)
    assert read(None, log, cell) is None
    assert read(traced(_raw()), HostLog(), cell) is None
    assert read(traced(_without_scopes_and_counts()), log, cell) is None


def test_the_cell_reports_what_it_lists():
    m = manifest.load_manifest(REPO)
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert {"tpot_p95_ms", "out_tokens_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    names = {e["name"] for e in cell.per_layer}
    assert {"cache_attn_decode_roofline", "full_prefill_attn_roofline",
            "window_prefill_attn_roofline", "decode_sub_ms.window_attn",
            "decode_sub_ms.full_attn", "window_pages_live_pct",
            "window_pages_read_pct",
            "mfu.serve", "decode_roofline", "mfu.prefill",
            "prefill_roofline", "decode_sub_ms.experts",
            "decode_sub_ms.router", "expert_pairs_per_step",
            "experts_hit_pct", "experts_read_pct",
            "prefill_expert_rows_per_pair", "queue_wait_p50_ms",
            "kv_pages_used_pct", "decode_step_ms", "decode_ms.attn",
            "decode_ms.mlp", "decode_ms.head", "decode_ms.other",
            "device_idle_pct.serve", "builds_in_trace", "gen_lag_p95_ms",
            "batch_occupancy_pct", "idle_pct.prefill", "idle_pct.kv_scatter",
            "idle_pct.decode_launch", "idle_pct.emit", "idle_pct.outside",
            "compiles_in_window"} <= names
    for e in m["per_layer"]:
        if CELL in e.get("workloads", []):
            assert e["moves"] in ("tpot_p95_ms", "out_tokens_per_s")
    limits = cell.params["limits"]
    assert set(limits) == {"logit_gap_mean", "logit_gap_p99", "logit_gap_max"}
    assert limits["logit_gap_max"] is None
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_p99"] < 1
