"""The `afmoe` family's part of the benchmark (Trinity-Mini): its weight
table against the program's parameters, its operations and bytes against
hand-worked numbers for the configuration BENCHMARK.json runs, the catalog
row's keys in the configuration's file, the traffic mix, the `serve_family`
driver end to end at a tiny size, the control, and each new reader on a
hand-made trace written with xplane_writer.py."""
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark_suite_helpers import (DATA, REPO, TEST_PEAKS,  # noqa: F401
                                     traced, without_scopes_and_counts)
from benchmark_suite_helpers import afmoe_raw as _raw

from benchmark import families, manifest, run, traffic
from benchmark.drivers import serve_family
from benchmark.families import afmoe as family
from benchmark.hostlog import HostLog

MS = 1_000_000  # ns
CELL = "trinity-mini-ep8.mixed-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real():
    """The configuration the benchmark runs."""
    return manifest.load_cell(CELL).config


@pytest.fixture(scope="module")
def tiny():
    return _read(DATA, "configs", "tiny-afmoe.json")


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
        name="tiny-afmoe.tiny-closed", chips=1, config_name="tiny-afmoe",
        config=tiny, mix_name="tiny-closed", mix=mix,
        params={"limits": {"logit_gap_mean": 1e-4, "logit_gap_p99": 1e-3,
                           "logit_gap_max": None}},
        end_to_end=e2e, per_layer=[], peaks=dict(TEST_PEAKS))


# -- the configuration --------------------------------------------------------


def test_the_file_holds_every_key_of_the_catalog_row(real):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    entry = next(c for c in manifest.load_manifest(REPO)["configs"]
                 if c["name"] == "trinity-mini-ep8")
    assert entry["source"] == row["source_url"] == real["source"]
    assert entry["reduced"] == real["reduced"] == ["num_experts"]
    differ = {k for k, v in row["config"].items() if real.get(k) != v}
    assert differ == {"num_experts"}
    assert real["published"] == {"num_experts": 128} \
        and real["router_experts"] == 128 and real["num_experts"] == 16
    assert (real["ep_rank"], real["ep_degree"]) == (0, 8)
    assert real["num_hidden_layers"] == 32 == len(real["layer_types"])
    assert {"output_gate", "qk_norm", "positions", "norms", "expert_bias",
            "weights", "dtype"} <= set(real["assumed"])
    assert real["engine"] == {"max_batch": 8, "max_seq_len": 9216,
                              "page_size": 256, "decode_burst": 16}


# -- the weight table ---------------------------------------------------------


def test_the_table_names_the_programs_parameters(tiny):
    from paddle_tpu.models import AfmoeForCausalLM

    model = AfmoeForCausalLM(family.model_config(tiny))
    want = {n: tuple(p.shape) for n, p in model.named_parameters()}
    got = {n: tuple(s) for n, s, _, _ in family.leaf_specs(tiny)}
    assert got == want
    assert family.param_count(tiny) == sum(
        int(np.prod(s)) for s in want.values())


def test_weights_follow_the_seed_and_the_bias_is_small(tiny):
    a = family.make_weights(tiny, 5, "float32")
    b = family.make_weights(tiny, 5, "float32")
    c = family.make_weights(tiny, 2**31 + 11, "float32")
    name = "model.layers.3.self_attn.gate_proj.weight"
    np.testing.assert_array_equal(np.asarray(a[name]), np.asarray(b[name]))
    assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))
    bias = np.asarray(a["model.layers.4.mlp.experts.gate.expert_bias"])
    assert bias.shape == (16,) and 0 < np.abs(bias).max() < 0.06
    gain = np.asarray(a["model.layers.0.self_attn.q_norm.weight"])
    assert gain.shape == (16,) and abs(gain.mean() - 1) < 0.05
    assert family.make_weights(tiny, 5)[name].dtype == jax.numpy.bfloat16
    model = family.build_model(tiny, 9)
    w = family.make_weights(tiny, 9, tiny["dtype"])
    for n, p in model.named_parameters():
        np.testing.assert_array_equal(np.asarray(p._data), np.asarray(w[n]))


# -- what the algorithm needs -------------------------------------------------


def test_sizes_of_the_configuration_the_benchmark_runs(real):
    attn = 2048 * 4096 * 3 + 2048 * 512 * 2
    assert family.attn_matrix_params(real) == attn == 27_262_976
    assert family.expert_params(real) == 3 * 2048 * 1024 == 6_291_456
    assert (family.window_layers(real), family.full_layers(real),
            family.expert_layers(real)) == (24, 8, 30)
    expert_layer = 17 * 6_291_456 + 2048 * 128 + 128
    gains = 32 * (4 * 2048 + 2 * 128) + 2048
    want = 32 * attn + 2 * 3 * 2048 * 6144 + 30 * expert_layer \
        + 2 * 200_192 * 2048 + gains
    assert family.param_count(real) == want
    assert round(family.weight_bytes(real) / 1e9, 2) == 9.97
    assert family.cache_bytes_per_token(real) == 2048
    assert family.page_bytes(real, 256) == 524_288
    assert family.pairs_per_token(real) == 1.0
    assert family.pair_flops(real) == 4 * 32 * 128
    # a window layer attends min(position + 1, 2048) keys a query
    assert family.window_pairs(5, 2048) == 15
    assert family.window_pairs(4096, 2048) == 2048 * 2049 // 2 + 2048 * 2048
    n = 5000
    per_token = 32 * attn + 2 * 3 * 2048 * 6144 \
        + 30 * (2 * 6_291_456 + 2048 * 128)
    assert family.matmul_params_per_token(real, head=False) == per_token
    assert family.prefill_flops(real, n) == pytest.approx(
        2 * per_token * n + 16384 * (8 * n * (n + 1) // 2
                                     + 24 * family.window_pairs(n, 2048))
        + 2 * 200_192 * 2048)
    # decode: a context inside the window pays every layer alike, one
    # beyond it pays the window in 24 layers of 32
    head = 200_192 * 2048
    assert family.decode_flops(real, 700) == pytest.approx(
        2 * (per_token + head) + 16384 * 32 * 700)
    assert family.decode_flops(real, 6000) == pytest.approx(
        2 * (per_token + head) + 16384 * (8 * 6000 + 24 * 2048))
    hit = 16 * (1 - (1 - 8 / 128) ** 8)
    assert family.experts_hit(real, 8) == pytest.approx(hit)
    outside = family.param_count(real) - 30 * 16 * 6_291_456 \
        - (200_192 - 8) * 2048
    assert family.decode_bytes(real, 8 * 5000, 8) == pytest.approx(
        2 * (outside + 30 * hit * 6_291_456)
        + 2048 * (8 * 40000 + 24 * 8 * 2048))
    # rows on both sides of the window: the mean errs HIGH, never low
    exact = family.attn_cache_bytes(real, 1000 + 9000, 1000 + 2048)
    assert family.decode_bytes(real, 10000, 2, hit=0) \
        - family.decode_bytes(real, 0, 2, hit=0) >= exact
    assert 6.0e9 < family.decode_bytes(real, 8 * 2600, 8) < 6.9e9


def test_needs_dispatches_on_the_family_key(real):
    assert families.needs(real) is family


# -- the traffic --------------------------------------------------------------


def test_the_mix_is_the_issues_letter_for_letter():
    cell = manifest.load_cell(CELL)
    mix = cell.mix
    assert mix["kind"] == "serve_family" and cell.mix_name == "mixed-closed"
    assert mix["arrivals"]["process"] == "closed" \
        and mix["arrivals"]["clients"] == 12
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "lo": 256,
                                    "hi": 8192}
    assert mix["output_tokens"] == {"dist": "log_uniform", "lo": 256,
                                    "hi": 1024}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"]) == (60, 6, 10)
    assert "shared_prefix" not in mix and "schedule_seed" in mix
    requests = traffic.serve_requests(mix, 2**31 + 7, 51, 200_192)
    assert len(requests) == mix["arrivals"]["pool"] >= 400
    assert {r.client for r in requests} == set(range(12))
    assert 256 <= min(len(r.prompt) for r in requests) <= 258
    assert 8150 <= max(len(r.prompt) for r in requests) <= 8192
    assert 256 <= min(r.max_new_tokens for r in requests) <= 257
    assert 1020 <= max(r.max_new_tokens for r in requests) <= 1024
    assert max(len(r.prompt) + r.max_new_tokens for r in requests) <= 9216
    assert max(int(r.prompt.max()) for r in requests) < 200_192
    # the order of lengths is the schedule's, the ids the seed's
    again = traffic.serve_requests(mix, 5, 51, 200_192)
    assert [len(r.prompt) for r in again] == [len(r.prompt)
                                              for r in requests]
    assert not np.array_equal(again[0].prompt, requests[0].prompt)


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
    assert len(engine.k_pages) == len(engine.v_pages) == 9
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
    from benchmark.reference import afmoe as reference

    w = family.make_weights(tiny, 4, "float32")
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
                           "afmoe.py")) as f:
        source = f.read()
    assert "paddle_tpu" not in source.split('"""', 2)[2]


# -- the readers --------------------------------------------------------------


@pytest.mark.parametrize("metric, value", [
    ("decode_sub_ms.window_attn", 4 / 4), ("decode_sub_ms.full_attn", 2 / 4),
    ("window_pages_live_pct", 100 * 180 / 480),
    ("window_pages_read_pct", 100 * 210 / 480),
    # (90 + 90 + 80 + 80) live pages of 8 x 2 x 16 x 2 x 4 B at the test's
    # HBM peak, over the 6 ms under the two scopes
    ("cache_attn_decode_roofline", None)])
def test_what_the_program_says_of_its_window_layers(tiny, traced, metric,
                                                    value):
    cell = tiny_cell(tiny)
    if value is None:
        # one layout for every layer: a page's bytes whatever its kind
        size = tiny["engine"]["page_size"]
        page = family.page_bytes(tiny, size, itemsize=2)
        assert page == 8 * 2 * 2 * 16 * 2 == family.page_bytes(
            tiny, size, "window") == family.page_bytes(tiny, size, "full")
        value = 100 * 340 * page / TEST_PEAKS["hbm_bytes_per_s"] / 0.006
    read = manifest.load_reader(metric)
    assert read(traced(_raw()), HostLog(), cell) == pytest.approx(value)
    assert read(None, HostLog(), cell) is None
    # a program without the finer scopes and the counts (the parent's, or
    # another family's): nothing, and no raise
    plain = without_scopes_and_counts(_raw())
    assert read(traced(plain), HostLog(), cell) is None


def test_the_window_layers_prefill_attention_against_its_roofline(tiny,
                                                                  traced):
    """Prompts of 12 and 40 tokens over a window of 16, 20 ms under
    `attn/window` in the one prefill program of the trace."""
    cell = tiny_cell(tiny)
    log = HostLog()
    log.samples = {"prefill": [(0.0, 12), (0.0, 40)]}
    pairs = 12 * 13 // 2 + (16 * 17 // 2 + 24 * 16)
    assert family.window_attn_flops(tiny, 12) + family.window_attn_flops(
        tiny, 40) == 4 * 4 * 16 * 7 * pairs
    read = manifest.load_reader("window_prefill_attn_roofline")
    assert read(traced(_raw()), log, cell) == pytest.approx(
        100 * 4 * 4 * 16 * 7 * pairs / TEST_PEAKS["bf16_flops_per_s"] / 0.020)
    assert read(None, log, cell) is None
    assert read(traced(_raw()), HostLog(), cell) is None


def test_the_cell_reports_what_the_issue_lists():
    m = manifest.load_manifest(REPO)
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert {"tpot_p95_ms", "out_tokens_per_s", "setup_s"} <= {
        e["name"] for e in cell.end_to_end}
    names = {e["name"] for e in cell.per_layer}
    assert {"decode_sub_ms.window_attn", "decode_sub_ms.full_attn",
            "window_pages_live_pct", "window_pages_read_pct",
            "cache_attn_decode_roofline", "window_prefill_attn_roofline",
            "mfu.serve", "decode_roofline",
            "mfu.prefill", "prefill_roofline", "decode_sub_ms.experts",
            "decode_sub_ms.router", "decode_sub_ms.shared_expert",
            "expert_pairs_per_step", "experts_hit_pct", "experts_read_pct",
            "prefill_expert_rows_per_pair", "queue_wait_p50_ms",
            "kv_pages_used_pct", "decode_step_ms", "decode_ms.attn",
            "decode_ms.mlp", "decode_ms.head", "decode_ms.other",
            "device_idle_pct.serve", "builds_in_trace",
            "compiles_in_window"} <= names
    for e in m["per_layer"]:
        if CELL in e.get("workloads", []):
            assert e["moves"] in ("tpot_p95_ms", "out_tokens_per_s")
    limits = cell.params["limits"]
    assert set(limits) == {"logit_gap_mean", "logit_gap_p99", "logit_gap_max"}
    assert limits["logit_gap_max"] is None
    assert 0 < limits["logit_gap_mean"] < limits["logit_gap_p99"] < 1
