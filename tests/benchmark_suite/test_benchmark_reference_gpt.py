"""benchmark/reference/gpt.py against the program's GPTForCausalLM at a tiny
size on the CPU (float32, so the two should agree to rounding), and
benchmark/flops.py against hand-worked numbers for GPT-3 1.3B."""
import numpy as np
import pytest

from benchmark_suite_helpers import tiny_cell

from benchmark import flops, program, weights
from benchmark.reference import gpt as reference


@pytest.fixture(scope="module")
def cell():
    return tiny_cell("tiny-gpt.tiny-open")


def test_full_forward_logits_agree(cell):
    import paddle_tpu as paddle

    cfg = cell.config
    model = program.build_model(cfg, seed=11, train=False)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 24)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._data[0])
    want = np.asarray(reference.logits_at(
        weights.make(cfg, 11, cfg["dtype"]), cfg, ids, np.arange(24)))
    assert got.shape == want.shape == (24, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_prefill_then_decode_through_the_engine_agrees_logits_level(cell):
    cfg = cell.config
    model = program.build_model(cfg, seed=12, train=False)
    engine = program.build_engine(model, cfg["engine"])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (9, 33, 17)]
    rids = [engine.add_request(p, max_new_tokens=10) for p in prompts]
    done = {f.request_id: np.asarray(f.output_ids) for f in engine.run()}
    tree = weights.make(cfg, 12, cfg["dtype"])
    for rid, prompt in zip(rids, prompts):
        out = done[rid]
        ids = np.concatenate([prompt, out])
        rows = np.asarray(reference.logits_at(
            tree, cfg, ids, len(prompt) - 1 + np.arange(len(out))))
        gaps = rows.max(-1) - rows[np.arange(len(out)), out]
        # float32 on both sides: the served token IS the reference's best
        # but for ties broken by rounding
        assert gaps.max() <= 1e-5, gaps


def test_loss_and_every_gradient_of_one_train_step_agree():
    cell = tiny_cell("tiny-gpt.tiny-train")
    cfg, trainer = cell.config, cell.config["trainer"]
    model = program.build_model(cfg, seed=13, train=True,
                                recompute=trainer["recompute"])
    step, _ = program.build_trainer(model, trainer)
    rng = np.random.default_rng(2)
    x = rng.integers(0, cfg["vocab_size"], (2, 32))
    y = rng.integers(0, cfg["vocab_size"], (2, 32))
    loss = float(step(program.to_tensor(x), program.to_tensor(y)))
    state = program.optimizer_state(step)
    start = weights.make(cfg, 13, cfg["dtype"])
    want_loss, want = reference.loss_and_grads(
        {k: np.asarray(v, np.float32) for k, v in start.items()}, cfg, x, y)
    assert abs(loss - want_loss) <= 1e-5
    assert set(state) == set(want)
    for name, g in want.items():
        # AdamW's first moment after one step from zero is (1 - beta1) g
        got = np.asarray(state[name]["moment1"]) / (1 - trainer["beta1"])
        scale = max(float(np.abs(np.asarray(g)).max()), 1e-6)
        np.testing.assert_allclose(got, np.asarray(g), atol=2e-4 * scale,
                                   err_msg=name)


def test_lower_precision_modes_move_the_reference(cell):
    cfg = cell.config
    tree = weights.make(cfg, 14, cfg["dtype"])
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], 40)
    full = np.asarray(reference.logits_at(tree, cfg, ids, np.arange(40)))
    errs = {}
    for mode in ("int8", "fp8"):
        low = np.asarray(reference.logits_at(tree, cfg, ids, np.arange(40),
                                             mode))
        errs[mode] = float(np.abs(low - full).max())
    assert 1e-4 < errs["int8"] < errs["fp8"] < 1.0, errs


GPT3_XL = dict(hidden_size=2048, intermediate_size=8192,
               num_attention_heads=16, vocab_size=50304,
               max_position_embeddings=2048, num_hidden_layers=24)


def test_flops_against_hand_worked_numbers():
    # 6 x (L x (4 h^2 + 2 h f) + v h) + 12 h L (s + 1) / 2
    l12 = dict(GPT3_XL, num_hidden_layers=12)
    assert flops.matmul_params(l12) == 12 * 12 * 2048 ** 2 + 50304 * 2048
    assert abs(flops.train_flops_per_token(l12, 2048) / 1e9 - 4.54) < 0.01
    assert abs(flops.train_flops_per_token(GPT3_XL, 2048) / 1e9 - 8.47) < 0.01
    # decode: every weight once (2.63 GB in bf16) + 2 h L x 2 bytes a token
    assert abs(flops.weight_bytes(GPT3_XL) / 1e9 - 2.63) < 0.005
    per_token = 2 * 2048 * 24 * 2
    assert flops.decode_bytes(GPT3_XL, 1000) \
        == flops.weight_bytes(GPT3_XL) + 1000 * per_token
    # the program's parameter count is the table the weights are made from
    n = sum(int(np.prod(s)) for _, s, _, _ in weights.leaf_specs(GPT3_XL))
    assert flops.weight_bytes(GPT3_XL) == 2 * n
    # prefill of n tokens: n (n + 1) / 2 attended pairs, head for one row
    n_tok = 512
    body = 2 * (flops.matmul_params(GPT3_XL) - 50304 * 2048) * n_tok \
        + 4 * 2048 * 24 * n_tok * (n_tok + 1) // 2 + 2 * 50304 * 2048
    assert flops.prefill_flops(GPT3_XL, n_tok) == body
    # one decoded token against c cached ones
    assert flops.decode_flops(GPT3_XL, 700) == \
        2 * flops.matmul_params(GPT3_XL) + 4 * 2048 * 24 * 700
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # HBM-bound: 2.63 GB / 819 GB/s = 3.2 ms
    t = flops.roofline_seconds(8 * flops.decode_flops(GPT3_XL, 700),
                               flops.decode_bytes(GPT3_XL, 5600), peak)
    assert abs(t - flops.decode_bytes(GPT3_XL, 5600) / 819e9) < 1e-12
