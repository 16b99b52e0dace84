"""The latent-attention MoE decoder (models/latent_moe.py), its expert
layer (incubate/distributed/models/moe/expert_share.py) and the serving
engine's one-pool cache layout, at a small size on the CPU, against the
plain float32 reference (benchmark/reference/pangu_ultra_moe.py) on the
family's seeded weights."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ServingEngine  # noqa: E402
from paddle_tpu.kernels import expert_grouped, expert_hit  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,  # noqa: E402
                               LatentMoEConfig, LatentMoEForCausalLM,
                               latent_moe)
from paddle_tpu.observability import tracing  # noqa: E402

from benchmark.families import pangu_ultra_moe as family  # noqa: E402
from benchmark.reference import pangu_ultra_moe as reference  # noqa: E402

SEED = 2027


def tiny_cfg(**over):
    with open(os.path.join(REPO, "tests", "benchmark_suite", "data",
                           "configs", "tiny-pangu.json")) as f:
        return dict(json.load(f), **over)


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def weights(cfg):
    return family.make_weights(cfg, SEED, "float32")


@pytest.fixture(scope="module")
def model(cfg):
    return family.build_model(cfg, SEED)


def _ref_logits(weights, cfg, ids, mode="f32"):
    return np.asarray(reference.logits_at(weights, cfg, ids,
                                          np.arange(len(ids)), mode))


def test_the_config_lists_every_layers_kinds():
    kinds = LatentMoEConfig.tiny().layer_kinds()
    assert kinds == [("latent", "dense", "sandwich"),
                     ("latent", "routed+shared", "sandwich"),
                     ("latent", "routed+shared", "sandwich")]
    with pytest.raises(ValueError, match="unknown layer kinds"):
        latent_moe.LatentMoEDecoderLayer(
            LatentMoEConfig.tiny(), ("full", "dense", "sandwich"))


def test_forward_agrees_with_the_reference(model, weights, cfg):
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 23)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    want = _ref_logits(weights, cfg, ids)
    # float32 against float32 at HIGHEST: round-off of the summation order
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # and a reference in bfloat16 arithmetic is NOT that close
    assert np.abs(_ref_logits(weights, cfg, ids, "bf16") - want).max() > 2e-3


def test_a_pre_norm_stack_is_another_list_of_kinds(cfg):
    pre = tiny_cfg(sandwich_norm=False)
    names = {n for n, *_ in family.leaf_specs(pre)}
    assert not any("post_" in n for n in names)
    m = family.build_model(pre, SEED)
    ids = np.arange(11) % pre["vocab_size"]
    got = np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]
    want = _ref_logits(family.make_weights(pre, SEED, "float32"), pre, ids)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def _engine(model, cfg, **kw):
    e = cfg["engine"]
    return ServingEngine(model, max_batch=e["max_batch"],
                         max_seq_len=e["max_seq_len"],
                         page_size=e["page_size"],
                         decode_burst=e["decode_burst"],
                         decode_strategy="greedy_search", **kw)


def test_the_engine_allocates_one_latent_pool_a_layer(model, cfg):
    eng = _engine(model, cfg)
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    assert model.kv_cache_layouts() == (((1, width),),) * len(
        model.model.layers)
    assert len(eng.k_pages) == cfg["num_hidden_layers"] and not eng.v_pages
    assert {p.shape for p in eng.k_pages} == {(1, 4 * 8, 8, width)}
    assert family.cache_bytes_per_token(cfg, 4) \
        == eng.k_pages[0][0, 0, 0].nbytes


def test_prefill_then_burst_decode_through_the_engine(model, weights, cfg):
    """Logits, not tokens: every served token's reference logit is the
    reference's best, to round-off, for prompts that share a prefill round
    and outlive several bursts and a page boundary."""
    eng = _engine(model, cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (5, 13, 22, 9)]
    rids = [eng.add_request(p, max_new_tokens=14) for p in prompts]
    done = {f.request_id: f.output_ids for f in eng.run()}
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(done[rid])
        assert len(out) == 14
        ids = np.concatenate([prompt, out])
        ref = _ref_logits(weights, cfg, ids)[len(prompt) - 1:-1]
        gap = ref.max(-1) - ref[np.arange(len(out)), out]
        assert gap.max() < 1e-4, gap
    # the engine's streams are generate()'s (dense latent cache)
    tokens, _ = model.generate(paddle.to_tensor(prompts[1][None]),
                               max_new_tokens=14)
    np.testing.assert_array_equal(np.asarray(tokens._data)[0], done[rids[1]])
    assert len(eng._free_pages) == eng._n_pages_total


def test_absorbed_attention_is_decompressed_attention(model, cfg):
    """One new token over latent pages in absorbed form against the same
    token through the dense cache in decompressed form."""
    attn = model.model.layers[1].self_attn
    rng = np.random.default_rng(2)
    b, t, page, pps = 3, 13, 8, 4
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    x = jnp.asarray(rng.normal(size=(b, t + 1, cfg["hidden_size"])),
                    jnp.float32)
    empty = jnp.zeros((b, pps * page, 1, width), jnp.float32)
    _, (rows,) = attn.forward_cached(paddle.to_tensor(x[:, :t]), (empty,), 0)
    want, (rows2,) = attn.forward_cached(paddle.to_tensor(x[:, t:]),
                                         (rows,), t)
    tables = jnp.asarray(1 + np.arange(b * pps).reshape(b, pps), jnp.int32)
    lens = jnp.full((b,), t, jnp.int32)
    pool = pa.prefill_paged_pool(
        jnp.zeros((1, 1 + b * pps, page, width), jnp.float32), rows[:, :t],
        tables, lens)
    got, (pool2,) = attn.forward_paged(paddle.to_tensor(x[:, t:]), (pool,),
                                       tables, lens)
    np.testing.assert_allclose(np.asarray(got._data), np.asarray(want._data),
                               atol=2e-5, rtol=2e-5)
    # and the page write put the new row where the dense cache has it
    np.testing.assert_allclose(
        np.asarray(pool2[0][tables].reshape(b, -1, width)[:, t]),
        np.asarray(rows2[:, t, 0]), atol=1e-6)


@pytest.mark.parametrize("block_bytes", [1 << 30, 4096])
def test_blocked_attention_is_whole_attention(cfg, block_bytes, monkeypatch):
    """A row and a group of heads at a time gives what one block gives."""
    monkeypatch.setattr(latent_moe, "SCORE_BLOCK_BYTES", block_bytes)
    c = LatentMoEConfig.tiny()
    rng = np.random.default_rng(3)
    b, s, h = 2, 9, c.num_attention_heads
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_nope, q_rope = f(b, s, h, 16), f(b, s, h, 8)
    latent, w = f(b, s, 40), f(32, h * 32)
    got = latent_moe.decompressed_attention(q_nope, q_rope, latent, w, c)
    w_k, w_v = latent_moe._split_kv_b(w, c)
    k = jnp.einsum("btr,rhn->bthn", latent[..., :32], w_k)
    v = jnp.einsum("btr,rhv->bthv", latent[..., :32], w_v)
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k)
              + jnp.einsum("bshr,btr->bhst", q_rope, latent[..., 32:])) \
        / np.sqrt(24)
    mask = np.tril(np.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    want = jnp.einsum("bhst,bthv->bshv", probs, v).reshape(b, s, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


PATHS = ["dense", "hit", "grouped"]


@pytest.fixture
def expert_path(request, monkeypatch):
    """The form the expert layer takes, whatever the shapes: `dense` is
    `share_ffn`, `hit` the kernel of kernels/expert_hit.py and `grouped`
    that of kernels/expert_grouped.py (interpreted here), which the choice
    would keep for the chip."""
    monkeypatch.setattr(expert_hit, "use_hit_path",
                        lambda *a: request.param == "hit")
    monkeypatch.setattr(expert_grouped, "use_grouped_path",
                        lambda *a: request.param == "grouped")
    return request.param


@pytest.mark.parametrize("expert_path", PATHS, indirect=True)
def test_the_shares_add_up_to_the_uncut_layer(weights, cfg, expert_path):
    """model-configs section 4: the routed parts that all `ep_degree`
    shares give, plus the shared expert counted once, equal what the uncut
    reference gives for the whole layer."""
    every = tiny_cfg(n_routed_experts=cfg["router_experts"])
    uncut = family.make_weights(every, SEED, "float32")
    pre = "model.layers.1.mlp."
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(19, cfg["hidden_size"])), jnp.float32)
    st = dict(reference.static_of(every))
    picks, w = reference.route(x, uncut[pre + "experts.gate.weight"], st,
                               "f32")
    shared = reference.gated_ffn(
        x, *(uncut[pre + f"shared_experts.{n}.weight"]
             for n in reference.FFN), "f32")
    whole = reference.routed_share(
        x, picks, w, uncut[pre + "experts.w_gate"],
        uncut[pre + "experts.w_up"], uncut[pre + "experts.w_down"], 0,
        "f32") + shared
    held, degree = cfg["n_routed_experts"], cfg["ep_degree"]
    total = np.zeros_like(np.asarray(whole))
    for rank in range(degree):
        share = tiny_cfg(ep_rank=rank)
        m = family.build_model(share, SEED)
        layer = m.model.layers[1].mlp
        for name in ("w_gate", "w_up", "w_down"):   # this rank's experts
            getattr(layer.experts, name)._rebind(
                uncut[pre + "experts." + name][rank * held:(rank + 1) * held])
        layer.experts.gate.weight._rebind(uncut[pre + "experts.gate.weight"])
        assert layer.experts.first == rank * held
        with paddle.no_grad():   # the hit path is for calls without one
            routed = np.asarray(layer.experts(paddle.to_tensor(x))._data)
        # the program's share is the reference's share of the same rank
        want = reference.routed_share(
            x, picks, w, *(uncut[pre + "experts." + n]
                           [rank * held:(rank + 1) * held]
                           for n in ("w_gate", "w_up", "w_down")),
            rank * held, "f32")
        np.testing.assert_allclose(routed, np.asarray(want), atol=1e-6)
        total += routed
    assert np.abs(total).max() > 1e-3 and np.abs(shared).max() > 1e-3
    np.testing.assert_allclose(total + np.asarray(shared), np.asarray(whole),
                               atol=2e-6)


@pytest.mark.parametrize("path", PATHS)
def test_no_token_is_dropped_and_the_denominator_is_over_all_picks(cfg,
                                                                   path):
    """16 tokens that all pick the same experts: a capacity would drop
    most; the weights of a token's picks sum to the scaling factor whether
    its experts are held here or not, and every one of the 16 gets the
    whole weighted sum of its picks' products on either path."""
    from paddle_tpu.incubate.distributed.models.moe import expert_share

    x = jnp.tile(jnp.asarray(np.random.default_rng(5).normal(size=(1, 48)),
                             jnp.float32), (16, 1))
    w_r = jnp.asarray(np.random.default_rng(6).normal(size=(48, 16)),
                      jnp.float32)
    picks, weights = expert_share.sigmoid_topk(x, w_r, 4, scale=2.5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    dense = [expert_share.held_weights(picks, weights, first, 4)
             for first in (0, 4, 8, 12)]
    np.testing.assert_allclose(sum(np.asarray(d).sum(-1) for d in dense),
                               2.5, rtol=1e-5)
    # every token keeps every pick: 16 tokens x 4 picks land somewhere
    assert sum(int((np.asarray(d) > 0).sum()) for d in dense) == 64
    rng = np.random.default_rng(7)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(4, 48, 24)), jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(4, 24, 48)), jnp.float32)
    ffn = {"dense": expert_share.share_ffn, "hit": expert_hit.hit_ffn,
           "grouped": expert_grouped.grouped_ffn}[path]
    first = max(range(4), key=lambda r: float(np.asarray(dense[r]).sum()))
    got = np.asarray(ffn(x, dense[first], w_gate, w_up, w_down))
    want = sum(
        float(dense[first][0, e]) * np.asarray(
            (jax.nn.silu(x[:1] @ w_gate[e]) * (x[:1] @ w_up[e])) @ w_down[e])
        for e in range(4))
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, np.tile(want, (16, 1)), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("expert_path", PATHS, indirect=True)
def test_the_step_counts_its_pairs_and_the_experts_hit(model, weights, cfg,
                                                       expert_path):
    """`expert_pairs`, `experts_hit`, `expert_layer_steps` and
    `experts_held` of one decode step, against the reference's picks; a
    dead row counts for nothing. `experts_read` is what the path streams:
    the experts hit on the hit and grouped paths, every held one on the
    dense; `expert_rows` the rows of expert products taken: the step's 4
    tokens for each expert read, on the grouped path a tile of 128 rows for
    each expert hit."""
    b, page, pps = 4, 8, 2
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    L = cfg["num_hidden_layers"]
    tables = jnp.asarray(np.arange(b * pps).reshape(b, pps), jnp.int32)
    pools = [(jnp.zeros((1, b * pps, page, width), jnp.float32),)] * L
    tok = np.asarray([[3], [17], [40], [66]])
    live = jnp.asarray([True, True, False, True])
    with tracing.device_counts() as counts, paddle.no_grad():
        model.forward_paged(paddle.to_tensor(tok), pools, tables,
                            jnp.zeros((b,), jnp.int32), active=live)
    pairs = hit = 0
    for t in (3, 17, 66):   # a first token: the sequence is the token
        picks = []
        reference.logits_at(weights, cfg, [t], [0], picks=picks)
        here = [np.asarray(p)[0] < cfg["n_routed_experts"] for p in picks]
        pairs += sum(int(h.sum()) for h in here)
    for layer in range(L - cfg["first_k_dense_replace"]):
        seen = set()
        for t in (3, 17, 66):
            picks = []
            reference.logits_at(weights, cfg, [t], [0], picks=picks)
            seen |= {int(e) for e in np.asarray(picks[layer])[0]
                     if e < cfg["n_routed_experts"]}
        hit += len(seen)
    assert {k: int(v) for k, v in counts.items()} == {
        "expert_pairs": pairs, "experts_hit": hit,
        "experts_read": 2 * 4 if expert_path == "dense" else hit,
        "expert_rows": {"dense": 4 * 2 * 4, "hit": 4 * hit,
                        "grouped": 128 * hit}[expert_path],
        "expert_layer_steps": 2, "experts_held": 2 * 4}
    # outside a collection nothing is counted and nothing is left behind
    assert not tracing.counting()
    tracing.count("expert_pairs", 1)


@pytest.mark.parametrize("expert_path", PATHS, indirect=True)
def test_the_burst_hands_its_counts_to_the_emit_phase(model, cfg,
                                                      monkeypatch,
                                                      expert_path):
    seen = []
    real = tracing.phase

    def phase(name, **attrs):
        if name == "serving.emit" and attrs:
            seen.append(attrs)
        return real(name, **attrs)

    monkeypatch.setattr(tracing, "phase", phase)
    eng = _engine(model, cfg)
    eng.add_request(np.arange(6), max_new_tokens=6)
    eng.run()
    # the phase that commits the prompt's first token carries the prefill
    # program's counts under names of their own: 6 live tokens of a bucket
    # of 8 in 2 expert layers, the padding in no pair
    first, burst = seen[0], seen[1]
    assert set(first) == {"prefill_expert_pairs", "prefill_expert_rows"}
    assert 0 <= first["prefill_expert_pairs"] <= 2 * 6 * 4
    if expert_path == "dense":
        assert first["prefill_expert_rows"] == 2 * 8 * 4
    elif expert_path == "grouped":
        assert first["prefill_expert_rows"] % 128 == 0 \
            and first["prefill_expert_rows"] <= 2 * 4 * 128
    assert set(burst) == {"expert_pairs", "experts_hit", "experts_read",
                          "expert_rows", "expert_layer_steps",
                          "experts_held"}
    # one live row, 2 expert layers, bursts of 4 steps
    assert burst["expert_layer_steps"] == 2 * 4
    assert burst["experts_held"] == 2 * 4 * 4
    assert burst["experts_read"] == burst[
        "experts_held" if expert_path == "dense" else "experts_hit"]
    assert 0 <= burst["experts_hit"] <= burst["expert_pairs"] <= 2 * 4 * 4


@pytest.mark.parametrize("expert_path", PATHS, indirect=True)
def test_a_padded_prefill_is_the_unpadded_prefill(model, cfg, expert_path):
    """Padded positions, and a padded row (`true_lens` 0), make no pair in
    an expert layer: the counts are the unpadded prompts' own, and the
    first token's logits and the cache rows at live positions are what
    each prompt gives alone."""
    from conftest import check_padded_prefill

    nb, bucket = 4, 16
    counts = check_padded_prefill(
        model, [np.arange(11) % cfg["vocab_size"],
                (np.arange(5) * 7 + 3) % cfg["vocab_size"]], nb, bucket)
    if expert_path == "dense":
        assert int(counts["expert_rows"]) == 2 * nb * bucket * 4


def test_a_gpt_engine_keeps_its_two_pools():
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    eng = ServingEngine(m, max_batch=2, max_seq_len=32, page_size=8,
                        decode_burst=4)
    assert m.kv_cache_layouts() == (((2, 16), (2, 16)),) * len(
        m.kv_cache_windows())
    assert len(eng.k_pages) == len(eng.v_pages) == 2
    assert eng.k_pages[0].shape == eng.v_pages[0].shape == (2, 8, 8, 16)
    k, v = m.init_kv_caches(3, 5)[0]
    assert k.shape == v.shape == (3, 5, 2, 16)
    out = eng._get_burst_fn(True, 4).lower(
        *_burst_args(eng)).compile()
    assert out is not None
    eng.add_request(np.arange(5), max_new_tokens=6)
    assert len(eng.run()[0].output_ids) == 6


def _burst_args(eng):
    params, buffers = eng._cached_params()
    mb = eng.max_batch
    z = lambda dt: jnp.zeros((mb,), dt)  # noqa: E731
    return (params, buffers, tuple(eng.k_pages), tuple(eng.v_pages), (), (),
            z(jnp.int64), jnp.zeros((mb, eng.pages_per_seq), jnp.int32),
            z(jnp.int32), z(jnp.bool_), z(jnp.int32), z(jnp.int32),
            jax.random.key_data(jax.random.key(0)), z(jnp.bool_),
            z(jnp.float32), z(jnp.int32), z(jnp.float32))


@pytest.mark.parametrize("asked, sentence", [
    (dict(kv_cache_quant="int8"), "kv_cache_quant='int8' is not built"),
    (dict(prefix_cache=1), "prefix_cache=1 is not built"),
    (dict(spec_decode=4), "spec_decode=4 is not built"),
    (dict(spec_decode=4, draft_model="a model"), "is not built"),
    (dict(prefill_chunk=16), "prefill_chunk=16 is not built"),
])
def test_what_latent_pages_cannot_do_yet_raises_at_construction(
        model, cfg, asked, sentence):
    with pytest.raises(ValueError, match=sentence):
        _engine(model, cfg, **asked)


def test_latent_pages_are_not_sharded_and_not_handed_off(model, cfg, mesh8):
    with pytest.raises(ValueError, match="cannot be sharded over tp=4"):
        _engine(model, cfg, mesh=mesh8)
    # refused before anything was placed: the model is where it was
    assert all(len(p._data.sharding.device_set) == 1
               for p in model.parameters())
    import paddle_tpu.distributed.mesh as mesh_mod

    mesh_mod.set_mesh(None)
    eng = _engine(model, cfg)
    rid = eng.add_request(np.arange(5), max_new_tokens=4)
    eng.step()
    with pytest.raises(NotImplementedError, match="no hand-off format"):
        eng.detach_request(rid)
    with pytest.raises(NotImplementedError, match="one token a row"):
        model.forward_paged(paddle.to_tensor(np.zeros((1, 2), np.int64)),
                            [], None, None)
