"""Motif-3-Beta's shape (`model_type` Motif) in the one stack of
models/latent_moe.py: grouped differential attention on latent rows over a
window (rings) and over every position (pages), PolyNorm experts, four
residual streams mixed by mHC and YaRN on the full layers, at a small size
on the CPU, against the plain float32 reference
(benchmark/reference/motif.py) on the family's seeded weights; and each
mechanism shown live: the program without it reads wide of the
reference."""
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
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    expert_share)
from paddle_tpu.inference import ServingEngine  # noqa: E402
from paddle_tpu.kernels import expert_grouped, expert_hit  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models import MotifConfig, latent_moe  # noqa: E402

from benchmark.families import motif as family  # noqa: E402
from benchmark.reference import motif as reference  # noqa: E402

SEED = 2042
# the program against the float32 reference, float32 weights on the CPU
TOL = 2e-5


def tiny_cfg(**over):
    with open(os.path.join(REPO, "tests", "benchmark_suite", "data",
                           "configs", "tiny-motif.json")) as f:
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


def _ref_logits(weights, cfg, ids, pad_to=96):
    """The reference's logits at every position of `ids`: causal, so one
    padded length (one compile) serves every sequence."""
    n = len(ids)
    return np.asarray(reference.logits_at(
        weights, cfg, np.pad(ids, (0, max(pad_to - n, 0))), np.arange(n)))


def _engine(model, cfg, **kw):
    e = dict(cfg["engine"], **{k: kw.pop(k) for k in list(kw)
                               if k in cfg["engine"]})
    return ServingEngine(model, max_batch=e["max_batch"],
                         max_seq_len=e["max_seq_len"],
                         page_size=e["page_size"],
                         decode_burst=e["decode_burst"],
                         decode_strategy="greedy_search", **kw)


def _gaps(weights, cfg, prompt, out):
    """How far the reference puts each served token below its own best."""
    ids = np.concatenate([prompt, out])
    ref = _ref_logits(weights, cfg, ids)[len(prompt) - 1:-1]
    return ref.max(-1) - ref[np.arange(len(out)), out]


# -- the stack ----------------------------------------------------------------


def test_the_config_lists_every_layers_kinds():
    c = MotifConfig(num_hidden_layers=7, first_layer=1)
    assert c.layer_kinds() == [
        ("gdla_window", "dense", "pre", "hyper"),
        ("gdla_window", "routed+shared", "pre", "hyper"),
        ("gdla_full", "routed+shared", "pre", "hyper"),
        ("gdla_window", "routed+shared", "pre", "hyper"),
        ("gdla_window", "routed+shared", "pre", "hyper"),
        ("gdla_window", "routed+shared", "pre", "hyper"),
        ("gdla_full", "routed+shared", "pre", "hyper")]
    whole = MotifConfig()
    kinds = whole.layer_kinds()
    assert sum(k[0] == "gdla_full" for k in kinds) == 13
    assert [k[1] for k in kinds[:3]] == ["dense", "dense", "routed+shared"]
    assert (whole.qk_nope_head_dim, whole.cache_width) == (128, 576)
    assert whole.moe_spec()["act"] == {"scale": 0.5, "clamp": 0.5,
                                       "eps": 1e-6}
    with pytest.raises(NotImplementedError, match="noise head"):
        MotifConfig(num_noise_heads=8)
    with pytest.raises(ValueError, match="unknown layer kinds"):
        latent_moe.LatentMoEDecoderLayer(
            MotifConfig.tiny(), ("gdla_full", "dense", "pre", "twisted"))


def test_the_tiny_config_is_the_tiny_file(cfg):
    assert family.model_config(cfg) == MotifConfig.tiny()


def test_forward_agrees_with_the_reference(model, weights, cfg):
    """Contexts several windows long: 40 positions over a window of 8, both
    kinds of layer, past the YaRN ramp's original length of 16."""
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 40))
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    assert got.dtype == np.float32
    for row in range(2):
        want = _ref_logits(weights, cfg, ids[row])
        assert np.abs(want).max() > 0.1
        np.testing.assert_allclose(got[row], want, atol=TOL)


_DIFF_COMBINE = latent_moe.diff_combine
_HYPER_IN = latent_moe.hyper_in


def _only_signal(ctx, lam, kv_heads, value_dim):
    return _DIFF_COMBINE(ctx, jnp.zeros_like(lam), kv_heads, value_dim)


def _plain_mixes(streams, phi, alpha, bias, iters, eps):
    x, res, post = _HYPER_IN(streams, phi, alpha, bias, iters, eps)
    n = res.shape[-1]
    return x, jnp.broadcast_to(jnp.eye(n, dtype=res.dtype), res.shape), \
        jnp.ones_like(post)


OMISSIONS = {
    "noise_head_subtraction": (latent_moe, "diff_combine", _only_signal),
    "mixes": (latent_moe, "hyper_in", _plain_mixes),
    "polynorm": (expert_share, "polynorm",
                 lambda u, *_: jax.nn.silu(u)),
    "yarn": (latent_moe, "yarn_inv_freq", lambda inv, *_: inv),
}


@pytest.mark.parametrize("omitted", sorted(OMISSIONS))
def test_each_mechanism_is_live(model, weights, cfg, omitted, monkeypatch):
    """The program with one mechanism left out (the noise heads'
    subtraction; the mixes, H_res := I and H_post := 1; PolyNorm, SiLU in
    its place; YaRN, plain rope in its place) reads beyond the tolerance
    the whole program is held to against the reference, ten times over."""
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], 64)
    want = _ref_logits(weights, cfg, ids)
    monkeypatch.setattr(*OMISSIONS[omitted])
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(got - want).max() > 10 * TOL


def test_h_res_is_doubly_stochastic(weights, cfg):
    """Over the tiny model's own streams and coefficients, and over logits
    of a spread like theirs: rows and columns sum to 1 to 1e-5, every
    entry > 0. (Twenty iterations end on the columns, which sum to 1
    exactly; at logits spread twice as wide the rows are 1e-4 off, in the
    reference as in the program.)"""
    rng = np.random.default_rng(4)
    n, c = 4, cfg["hidden_size"]
    streams = jnp.asarray(rng.normal(size=(3, 5, n, c)), jnp.float32)
    pre = "model.layers.2.attn_hc."
    _, res, post = latent_moe.hyper_in(
        streams, weights[pre + "phi"], weights[pre + "alpha"],
        weights[pre + "bias"], 20, 1e-5)
    wide = latent_moe.sinkhorn(jnp.asarray(
        rng.normal(size=(64, n, n)) * 0.5, jnp.float32), 20)
    for m in (np.asarray(res), np.asarray(wide)):
        assert (m > 0).all()
        np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
        np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    # far from the identity and from the uniform mix
    assert np.abs(np.asarray(res) - np.eye(n)).max() > 0.1
    assert np.abs(np.asarray(res) - 1 / n).max() > 0.05
    assert (np.asarray(post) > 0).all() and (np.asarray(post) < 2).all()


def test_yarn_takes_the_published_ramp():
    """d 64, base 10,000, factor 64 over 4,096 original positions, beta
    32 / 1: pairs 0-10 keep their frequency, pairs 23-31 are divided by 64,
    the ones between blend linearly."""
    inv = 1.0 / (10000 ** (np.arange(0, 64, 2) / 64))
    got = np.asarray(latent_moe.yarn_inv_freq(
        jnp.asarray(inv, jnp.float32), 64, 10000.0, (64.0, 4096, 32.0, 1.0)))
    np.testing.assert_allclose(got[:11], inv[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], inv[23:] / 64, rtol=1e-6)
    share = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        got[11:23], inv[11:23] / 64 * share + inv[11:23] * (1 - share),
        rtol=1e-5)
    np.testing.assert_allclose(got, np.asarray(reference.frequencies(
        64, 10000.0, (64.0, 4096, 32.0, 1.0))), rtol=1e-6)


def test_a_padded_prefill_through_the_kernel_is_the_unpadded_prompt(
        monkeypatch):
    """Latent attention at the kernel's widths (keys of 120 + 8 rope over
    values of 128) in a bucket of 512 that the kernel takes (its length
    lowered to 512): prompts of 300 and 129 tokens and two rows of none
    prefill through the grouped-query kernel with each row's true length,
    in the window layers and the full one, and the first token's logits
    and the latent rows at live positions are each prompt's alone, whose
    length takes XLA's attention (`check_padded_prefill`)."""
    from conftest import check_padded_prefill
    from paddle_tpu.kernels import flash_attention as fa

    c = MotifConfig.tiny(layers=3, window=300)
    c.head_dim, c.v_head_dim, c.max_position_embeddings = 128, 128, 2048
    paddle.seed(3)
    m = latent_moe.MotifForCausalLM(c)
    m.eval()
    monkeypatch.setattr(fa, "GQA_MIN_SEQ", 512)
    calls = []
    real = fa.flash_attention_gqa_bshd
    monkeypatch.setattr(fa, "flash_attention_gqa_bshd", lambda *a, **kw:
                        calls.append((kw["window"], kw["lens"] is not None))
                        or real(*a, **kw))
    rng = np.random.default_rng(9)
    check_padded_prefill(m, [rng.integers(0, 96, 300),
                             rng.integers(0, 96, 129)], 4, 512)
    assert calls == [(300, True), (300, True), (None, True)]


# -- the engine ---------------------------------------------------------------


def test_the_engine_keeps_latent_rings_beside_latent_pages(model, cfg):
    """One pool of latent rows a layer: a window layer's ring of ceil(8 /
    8) + 1 = 2 pages a slot, a full layer's pages from the allocator; the
    rings count under `kv_pool_bytes_window`."""
    eng = _engine(model, cfg)
    kinds = [family.kind_of(cfg, i) for i in range(7)]
    assert kinds == ["window", "window", "full", "window", "window",
                     "window", "full"]
    assert eng._rings == tuple(2 if k == "window" else None for k in kinds)
    assert eng.v_pages == []
    for li, kind in enumerate(kinds):
        assert eng.k_pages[li].shape == ((1, 4 * 2, 8, 40) if kind == "window"
                                         else (1, 4 * 16, 8, 40))
    got = eng.kv_pool_bytes()
    assert got == {"kv_pool_bytes_full": 2 * 4 * 16 * 8 * 40 * 4,
                   "kv_pool_bytes_window": 5 * 4 * 2 * 8 * 40 * 4}
    for kind in family.KINDS:
        assert got["kv_pool_bytes_" + kind] == family.pool_bytes(
            cfg, cfg["engine"], kind, itemsize=4)


@pytest.mark.parametrize("lengths, new", [
    ((5, 13, 22, 9), 12),      # inside and past the window, together
    ((30, 3, 44, 17), 40),     # rings that wrap many times, pages grown
])
def test_prefill_then_burst_decode_through_the_engine(model, weights, cfg,
                                                      lengths, new):
    """Logits, not tokens: every served token's reference logit is the
    reference's best, to round-off, for prompts prefilled and then decoded
    in bursts through the full layers' pages and the window layers' rings
    (84 positions over a window of 8 and rings of 16)."""
    eng = _engine(model, cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in lengths]
    rids = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    done = {f.request_id: f.output_ids for f in eng.run()}
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(done[rid])
        assert len(out) == new
        assert _gaps(weights, cfg, prompt, out).max() < TOL
    tokens, _ = model.generate(paddle.to_tensor(prompts[1][None]),
                               max_new_tokens=new)
    np.testing.assert_array_equal(np.asarray(tokens._data)[0], done[rids[1]])
    assert len(eng._free_pages) == eng._n_pages_total


def test_the_burst_counts_the_latent_rings_pages(model, cfg):
    from paddle_tpu.observability import tracing

    eng = _engine(model, cfg)
    eng.add_request(np.arange(20) % cfg["vocab_size"], max_new_tokens=9)
    seen = []
    real = tracing.phase

    def spy(name, **attrs):
        if name == "serving.emit" and "attn_window_pages_live" in attrs:
            seen.append(attrs)
        return real(name, **attrs)

    tracing.phase = spy
    try:
        eng.run()
    finally:
        tracing.phase = real
    assert seen
    for a in seen:
        # one live row: a ring of 2 pages read in each of 5 window layers
        assert a["attn_window_pages_read"] % (5 * 2) == 0
        assert 0 < a["attn_window_pages_live"] <= a["attn_window_pages_read"]
        assert a["attn_window_pages_context"] >= a["attn_window_pages_live"]


@pytest.mark.parametrize("asked", [dict(kv_cache_quant="int8"),
                                   dict(prefix_cache=True),
                                   dict(prefill_chunk=16)])
def test_what_a_mixed_layout_cannot_do_yet_raises(model, cfg, asked):
    with pytest.raises(ValueError, match="not built for a mixed layout"):
        _engine(model, cfg, **asked)


def test_a_ring_of_latent_rows_reads_its_window():
    """`paged_latent_attention_xla` with `first` over a ring as
    `ring_view` hands it: the softmax of the last `window` rows only."""
    rng = np.random.default_rng(6)
    page, ring, window, width, rank = 4, 3, 6, 12, 8
    rows_written = 11
    pool = jnp.zeros((1, ring, page, width), jnp.float32)
    seq = jnp.asarray(rng.normal(size=(1, rows_written, 1, width)),
                      jnp.float32)
    pool = pa.prefill_ring_pool(pool, pa.ring_tail(
        seq, jnp.asarray([rows_written]), ring, page), jnp.asarray([0]),
        jnp.asarray([rows_written]), ring, rows_written)
    q = jnp.asarray(rng.normal(size=(1, 2, width)), jnp.float32)
    tables, read, first = pa.ring_view(jnp.asarray([0]), ring, page,
                                       jnp.asarray([rows_written]), window)
    got = pa.paged_latent_attention_xla(q, pool, tables, read, rank, 0.5,
                                        first=first)
    kept = np.asarray(seq[0, rows_written - window:, 0])
    s = np.asarray(q[0]) @ kept.T * 0.5
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[0]), p @ kept[:, :rank],
                               atol=1e-5)


# -- the experts --------------------------------------------------------------


@pytest.mark.parametrize("n, blocks", [(5, 1), (16, 2)])
def test_the_polynorm_kernels_are_the_dense_products(n, blocks,
                                                     monkeypatch):
    """The hit and grouped kernels' PolyNorm variant, interpreted on the
    CPU, against `share_ffn`: whole-width statistics over one block of the
    width and over two."""
    held, d, f = 8, 128, 256
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(held, d, f)) * 0.1,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(held, f, d)) * 0.1, jnp.float32)
    act = (jnp.asarray(rng.normal(size=(held, 3)) * 0.3 + 0.3, jnp.float32),
           jnp.asarray(rng.normal(size=(held, 1)), jnp.float32), 0.5, 0.5,
           1e-6)
    dense_w = np.zeros((n, held), np.float32)
    for r in range(n):
        for e in rng.choice(held, 2, replace=False):
            dense_w[r, e] = 0.2 + rng.random()
    dense_w = jnp.asarray(dense_w)
    if blocks > 1:
        monkeypatch.setattr(expert_hit, "_WEIGHT_VMEM_BYTES", 6 * d * 128 * 4)
        monkeypatch.setattr(expert_grouped, "_WHOLE_EXPERT_VMEM_BYTES", 0)
    assert expert_hit._block_width(d, f, 4) == f // blocks
    want = expert_share.share_ffn(x, dense_w, w_gate, w_up, w_down, act=act)
    silu = expert_share.share_ffn(x, dense_w, w_gate, w_up, w_down)
    assert np.abs(np.asarray(want - silu)).max() > 0.5
    for got in (expert_hit.hit_ffn(x, dense_w, w_gate, w_up, w_down,
                                   act=act),
                expert_grouped.grouped_ffn(x, dense_w, w_gate, w_up, w_down,
                                           act=act)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5 * np.abs(np.asarray(want)).max())


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: over the 8 ranks of an 8-way deployment (16
    experts, 2 held a rank) the routed parts the shares give, with the
    shared expert counted once, add up to the uncut reference expert
    layer."""
    def share_cfg(**over):
        return tiny_cfg(**dict(dict(
            num_hidden_layers=2, num_experts=2, ep_degree=8), **over))

    every = share_cfg(num_experts=16, ep_degree=1)
    uncut = family.make_weights(every, SEED, "float32")
    pre = "model.layers.1.mlp."
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(19, every["hidden_size"])), jnp.float32)
    st = dict(reference.static_of(every))
    picks, w = reference.route(x, uncut[pre + "experts.gate.weight"], st,
                               "f32")
    names = ("w_gate", "w_up", "w_down", "act_weight", "act_bias")
    shared = reference.ffn(
        x, *(uncut[pre + f"shared_experts.{n}.weight"]
             for n in reference.FFN),
        uncut[pre + "shared_experts.act_fn.weight"],
        uncut[pre + "shared_experts.act_fn.bias"], st, "f32")
    whole = reference.routed_share(
        x, picks, w, [uncut[pre + "experts." + n] for n in names], st,
        "f32") + shared
    total = np.zeros_like(np.asarray(whole))
    for rank in range(8):
        m = family.build_model(share_cfg(ep_rank=rank), SEED)
        layer = m.model.layers[1].mlp
        mine = [uncut[pre + "experts." + n][rank * 2:(rank + 1) * 2]
                for n in names]
        for name, leaf in zip(names, mine):   # this rank's experts
            getattr(layer.experts, name)._rebind(leaf)
        layer.experts.gate.weight._rebind(uncut[pre + "experts.gate.weight"])
        for n in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer.shared_experts, n).weight._rebind(
                uncut[pre + f"shared_experts.{n}.weight"])
        layer.shared_experts.act_fn.weight._rebind(
            uncut[pre + "shared_experts.act_fn.weight"])
        layer.shared_experts.act_fn.bias._rebind(
            uncut[pre + "shared_experts.act_fn.bias"])
        assert layer.experts.first == rank * 2
        with paddle.no_grad():
            routed = np.asarray(layer.experts(paddle.to_tensor(x))._data)
            both = np.asarray(layer(paddle.to_tensor(x))._data)
        np.testing.assert_allclose(both - routed, np.asarray(shared),
                                   atol=2e-6)
        st_rank = dict(st, first=rank * 2)
        want = reference.routed_share(x, picks, w, mine, st_rank, "f32")
        np.testing.assert_allclose(routed, np.asarray(want), atol=2e-6)
        total += routed
    total += np.asarray(shared)   # the shared expert, once
    assert np.abs(total).max() > 1e-3
    np.testing.assert_allclose(total, np.asarray(whole), atol=5e-6)
