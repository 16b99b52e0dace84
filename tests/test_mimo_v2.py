"""MiMo-V2.5's shape (`model_type` mimo_v2) in the one stack of
models/latent_moe.py: window layers with a sink in the softmax beside full
layers of ANOTHER kv-head count, keys wider than values, rope on the first
dims of a head with a base a kind, scaled values, routed experts with no
shared one; the serving engine's per-layer cache layouts; the decode and
prefill kernels' key / value widths and sink, at a small size on the CPU,
against the plain float32 reference (benchmark/reference/mimo_v2.py) on the
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
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402
from paddle_tpu.kernels import paged_attention as pa  # noqa: E402
from paddle_tpu.models import (MiMoV2Config, MiMoV2ForCausalLM,  # noqa: E402
                               latent_moe)
from paddle_tpu.observability import tracing  # noqa: E402

from benchmark.families import mimo_v2 as family  # noqa: E402
from benchmark.reference import mimo_v2 as reference  # noqa: E402

SEED = 2033
PATTERN = [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]


def tiny_cfg(**over):
    with open(os.path.join(REPO, "tests", "benchmark_suite", "data",
                           "configs", "tiny-mimo-v2.json")) as f:
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
    c = MiMoV2Config.tiny()
    kinds = c.layer_kinds()
    assert [k[0] for k in kinds] == [
        "sink_window" if w else "sink_full" for w in PATTERN]
    assert [k[1] for k in kinds] == ["dense"] + ["routed"] * 11
    assert {k[2] for k in kinds} == {"pre"}
    # the published rule: 9 full layers of 48, at 0 and then every sixth
    real = MiMoV2Config()
    assert [i for i, w in enumerate(real.hybrid_layer_pattern)
            if not w] == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert real.moe_layer_freq == (0,) + (1,) * 47
    full, window = real.attention(False), real.attention(True)
    assert (full["kv_heads"], window["kv_heads"]) == (4, 8)
    assert full["key_dim"] == window["key_dim"] == 192
    assert full["value_dim"] == window["value_dim"] == 128
    assert full["rope_dims"] == window["rope_dims"] == 64
    assert (full["rope_theta"], window["rope_theta"]) == (1e7, 1e4)
    assert (full["window"], window["window"]) == (None, 128)
    assert (full["sink"], window["sink"]) == (False, True)
    assert real.moe_spec() == dict(
        width=2048, num_experts=256, top_k=8, scale=1.0, norm_topk=True,
        pick_bias=True, shared=0)
    with pytest.raises(ValueError, match="hybrid_layer_pattern must give"):
        MiMoV2Config(num_hidden_layers=3, hybrid_layer_pattern=(0, 1))
    with pytest.raises(ValueError, match="publishes no shared expert"):
        MiMoV2Config(n_shared_experts=1)
    with pytest.raises(NotImplementedError, match="sink in a full layer"):
        MiMoV2ForCausalLM(MiMoV2Config.tiny(layers=1).__class__(
            **dict(MiMoV2Config.tiny(layers=1).__dict__,
                   add_full_attention_sink_bias=True)))


def test_it_is_the_one_stack_with_other_kinds(model):
    assert isinstance(model, latent_moe.LatentMoEForCausalLM)
    assert type(model.model) is latent_moe.LatentMoEModel
    layers = model.model.layers
    assert all(type(layer) is latent_moe.LatentMoEDecoderLayer
               for layer in layers)
    assert all(type(layer.self_attn) is latent_moe.SinkGQAttention
               for layer in layers)
    assert [layer.self_attn.window for layer in layers] \
        == [16 if w else None for w in PATTERN]
    # a layer's layout is its own: 1 kv head in a full layer, 2 in a window
    # layer, and in both a key of 24 numbers over a value of 16
    full, window = ((1, 24), (1, 16)), ((2, 24), (2, 16))
    assert model.kv_cache_layouts() == tuple(
        window if w else full for w in PATTERN)
    assert model.kv_cache_windows() == tuple(
        16 if w else None for w in PATTERN)
    caches = model.init_kv_caches(3, 5)
    assert [c.shape for c in caches[0]] == [(3, 5, 1, 24), (3, 5, 1, 16)]
    assert [c.shape for c in caches[1]] == [(3, 5, 2, 24), (3, 5, 2, 16)]
    # no shared expert, no post-sublayer norm, a sink in the window layers
    names = {n for n, _ in model.named_parameters()}
    assert not any("shared_experts" in n or "post_" in n for n in names)
    assert "model.layers.1.self_attn.attention_sink_bias" in names
    assert "model.layers.0.self_attn.attention_sink_bias" not in names
    assert layers[1].mlp.shared_experts is None


def test_forward_agrees_with_the_reference(model, weights, cfg):
    """Contexts several windows long: 70 positions over a window of 16."""
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 70))
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    assert got.dtype == np.float32
    for row in range(2):
        np.testing.assert_allclose(got[row], _ref_logits(weights, cfg,
                                                         ids[row]),
                                   atol=2e-4)


@pytest.mark.parametrize("change, early", [
    (dict(sliding_window=1 << 20), True),       # the window
    (dict(add_swa_attention_sink_bias=False), False),   # the sink
    (dict(attention_value_scale=1.0), False),   # the value scale
    (dict(partial_rotary_factor=1.0), False),   # rope on a third of a head
    (dict(swa_rope_theta=10000000), False),     # a base a kind
    (dict(rope_theta=10000), False),
])
def test_each_mechanism_is_live_in_the_reference(weights, cfg, change,
                                                 early):
    """What the reference is not when a mechanism is dropped or changed:
    it reads wide (and a window as long as the context changes nothing
    inside the first window)."""
    ids = np.random.default_rng(5).integers(0, cfg["vocab_size"], 60)
    want = _ref_logits(weights, cfg, ids)
    w = weights if change.get("add_swa_attention_sink_bias", True) else {
        k: v for k, v in weights.items()
        if not k.endswith("attention_sink_bias")}
    other = _ref_logits(w, dict(cfg, **change), ids)
    if early:
        assert np.abs(other[:16] - want[:16]).max() < 1e-5
    # (more than the 2e-4 the program is held to the reference by)
    assert np.abs(other[40:] - want[40:]).max() > 4e-4


@pytest.mark.parametrize("block_bytes", [1 << 30, 4096])
def test_blocked_attention_is_whole_attention(block_bytes, monkeypatch):
    """`gqa_attention` with a key wider than a value, with and without a
    window and a sink, whole and in blocks of queries."""
    monkeypatch.setattr(latent_moe, "SCORE_BLOCK_BYTES", block_bytes)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 32, 2, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    for window, s in ((None, None), (5, None), (5, sink), (None, sink)):
        got = latent_moe.gqa_attention(q, k, v, window, sink=s)
        assert got.shape == (2, 32, 4 * 16)
        want = np.stack([np.asarray(reference.attend(q[b], k[b], v[b],
                                                     window, s))
                         for b in range(2)])
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


# -- the engine ---------------------------------------------------------------


def test_the_engine_shapes_each_layers_pools_from_that_layers_layout(
        model, cfg):
    """1 and 2 kv heads in ONE engine, keys of 24 over values of 16: a full
    layer has the allocator's pages, a window layer a ring a slot."""
    eng = _engine(model, cfg)
    ring = pa.ring_pages(16, 8)
    assert ring == 3 and eng._rings == tuple(
        3 if w else None for w in PATTERN)
    assert pa.ring_pages(128, 256) == 2
    for li, w in enumerate(PATTERN):
        k, v = eng.k_pages[li].shape, eng.v_pages[li].shape
        if w:
            assert (k, v) == ((2, 4 * 3, 8, 24), (2, 4 * 3, 8, 16))
        else:
            assert (k, v) == ((1, 4 * 16, 8, 24), (1, 4 * 16, 8, 16))
    # the allocator is the full layers': a ring takes nothing from it
    assert eng._n_pages_total == 4 * 16 == len(eng._free_pages)
    # the pools' bytes by kind, as the family's table says them
    got = eng.kv_pool_bytes()
    assert got == {
        "kv_pool_bytes_full": 3 * 4 * 16 * 8 * 1 * (24 + 16) * 4,
        "kv_pool_bytes_window": 9 * 4 * 3 * 8 * 2 * (24 + 16) * 4}
    for kind in family.KINDS:
        assert got["kv_pool_bytes_" + kind] == family.pool_bytes(
            cfg, cfg["engine"], kind, itemsize=4)


@pytest.mark.parametrize("lengths, new", [
    ((5, 13, 22, 9), 14),      # inside the window, admitted together
    ((40, 3, 70, 17), 45),     # past it, across ring and page boundaries
])
def test_prefill_then_burst_decode_through_the_engine(model, weights, cfg,
                                                      lengths, new):
    """Logits, not tokens: every served token's reference logit is the
    reference's best, to round-off, for prompts that are admitted together,
    outlive many bursts and reach contexts several windows long (115
    positions over a window of 16 and rings of 24, which wrap four times)."""
    eng = _engine(model, cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in lengths]
    rids = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    done = {f.request_id: f.output_ids for f in eng.run()}
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(done[rid])
        assert len(out) == new
        assert _gaps(weights, cfg, prompt, out).max() < 2e-4
    # the engine's streams are generate()'s (dense caches of every position)
    tokens, _ = model.generate(paddle.to_tensor(prompts[1][None]),
                               max_new_tokens=new)
    np.testing.assert_array_equal(np.asarray(tokens._data)[0], done[rids[1]])
    assert len(eng._free_pages) == eng._n_pages_total


def test_a_prompt_longer_than_the_window_is_prefilled_then_decoded(
        model, weights, cfg):
    """100 positions: the ring keeps the last three pages of them, a slot
    that served a long request serves a short one next, and both agree
    with the reference."""
    eng = _engine(model, cfg, max_batch=1)
    rng = np.random.default_rng(7)
    for n, new in ((100, 20), (6, 30), (41, 9)):
        prompt = rng.integers(0, cfg["vocab_size"], n)
        rid = eng.add_request(prompt, max_new_tokens=new)
        (done,) = eng.run()
        assert done.request_id == rid
        assert _gaps(weights, cfg, prompt,
                     np.asarray(done.output_ids)).max() < 2e-4


def test_a_key_of_192_is_stored_256_wide_and_scored_by_192():
    """The published head sizes (keys 192, of which 64 roped, over values
    128) at a tiny hidden size: the pools store a key a whole number of
    lane tiles wide, zeros behind it, and prefill, the decode step over
    pages and rings, and `generate` over the dense cache agree with the
    reference all the same."""
    assert latent_moe._pool_width(192) == family.pool_width(192) == 256
    assert [latent_moe._pool_width(w) for w in (24, 128, 256, 320)] \
        == [24, 128, 256, 384]
    c = tiny_cfg(hidden_size=32, intermediate_size=64, num_hidden_layers=3,
                 hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1],
                 num_attention_heads=2, swa_num_attention_heads=2,
                 head_dim=192, swa_head_dim=192, v_head_dim=128,
                 swa_v_head_dim=128)
    w = family.make_weights(c, SEED, "float32")
    m = family.build_model(c, SEED)
    assert m.kv_cache_layouts() == (((1, 256), (1, 128)),) \
        + (((2, 256), (2, 128)),) * 2
    eng = _engine(m, c, max_batch=2)
    assert eng.k_pages[0].shape[-1] == eng.k_pages[1].shape[-1] == 256
    assert eng.v_pages[1].shape == (2, 2 * 3, 8, 128)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n) for n in (37, 9)]
    rids = [eng.add_request(p, max_new_tokens=30) for p in prompts]
    done = {f.request_id: f.output_ids for f in eng.run()}
    for rid, prompt in zip(rids, prompts):
        assert _gaps(w, c, prompt, np.asarray(done[rid])).max() < 2e-4
    tokens, _ = m.generate(paddle.to_tensor(prompts[0][None]),
                           max_new_tokens=30)
    np.testing.assert_array_equal(np.asarray(tokens._data)[0], done[rids[0]])
    # what lies behind a key in its pool is zeros
    assert not np.asarray(eng.k_pages[1])[..., 192:].any()
    assert np.asarray(eng.k_pages[1])[..., :192].any()


@pytest.mark.parametrize("path", ["dense", "grouped"])
def test_a_padded_prefill_is_the_unpadded_prefill(model, cfg, path,
                                                  monkeypatch):
    """Padded positions, and a padded row (`true_lens` 0), make no pair in
    an expert layer on either form: the counts are the unpadded prompts'
    own, and the first token's logits and the K/V at live positions are
    what each prompt gives alone."""
    from conftest import check_padded_prefill
    from paddle_tpu.kernels import expert_grouped

    monkeypatch.setattr(expert_grouped, "use_grouped_path",
                        lambda *a: path == "grouped")
    check_padded_prefill(
        model, [np.arange(21) % 90, (np.arange(6) * 7 + 3) % 90], 4, 24)


def test_the_burst_counts_each_kinds_pages(model, cfg):
    """What rides out on `serving.emit`: pages the window layers stream,
    pages holding a position a row still sees, pages a layer holding every
    position would read; the full layers keep `attn_pages_read`."""
    eng = _engine(model, cfg, max_batch=2)
    seen = []
    real = tracing.phase

    def phase(name, **attrs):
        if name == "serving.emit" and "attn_window_pages_live" in attrs:
            seen.append(attrs)
        return real(name, **attrs)

    eng.add_request(np.arange(50) % 90, max_new_tokens=9)
    import paddle_tpu.inference.serving as serving
    orig, serving._trace.phase = serving._trace.phase, phase
    try:
        eng.run()
    finally:
        serving._trace.phase = orig
    first = seen[0]
    # one live row at contexts 51..54 over a burst of 4: a window of 16 at
    # pages of 8 spans 3 pages (2 when it starts on a page boundary)
    window_layers, full_layers, steps = 9, 3, 4
    live = sum(-(-n // 8) - max(n - 16, 0) // 8 for n in range(51, 55))
    assert first["attn_window_pages_live"] == window_layers * live
    assert first["attn_window_pages_context"] == window_layers * sum(
        -(-n // 8) for n in range(51, 55))
    # the CPU's dense gather maps the whole ring of every live row
    assert first["attn_window_pages_read"] == window_layers * steps * 3
    assert first["attn_pages_read"] == full_layers * sum(
        -(-n // 8) for n in range(51, 55))
    assert first["experts_held"] == 11 * steps * 4 and "expert_pairs" in first


@pytest.mark.parametrize("asked, sentence", [
    (dict(kv_cache_quant="int8"), "kv_cache_quant='int8' is not built"),
    (dict(prefix_cache=1), "prefix_cache=1 is not built"),
    (dict(spec_decode=4), "spec_decode=4 is not built"),
    (dict(spec_decode=4, draft_model="a model"), "is not built"),
    (dict(prefill_chunk=16), "prefill_chunk=16 is not built"),
])
def test_what_a_mixed_layout_cannot_do_yet_raises_at_construction(
        model, cfg, asked, sentence):
    with pytest.raises(ValueError, match=sentence + ".*mixed layout"):
        _engine(model, cfg, **asked)


def test_layers_that_differ_alone_make_a_layout_mixed():
    """No ring at all (every layer full), but 1 kv head in some layers and
    2 in others: still a layer's own write program, still refused what a
    mixed layout cannot do, and still the reference's logits."""
    c = tiny_cfg(num_hidden_layers=3, hybrid_layer_pattern=[0, 1, 0],
                 moe_layer_freq=[0, 1, 1], sliding_window=512,
                 sliding_window_size=512, add_swa_attention_sink_bias=False)
    m = family.build_model(c, SEED)
    w = family.make_weights(c, SEED, "float32")
    # (a window as long as the engine's sequences is still a ring: make
    # the layers differ with no window at all)
    for layer in m.model.layers:
        layer.self_attn.window = None
    eng = _engine(m, c, max_batch=2)
    assert not eng._has_rings and eng._layer_writes and eng._mixed_layout
    assert [p.shape[0] for p in eng.k_pages] == [1, 2, 1]
    with pytest.raises(ValueError, match="is not built.*mixed layout"):
        _engine(m, c, prefix_cache=1)
    prompt = np.random.default_rng(11).integers(0, 96, 19)
    rid = eng.add_request(prompt, max_new_tokens=12)
    (done,) = eng.run()
    assert done.request_id == rid
    assert _gaps(w, dict(c, sliding_window=1 << 20), prompt,
                 np.asarray(done.output_ids)).max() < 2e-4


def test_a_mixed_layout_is_not_sharded_and_not_handed_off(model, cfg, mesh8):
    with pytest.raises(ValueError, match="cannot be sharded over tp=4"):
        _engine(model, cfg, mesh=mesh8)
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


def test_pools_of_unequal_counts_are_refused(model, cfg, monkeypatch):
    monkeypatch.setattr(
        type(model), "kv_cache_layouts",
        lambda self: (((1, 8),),) + (((1, 8), (1, 8)),) * 11)
    with pytest.raises(ValueError, match="as many pools"):
        _engine(model, cfg)


# -- the kernels --------------------------------------------------------------


def _softmax_by_hand(q, k, v, lens, first, sink, scale):
    """[b, heads x d_v]: each row's softmax over positions first[b] ..
    lens[b] - 1 with exp(sink_h) in the denominator, numpy float64."""
    b, h, _ = q.shape
    kv = k.shape[2]
    out = np.zeros((b, h, v.shape[-1]))
    for r in range(b):
        for n in range(h):
            lo, hi = int(first[r]), int(lens[r])
            if hi <= lo:
                continue
            kk, vv = k[r, lo:hi, n // (h // kv)], v[r, lo:hi, n // (h // kv)]
            s = (kk @ q[r, n]) * scale
            top = max(s.max(), sink[n]) if sink is not None else s.max()
            e = np.exp(s - top)
            den = e.sum() + (np.exp(sink[n] - top) if sink is not None else 0)
            out[r, n] = (e / den) @ vv
    return out.reshape(b, -1)


def _paged_case(rng, lens, page, pages, heads=4, kv=2, d_k=24, d_v=16):
    """Dense K / V of `lens` positions a row beside pools that hold them
    through plain block tables (row b owns pages b * pages ..)."""
    b, t = len(lens), pages * page
    k = rng.normal(size=(b, t, kv, d_k)).astype(np.float32)
    v = rng.normal(size=(b, t, kv, d_v)).astype(np.float32)
    kp = jnp.asarray(k.reshape(b, pages, page, kv, d_k).transpose(
        3, 0, 1, 2, 4).reshape(kv, b * pages, page, d_k))
    vp = jnp.asarray(v.reshape(b, pages, page, kv, d_v).transpose(
        3, 0, 1, 2, 4).reshape(kv, b * pages, page, d_v))
    tables = jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    q = rng.normal(size=(b, heads, d_k)).astype(np.float32)
    return q, k, v, kp, vp, tables


@pytest.mark.parametrize("attend", [pa.paged_attention,
                                    pa.paged_attention_xla])
@pytest.mark.parametrize("with_sink", [False, True])
def test_decode_attention_with_a_key_wider_than_a_value_and_a_sink(
        attend, with_sink):
    """The page-grid kernel's body in interpret mode and the XLA gather,
    keys of 24 over values of 16, against softmaxes made by hand: rows
    inside a window, past it and empty; a head whose sink is LARGE (its
    output all but vanishes), one whose sink is all but -inf (the plain
    softmax), and two in between."""
    rng = np.random.default_rng(3)
    lens, first = [5, 16, 17, 24, 40, 0], [0, 0, 1, 8, 24, 0]
    q, k, v, kp, vp, tables = _paged_case(rng, lens, 8, 5)
    sink = np.array([9.0, -1e9, 0.3, -0.7], np.float32) if with_sink \
        else None
    kw = {} if sink is None else {"sink": jnp.asarray(sink)}
    got = attend(jnp.asarray(q), kp, vp, tables, jnp.asarray(lens),
                 first=jnp.asarray(first), **kw)
    assert got.shape == (6, 4, 16)
    want = _softmax_by_hand(q, k, v, lens, first, sink, 24 ** -0.5)
    np.testing.assert_allclose(np.asarray(got).reshape(6, -1), want,
                               atol=2e-5)
    assert not np.asarray(got)[5].any()      # a row that reads nothing
    if with_sink:
        plain = _softmax_by_hand(q, k, v, lens, first, None, 24 ** -0.5)
        got = np.asarray(got)
        assert np.abs(got[0, 0]).max() < 0.05 * np.abs(
            plain.reshape(6, 4, 16)[0, 0]).max()
        np.testing.assert_allclose(got[:, 1], plain.reshape(6, 4, 16)[:, 1],
                                   atol=2e-5)
    # without a window: the whole context, a scale handed in
    got = attend(jnp.asarray(q), kp, vp, tables, jnp.asarray(lens),
                 scale=0.11, **kw)
    want = _softmax_by_hand(q, k, v, lens, [0] * 6, sink, 0.11)
    live = np.asarray(lens) > 0   # (an empty row's output is discarded)
    np.testing.assert_allclose(np.asarray(got).reshape(6, -1)[live],
                               want[live], atol=2e-5)


def test_the_cache_writes_take_each_pools_own_width():
    """A token's key lands in a pool 24 wide and its value in one 16 wide,
    through block tables and through rings; a prompt's likewise."""
    rng = np.random.default_rng(8)
    kp, vp = jnp.zeros((2, 6, 4, 24)), jnp.zeros((2, 6, 4, 16))
    k = jnp.asarray(rng.normal(size=(2, 2, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 16)), jnp.float32)
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    k2, v2 = pa.update_paged_kv_cache(kp, vp, k, v, tables,
                                      jnp.asarray([5, 0]))
    np.testing.assert_array_equal(np.asarray(k2)[:, 1, 1], np.asarray(k[0]))
    np.testing.assert_array_equal(np.asarray(v2)[:, 3, 0], np.asarray(v[1]))
    k3, v3 = pa.update_ring_kv_cache(kp, vp, k, v, jnp.arange(2),
                                     jnp.asarray([13, 2]))
    np.testing.assert_array_equal(np.asarray(k3)[:, 0, 1], np.asarray(k[0]))
    np.testing.assert_array_equal(np.asarray(v3)[:, 3, 2], np.asarray(v[1]))
    ks = jnp.asarray(rng.normal(size=(2, 8, 2, 24)), jnp.float32)
    vs = jnp.asarray(rng.normal(size=(2, 8, 2, 16)), jnp.float32)
    k4, v4 = pa.prefill_paged_kv_cache(kp, vp, ks, vs, tables,
                                       jnp.asarray([7, 3]))
    np.testing.assert_array_equal(np.asarray(k4)[0, 1, 2],
                                  np.asarray(ks)[0, 6, 0])
    np.testing.assert_array_equal(np.asarray(v4)[1, 3, 2],
                                  np.asarray(vs)[1, 2, 1])
    assert not np.asarray(v4)[:, 3, 3].any()
    lens = jnp.asarray([7, 3])
    k5, v5 = pa.prefill_ring_kv_cache(
        kp, vp, pa.ring_tail(ks, lens, 3, 4), pa.ring_tail(vs, lens, 3, 4),
        jnp.arange(2), lens, 3, 8)
    np.testing.assert_array_equal(np.asarray(k5)[1, 1, 1],
                                  np.asarray(ks)[0, 5, 1])
    np.testing.assert_array_equal(np.asarray(v5)[0, 3, 2],
                                  np.asarray(vs)[1, 2, 0])


@pytest.mark.parametrize("window, with_sink, kv, lens", [
    (None, False, 1, None), (128, True, 2, None), (128, False, 2, None),
    (200, True, 2, None), (None, True, 4, None),
    (None, True, 4, (1024, 0, 511, 1, 513)),
    (128, True, 8, (1, 127, 0, 1024, 129))],
    ids=["None-False-1", "128-True-2", "128-False-2", "200-True-2",
         "None-True-4", "full-lens", "window-lens"])
def test_the_prefill_kernel_with_a_wide_key_a_window_and_a_sink(
        window, with_sink, kv, lens):
    """`flash_attention_gqa_bshd` in interpret mode at keys of 192 over
    values of 128 against the reference's attention: causal over 16 query
    heads a kv head (two blocks of 8), a window of 128 walked two key
    blocks of 128 a query block, its edge at i - j = 127 / 128, a sink a
    head. With `lens`, MiMo's 64 query heads on 4 (full) and on 8 (a window
    of 128) over rows of 0, 1, a key block's edge - 1 and + 1 and every
    token in one batch, the padding NaN (`check_gqa_lengths`)."""
    rng = np.random.default_rng(6)
    if lens is not None:
        from conftest import check_gqa_lengths

        s, h, b = 1024, 64, len(lens)
        q = jnp.asarray(rng.normal(size=(b, s, h, 192)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, kv, 192)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, kv, 128)), jnp.float32)
        sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
        check_gqa_lengths(lambda *a: reference.attend(*a, window, sink), q,
                          k, v, lens, 3e-5, window=window, sink=sink)
        return
    s, h = 1024, 16 if kv == 1 else 8
    q = jnp.asarray(rng.normal(size=(1, s, h, 192)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, kv, 192)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, kv, 128)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32) if with_sink \
        else None
    assert fa.use_gqa_flash(s, 192, 128) and not fa.use_gqa_flash(s, 96)
    got = fa.flash_attention_gqa_bshd(q, k, v, window=window, sink=sink)
    assert got.shape == (1, s, h, 128)
    want = reference.attend(q[0], k[0], v[0], window, sink)
    np.testing.assert_allclose(np.asarray(got)[0].reshape(s, -1),
                               np.asarray(want), atol=3e-5)
    if window == 128:
        # the edge: with the key at i - 127 made huge, query i follows it;
        # the key at i - 128 is not seen whatever it holds
        i = 700
        big = k.at[0, i - 127].multiply(40.0).at[0, i - 128].multiply(-90.0)
        edge = fa.flash_attention_gqa_bshd(q, big, v, window=window,
                                           sink=sink)
        want = reference.attend(q[0], big[0], v[0], window, sink)
        np.testing.assert_allclose(np.asarray(edge)[0, i].reshape(-1),
                                   np.asarray(want)[i], atol=3e-5)
        far = fa.flash_attention_gqa_bshd(
            q, k.at[0, i - 128].multiply(-90.0), v, window=window, sink=sink)
        np.testing.assert_allclose(np.asarray(far)[0, i],
                                   np.asarray(got)[0, i], atol=1e-6)


def test_the_tiling_of_the_prefill_kernel_is_made_from_the_shape():
    # (query heads a block, key block)
    assert fa._gqa_tiling(8, None) == (8, 512)
    assert fa._gqa_tiling(8, 2048) == (8, 512)   # Trinity's
    assert fa._gqa_tiling(8, 512) == (8, 512)
    assert fa._gqa_tiling(8, 128) == (8, 128)    # MiMo's window
    assert fa._gqa_tiling(16, None) == (8, 512)  # MiMo's full
    assert fa._gqa_tiling(8, 200) == (8, 256)
    assert fa._gqa_tiling(4, 500) == (4, 512)
    assert fa._gqa_tiling(3, None) == (3, 512)
    assert fa._gqa_tiling(20, None) == (5, 512)  # Jamba's: 4 blocks of 5
    # a window of 128: query block i sees key blocks i - 1 and i alone
    lo, hi = fa._gqa_key_blocks(7, 128, 128, 128)
    assert (int(lo), int(hi)) == (6, 7)
    # the grid's steps: the causal triangle of 512-key blocks, 4 query
    # blocks a key block (1 + 2 + 3 + 4 steps for each 4), and a window of
    # 128's band of two key blocks a query block (one for the first)
    query, key, first = fa._gqa_steps(2048, 512, None)
    assert query.size == 4 * (1 + 2 + 3 + 4) and first[-1] == query.size
    assert list(zip(query[:7], key[:7])) == [
        (0, 0), (1, 0), (2, 0), (3, 0), (4, 0), (4, 1), (5, 0)]
    assert list(first[:6]) == [0, 1, 2, 3, 4, 6]
    query, key, first = fa._gqa_steps(16384, 128, 128)
    assert query.size == 2 * 128 - 1
    assert list(key[first[7]:first[8]]) == [6, 7]
    # a window of 2,048: query block 40 (positions 5,120 ..) sees keys
    # from 3,073 on, key blocks 6 to 10
    query, key, first = fa._gqa_steps(8192, 512, 2048)
    assert list(key[first[40]:first[41]]) == [6, 7, 8, 9, 10]


def test_a_prefill_from_the_kernels_lengths_on_takes_the_kernel(monkeypatch):
    """The mixer asks `use_gqa_flash`; from its length on the prefill's
    attention is the kernel's (each kind's window and sink handed to it),
    and the logits are the XLA path's."""
    c = MiMoV2Config.tiny(layers=2)
    c.head_dim = c.swa_head_dim = 192
    c.v_head_dim = c.swa_v_head_dim = 128
    c.hidden_size, c.num_attention_heads, c.swa_num_attention_heads = 64, 2, 2
    c.sliding_window, c.max_position_embeddings = 300, 2048
    paddle.seed(3)
    m = MiMoV2ForCausalLM(c)
    m.eval()
    sink = m.model.layers[1].self_attn.attention_sink_bias
    sink._rebind(jnp.asarray([0.4, -0.3], sink._data.dtype))
    ids = paddle.to_tensor(np.random.default_rng(8).integers(0, 96,
                                                             (1, 1024)))
    monkeypatch.setattr(fa, "GQA_MIN_SEQ", 2048)
    calls = []
    real = fa.flash_attention_gqa_bshd
    monkeypatch.setattr(fa, "flash_attention_gqa_bshd", lambda *a, **kw:
                        calls.append((kw["window"], "sink" in kw))
                        or real(*a, **kw))
    with paddle.no_grad():
        dense = np.asarray(m(ids)._data)
        assert not calls
        monkeypatch.setattr(fa, "GQA_MIN_SEQ", 1024)
        flash = np.asarray(m(ids)._data)
    assert calls == [(None, False), (300, True)]
    np.testing.assert_allclose(flash, dense, atol=2e-4)


def test_a_padded_prefill_through_the_kernel_is_the_unpadded_prompt(
        monkeypatch):
    """A bucket of 512 that the kernel takes (its length lowered to 512):
    prompts of 300 and 129 tokens and two rows of none prefill through the
    grouped-query kernel with each row's true length, in the full layer
    and in the window layer with its sink, and the first token's logits
    and the K/V at live positions are each prompt's alone, whose length
    takes XLA's attention (`check_padded_prefill`)."""
    from conftest import check_padded_prefill

    c = MiMoV2Config.tiny(layers=2)
    c.head_dim = c.swa_head_dim = 192
    c.v_head_dim = c.swa_v_head_dim = 128
    c.hidden_size, c.num_attention_heads, c.swa_num_attention_heads = 64, 2, 2
    c.sliding_window, c.max_position_embeddings = 300, 2048
    paddle.seed(3)
    m = MiMoV2ForCausalLM(c)
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
    assert calls == [(None, True), (300, True)]


# -- the expert layer ---------------------------------------------------------


def test_the_correction_bias_moves_the_pick_and_not_the_weights(cfg,
                                                                weights):
    from paddle_tpu.incubate.distributed.models.moe import expert_share

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(11, cfg["hidden_size"])), jnp.float32)
    pre = "model.layers.2.mlp.experts.gate."
    w_r = weights[pre + "weight"]
    bias = jnp.zeros((16,)).at[5].set(10.0)
    picks, w = expert_share.sigmoid_topk(x, w_r, 4, scale=1.0, bias=bias)
    assert (np.asarray(picks) == 5).any(axis=-1).all()
    scores = jax.nn.sigmoid(x @ w_r)
    top = np.take_along_axis(np.asarray(scores), np.asarray(picks), -1)
    np.testing.assert_allclose(np.asarray(w),
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    # and the reference's route is the same function
    st = dict(reference.static_of(cfg))
    rp, rw = reference.route(x, w_r, bias, st, "f32")
    np.testing.assert_array_equal(np.sort(np.asarray(rp), -1),
                                  np.sort(np.asarray(picks), -1))
    np.testing.assert_allclose(np.sort(np.asarray(rw), -1),
                               np.sort(np.asarray(w), -1), rtol=1e-5)
    # the seeded bias and the seeded sinks are live
    seeded = weights[pre + "expert_bias"]
    assert 0 < float(jnp.abs(seeded).max()) < 0.06
    plain, _ = expert_share.sigmoid_topk(x * 0.05, w_r, 4)
    moved, _ = expert_share.sigmoid_topk(x * 0.05, w_r, 4, bias=seeded)
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(moved), -1)).any()
    sinks = np.asarray(weights["model.layers.1.self_attn."
                               "attention_sink_bias"])
    assert sinks.shape == (4,) and 0 < np.abs(sinks).max() < 0.6


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """model-configs section 4: over the 16 ranks of a 16-way deployment
    (32 experts, 2 held a rank) the routed parts that the shares give add
    up to what the uncut reference gives for the whole expert layer (there
    is no shared expert to count once)."""
    def share_cfg(**over):
        return tiny_cfg(**dict(dict(
            num_hidden_layers=2, hybrid_layer_pattern=[0, 1],
            moe_layer_freq=[0, 1], router_experts=32, n_routed_experts=2,
            ep_degree=16), **over))

    every = share_cfg(n_routed_experts=32, ep_degree=1)
    uncut = family.make_weights(every, SEED, "float32")
    pre = "model.layers.1.mlp."
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(19, every["hidden_size"])), jnp.float32)
    st = dict(reference.static_of(every))
    picks, w = reference.route(x, uncut[pre + "experts.gate.weight"],
                               uncut[pre + "experts.gate.expert_bias"], st,
                               "f32")
    names = ("w_gate", "w_up", "w_down")
    whole = reference.routed_share(
        x, picks, w, *(uncut[pre + "experts." + n] for n in names), 0, "f32")
    total = np.zeros_like(np.asarray(whole))
    for rank in range(16):
        m = family.build_model(share_cfg(ep_rank=rank), SEED)
        layer = m.model.layers[1].mlp
        mine = [uncut[pre + "experts." + n][rank * 2:(rank + 1) * 2]
                for n in names]
        for name, leaf in zip(names, mine):   # this rank's experts
            getattr(layer.experts, name)._rebind(leaf)
        layer.experts.gate.weight._rebind(uncut[pre + "experts.gate.weight"])
        layer.experts.gate.expert_bias._rebind(
            uncut[pre + "experts.gate.expert_bias"])
        assert layer.experts.first == rank * 2
        with paddle.no_grad():
            routed = np.asarray(layer(paddle.to_tensor(x))._data)
        want = reference.routed_share(x, picks, w, *mine, rank * 2, "f32")
        np.testing.assert_allclose(routed, np.asarray(want), atol=1e-6)
        total += routed
    assert np.abs(total).max() > 1e-3
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-6)
