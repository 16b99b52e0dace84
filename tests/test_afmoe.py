"""Trinity-Mini's shape (`model_type` afmoe) in the one stack of
models/latent_moe.py: window and full gated grouped-query layers, the
serving engine's mixed cache layout (a ring of pages a slot for a window
layer, the allocator's pages for a full one), the windowed decode and
prefill kernels and the expert layer's pick bias, at a small size on the
CPU, against the plain float32 reference (benchmark/reference/afmoe.py) on
the family's seeded weights."""
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
from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM,  # noqa: E402
                               latent_moe)
from paddle_tpu.observability import tracing  # noqa: E402

from benchmark.families import afmoe as family  # noqa: E402
from benchmark.reference import afmoe as reference  # noqa: E402

SEED = 2031


def tiny_cfg(**over):
    with open(os.path.join(REPO, "tests", "benchmark_suite", "data",
                           "configs", "tiny-afmoe.json")) as f:
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
    c = AfmoeConfig.tiny()
    kinds = c.layer_kinds()
    assert [k[0] for k in kinds] == (["gqa_window"] * 3 + ["gqa_full"]) * 2 \
        + ["gqa_window"]
    assert [k[1] for k in kinds] == ["dense"] + ["routed+shared"] * 8
    assert {k[2] for k in kinds} == {"sandwich"}
    # the published rule: layer i is full iff (i + 1) % 4 == 0
    real = AfmoeConfig()
    assert [i for i, t in enumerate(real.layer_types)
            if t == "full_attention"] == [3, 7, 11, 15, 19, 23, 27, 31]
    assert real.embed_scale == pytest.approx(2048 ** 0.5)
    with pytest.raises(ValueError, match="layer_types must name"):
        AfmoeConfig(num_hidden_layers=3, layer_types=("full_attention",))


def test_it_is_the_one_stack_with_other_kinds(model):
    assert isinstance(model, latent_moe.LatentMoEForCausalLM)
    assert type(model.model) is latent_moe.LatentMoEModel
    layers = model.model.layers
    assert all(type(layer) is latent_moe.LatentMoEDecoderLayer
               for layer in layers)
    assert [layer.self_attn.window for layer in layers] \
        == [16, 16, 16, None, 16, 16, 16, None, 16]
    assert model.kv_cache_layouts() == (((2, 16), (2, 16)),) * 9
    assert model.kv_cache_windows() == (16, 16, 16, None) * 2 + (16,)


def test_forward_agrees_with_the_reference(model, weights, cfg):
    """Contexts several windows long: 70 positions over a window of 16."""
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 70))
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    assert got.dtype == np.float32
    for row in range(2):
        np.testing.assert_allclose(got[row], _ref_logits(weights, cfg,
                                                         ids[row]),
                                   atol=2e-4)


def test_the_window_and_the_missing_positions_are_live(model, weights, cfg):
    """What the reference is not when a mechanism is dropped: a window
    layer that saw everything, or a full layer that roped, reads wide."""
    ids = np.random.default_rng(5).integers(0, cfg["vocab_size"], 60)
    want = _ref_logits(weights, cfg, ids)
    no_window = _ref_logits(weights, dict(cfg, sliding_window=1 << 20), ids)
    assert np.abs(no_window[:16] - want[:16]).max() < 1e-5
    assert np.abs(no_window[40:] - want[40:]).max() > 1e-2
    all_window = _ref_logits(weights, dict(
        cfg, layer_types=["sliding_attention"] * 9), ids)
    assert np.abs(all_window - want).max() > 1e-2


@pytest.mark.parametrize("block_bytes", [1 << 30, 4096])
def test_blocked_attention_is_whole_attention(block_bytes, monkeypatch):
    monkeypatch.setattr(latent_moe, "SCORE_BLOCK_BYTES", block_bytes)
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 32, 2, 16)), jnp.float32)
    for window in (None, 5):
        got = latent_moe.gqa_attention(q, k, v, window)
        want = np.stack([np.asarray(reference.attend(q[b], k[b], v[b],
                                                     window))
                         for b in range(2)])
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


# -- the engine ---------------------------------------------------------------


def test_the_engine_gives_a_window_layer_a_ring_and_a_full_layer_pages(
        model, cfg):
    eng = _engine(model, cfg)
    ring = pa.ring_pages(16, 8)
    assert ring == 3 and eng._rings == (3, 3, 3, None) * 2 + (3,)
    assert pa.ring_pages(2048, 256) == 9
    shapes = [p.shape for p in eng.k_pages]
    assert shapes == [p.shape for p in eng.v_pages]
    assert shapes[3] == shapes[7] == (2, 4 * 16, 8, 16)
    assert {shapes[i] for i in (0, 1, 2, 4, 5, 6, 8)} == {(2, 4 * 3, 8, 16)}
    # the allocator is the full layers': a ring takes nothing from it
    assert eng._n_pages_total == 4 * 16 == len(eng._free_pages)


@pytest.mark.parametrize("lengths, new", [
    ((5, 13, 22, 9), 14),      # inside the window, admitted together
    ((40, 3, 70, 17), 45),     # past it, across ring and page boundaries
])
def test_prefill_then_burst_decode_through_the_engine(model, weights, cfg,
                                                      lengths, new):
    """Logits, not tokens: every served token's reference logit is the
    reference's best, to round-off, for prompts that are admitted together,
    outlive many bursts and reach contexts several windows long (115
    positions over a window of 16 and rings of 24)."""
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


def test_the_prefill_writes_a_ring_only_what_a_later_step_sees():
    """Of 29 positions at pages of 4 and a ring of 3 pages, pages 5, 6, 7
    (positions 20-28) land in ring pages 2, 0, 1; the rest is dropped."""
    k = jnp.arange(2 * 32, dtype=jnp.float32).reshape(2, 32, 1, 1) + 1
    pool = jnp.zeros((1, 2 * 3, 4, 1), jnp.float32)
    lens = jnp.array([29, 0])
    tail = pa.ring_tail(k, lens, 3, 4)
    assert tail.shape == (2, 12, 1, 1)
    got, _ = pa.prefill_ring_kv_cache(pool, pool, tail, tail,
                                      jnp.array([1, 0]), lens, 3, 32)
    got = np.asarray(got)[0, :, :, 0]
    assert not got[:3].any()                       # slot 0: a padded row
    np.testing.assert_array_equal(got[3 + 2], 1 + np.arange(20, 24))
    np.testing.assert_array_equal(got[3 + 0], 1 + np.arange(24, 28))
    np.testing.assert_array_equal(got[3 + 1], [29, 0, 0, 0])


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


def test_the_burst_counts_the_window_layers_pages(model, cfg):
    """What rides out on `serving.emit`: pages the window layers stream,
    pages holding a position a row still sees, pages a layer holding every
    position would read; the full layers keep `attn_pages_read`."""
    eng = _engine(model, cfg, max_batch=2)
    seen = []
    real = tracing.phase

    def phase(name, **attrs):
        # (the phase that commits the first token carries the prefill
        # program's own counts, `prefill_*`: not a burst's)
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
    window_layers, steps = 7, 4
    live = sum(-(-n // 8) - max(n - 16, 0) // 8 for n in range(51, 55))
    assert first["attn_window_pages_live"] == window_layers * live
    assert first["attn_window_pages_context"] == window_layers * sum(
        -(-n // 8) for n in range(51, 55))
    # the CPU's dense gather maps the whole ring of every live row
    assert first["attn_window_pages_read"] == window_layers * steps * 3
    assert first["attn_pages_read"] == 2 * sum(
        -(-n // 8) for n in range(51, 55))


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


# -- the kernels --------------------------------------------------------------


def _ring_case(rng, lens, window, page, ring, heads=4, kv=2, d=16):
    """Pools of rings holding positions 0 .. lens[b] - 1 of random K/V
    (written token by token, as decode does) beside the dense rows."""
    b, t = len(lens), max(lens)
    k = jnp.asarray(rng.normal(size=(b, t, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kv, d)), jnp.float32)
    kp = jnp.zeros((kv, b * ring, page, d), jnp.float32)
    vp = jnp.zeros_like(kp)
    rows = jnp.arange(b)
    for pos in range(t):
        at = jnp.full((b,), pos)
        kp, vp = pa.update_ring_kv_cache(
            kp, vp, k[:, pos], v[:, pos], rows, at,
            active=at < jnp.asarray(lens))
    q = jnp.asarray(rng.normal(size=(b, heads, d)), jnp.float32)
    return q, k, v, kp, vp


def _dense_window(q, k, v, lens, window):
    out = []
    for b, n in enumerate(lens):
        if n == 0:
            out.append(np.zeros((q.shape[1] * q.shape[2],), np.float32))
            continue
        qq = jnp.zeros((n,) + q.shape[1:], jnp.float32).at[n - 1].set(q[b])
        out.append(np.asarray(reference.attend(qq, k[b, :n], v[b, :n],
                                               window))[n - 1])
    return np.stack(out)


@pytest.mark.parametrize("attend", [pa.paged_attention,
                                    pa.paged_attention_xla])
def test_the_window_kernel_in_interpret_mode_against_the_dense_mask(attend):
    """Rows inside the window, past it, on page and ring boundaries, and an
    empty one: the page-grid kernel told the first visible position a row
    (and the gather it is compared with) against the dense masked
    softmax."""
    window, page = 16, 8
    ring = pa.ring_pages(window, page)
    lens = [5, 16, 17, 24, 47, 48, 0, 63]
    q, k, v, kp, vp = _ring_case(np.random.default_rng(3), lens, window,
                                 page, ring)
    tables, read, first = pa.ring_view(jnp.arange(len(lens)), ring, page,
                                       jnp.asarray(lens), window)
    assert int(read.max()) <= ring * page
    got = attend(q, kp, vp, tables, read, first=first)
    want = _dense_window(q, k, v, lens, window)
    np.testing.assert_allclose(
        np.asarray(got).reshape(len(lens), -1), want, atol=1e-5)


def test_a_window_over_plain_block_tables_masks_the_pages_before_it():
    """`first` is a mask, general over ordinary tables: pages wholly below
    it (one of them every column masked) add nothing. Skipping them is the
    caller's, by tables that start at the first visible page."""
    rng = np.random.default_rng(4)
    page, pages = 8, 6
    k = jnp.asarray(rng.normal(size=(2, 2, pages, page, 16)), jnp.float32)
    kp = k.reshape(2, 2 * pages, page, 16)
    tables = jnp.arange(2 * pages).reshape(2, pages).astype(jnp.int32)
    lens, first = jnp.array([45, 9]), jnp.array([29, 0])
    q = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
    got = pa.paged_attention(q, kp, kp, tables, lens, first=first)
    want = pa.paged_attention_xla(q, kp, kp, tables, lens, first=first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the same rows through tables that start at the first visible page
    view = jnp.stack([jnp.roll(tables[0], -3), tables[1]])
    cut = pa.paged_attention(q, kp, kp, view, lens - jnp.array([24, 0]),
                             first=first - jnp.array([24, 0]))
    np.testing.assert_allclose(np.asarray(cut), np.asarray(want), atol=1e-5)


def test_the_pages_read_are_counted_from_the_table_the_kernel_is_handed():
    """`decode_pages_fetched` reads the block indices the kernel's pipeline
    follows: over rings it is the live pages (`ring_pages_live`, from the
    lengths alone); a table that named more would count more."""
    window, page = 16, 8
    ring = pa.ring_pages(window, page)
    for lens in ([5, 16, 17, 24, 47, 48, 0, 63], [0, 0, 9, 0], [40, 41],
                 [0, 33, 0, 0, 8, 64]):
        n = jnp.asarray(lens)
        tables, read, _first = pa.ring_view(jnp.arange(len(lens)), ring,
                                            page, n, window)
        want = sum(-(-c // page) - max(c - window, 0) // page
                   for c in lens if c)
        assert int(pa.ring_pages_live(n, window, page)) == want
        assert int(pa.decode_pages_fetched(tables, read, page)) == want
    # plain tables and whole contexts: every page of every context
    tables = jnp.arange(12).reshape(2, 6).astype(jnp.int32)
    assert int(pa.decode_pages_fetched(tables, jnp.array([45, 9]), page)) \
        == 6 + 2
    # no live row: the one block the grid starts on
    assert int(pa.decode_pages_fetched(tables, jnp.array([0, 0]), page)) == 1


@pytest.mark.parametrize("window, s, h, kv, lens", [
    (None, 1024, 4, 2, None), (200, 1024, 4, 2, None),
    (512, 1024, 4, 2, None), (1500, 1024, 4, 2, None),
    # Trinity's 32 query heads on 4, rows of 0, 1, a key block's edge - 1
    # and + 1 and every token; Jamba's 20 on one (4 blocks of 5)
    (None, 1024, 32, 4, (0, 1, 511, 513, 1024)),
    (2048, 2560, 32, 4, (2560, 1, 0, 2047, 513)),
    (None, 1024, 20, 1, (1, 1024, 513, 0, 511))],
    ids=["None", "200", "512", "1500", "lens", "2048-lens", "20-on-1-lens"])
def test_the_prefill_kernel_in_interpret_mode_against_the_dense_mask(
        window, s, h, kv, lens):
    """Grouped heads, causal, with and without a window that straddles key
    blocks: flash_attention_gqa_bshd against the reference's attention;
    with `lens`, rows of those true lengths in one batch, the padding NaN
    (`check_gqa_lengths`)."""
    rng = np.random.default_rng(6)
    d = 128
    b = 1 if lens is None else len(lens)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), jnp.float32)
    if lens is None:
        got = fa.flash_attention_gqa_bshd(q, k, v, window=window)
        want = reference.attend(q[0], k[0], v[0], window)
        np.testing.assert_allclose(np.asarray(got)[0].reshape(s, -1),
                                   np.asarray(want), atol=2e-5)
    else:
        from conftest import check_gqa_lengths
        check_gqa_lengths(lambda *a: reference.attend(*a, window), q, k, v,
                          lens, 2e-5, window=window)
    # which key blocks a query block fetches: none wholly outside its window
    lo, hi = fa._gqa_key_blocks(7, 128, 512, window)
    assert int(hi) == 1 and int(lo) == (1 if window == 200 else 0)


def test_a_prefill_from_the_kernels_lengths_on_takes_the_kernel(monkeypatch):
    """The mixer asks `use_gqa_flash`; from its length on the prefill's
    attention is the kernel's, and the logits are the XLA path's."""
    c = AfmoeConfig.tiny(layers=2)
    c.head_dim, c.hidden_size = 128, 64
    c.layer_types = ("sliding_attention", "full_attention")
    c.sliding_window, c.max_position_embeddings = 300, 2048
    paddle.seed(3)
    m = AfmoeForCausalLM(c)
    m.eval()
    ids = paddle.to_tensor(np.random.default_rng(8).integers(0, 96,
                                                             (1, 1024)))
    assert fa.GQA_MIN_SEQ == 1024
    monkeypatch.setattr(fa, "GQA_MIN_SEQ", 2048)
    calls = []
    real = fa.flash_attention_gqa_bshd
    monkeypatch.setattr(fa, "flash_attention_gqa_bshd", lambda *a, **kw:
                        calls.append(kw["window"]) or real(*a, **kw))
    with paddle.no_grad():
        dense = np.asarray(m(ids)._data)
        assert not calls
        monkeypatch.setattr(fa, "GQA_MIN_SEQ", 1024)
        flash = np.asarray(m(ids)._data)
    assert calls == [300, None]
    np.testing.assert_allclose(flash, dense, atol=2e-4)


# -- the expert layer ---------------------------------------------------------


def test_the_bias_moves_the_pick_and_not_the_weights(cfg, weights):
    from paddle_tpu.incubate.distributed.models.moe import expert_share

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(11, cfg["hidden_size"])), jnp.float32)
    pre = "model.layers.2.mlp.experts.gate."
    w_r = weights[pre + "weight"]
    bias = jnp.zeros((16,)).at[5].set(10.0)
    picks, w = expert_share.sigmoid_topk(x, w_r, 4, scale=2.826, bias=bias)
    assert (np.asarray(picks) == 5).any(axis=-1).all()
    scores = jax.nn.sigmoid(x @ w_r)
    top = np.take_along_axis(np.asarray(scores), np.asarray(picks), -1)
    np.testing.assert_allclose(
        np.asarray(w), 2.826 * top / top.sum(-1, keepdims=True), rtol=1e-5)
    # and the reference's route is the same function
    st = dict(reference.static_of(cfg))
    rp, rw = reference.route(x, w_r, bias, st, "f32")
    np.testing.assert_array_equal(np.sort(np.asarray(rp), -1),
                                  np.sort(np.asarray(picks), -1))
    np.testing.assert_allclose(np.sort(np.asarray(rw), -1),
                               np.sort(np.asarray(w), -1), rtol=1e-5)
    # the seeded bias is live: it changes some token's picks
    seeded = weights[pre + "expert_bias"]
    assert float(jnp.abs(seeded).max()) > 0
    plain, _ = expert_share.sigmoid_topk(x * 0.05, w_r, 4)
    moved, _ = expert_share.sigmoid_topk(x * 0.05, w_r, 4, bias=seeded)
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(moved), -1)).any()


def test_the_shares_add_up_to_the_uncut_layer(weights, cfg):
    """model-configs section 4: the routed parts that all `ep_degree`
    shares give, plus the shared expert counted once, equal what the uncut
    reference gives for the whole layer."""
    every = tiny_cfg(num_experts=cfg["router_experts"])
    uncut = family.make_weights(every, SEED, "float32")
    pre = "model.layers.2.mlp."
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(19, cfg["hidden_size"])), jnp.float32)
    st = dict(reference.static_of(every))
    picks, w = reference.route(x, uncut[pre + "experts.gate.weight"],
                               uncut[pre + "experts.gate.expert_bias"], st,
                               "f32")
    shared = reference.gated_ffn(
        x, *(uncut[pre + f"shared_experts.{n}.weight"]
             for n in reference.FFN), "f32")
    whole = reference.routed_share(
        x, picks, w, uncut[pre + "experts.w_gate"],
        uncut[pre + "experts.w_up"], uncut[pre + "experts.w_down"], 0,
        "f32") + shared
    held, degree = cfg["num_experts"], cfg["ep_degree"]
    total = np.zeros_like(np.asarray(whole))
    for rank in range(degree):
        m = family.build_model(tiny_cfg(ep_rank=rank), SEED)
        layer = m.model.layers[2].mlp
        for name in ("w_gate", "w_up", "w_down"):   # this rank's experts
            getattr(layer.experts, name)._rebind(
                uncut[pre + "experts." + name][rank * held:(rank + 1) * held])
        layer.experts.gate.weight._rebind(uncut[pre + "experts.gate.weight"])
        layer.experts.gate.expert_bias._rebind(
            uncut[pre + "experts.gate.expert_bias"])
        assert layer.experts.first == rank * held
        with paddle.no_grad():
            routed = np.asarray(layer.experts(paddle.to_tensor(x))._data)
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
