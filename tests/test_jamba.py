"""AI21-Jamba2-3B's shape (`model_type` jamba) in the one stack of
models/latent_moe.py: Mamba mixers beside grouped-query attention layers
with every option off, a dense FFN in every layer, a tied float32 head;
the serving engine's state pools a slot beside K/V pages; the selective
scan's kernel against the recurrence a token at a time. At a small size on the CPU, against the
plain float32 reference (benchmark/reference/jamba.py) on the family's
seeded weights."""
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
from paddle_tpu.kernels import selective_scan as ss  # noqa: E402
from paddle_tpu.models import JambaConfig, latent_moe  # noqa: E402
from paddle_tpu.models.causal_lm import StatePool  # noqa: E402
from paddle_tpu.tensor import as_array  # noqa: E402

from benchmark.families import jamba as family  # noqa: E402
from benchmark.reference import jamba as reference  # noqa: E402

SEED = 2039


def tiny_cfg(**over):
    with open(os.path.join(REPO, "tests", "benchmark_suite", "data",
                           "configs", "tiny-jamba.json")) as f:
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


def _prompts(cfg, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg["vocab_size"], n) for n in lengths]


# -- the stack ----------------------------------------------------------------


def test_the_config_lists_every_layers_kinds():
    real = JambaConfig()
    kinds = real.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k[0] == "sink_full"] == [7, 21]
    assert {k[0] for k in kinds} == {"sink_full", "mamba"}
    assert {k[1:] for k in kinds} == {("dense", "pre")}
    assert real.mamba() == dict(d_inner=5120, d_state=16, d_conv=4,
                                dt_rank=160)
    a = real.attention(False)
    assert (a["heads"], a["kv_heads"], a["key_dim"], a["value_dim"]) \
        == (20, 1, 128, 128)
    assert (a["rope_dims"], a["window"], a["sink"], a["value_scale"]) \
        == (0, None, False, 1.0)
    tiny = JambaConfig.tiny()
    assert [i for i, k in enumerate(tiny.layer_kinds())
            if k[0] == "sink_full"] == [2, 6]
    for bad in (dict(num_experts=16), dict(sliding_window=4096),
                dict(mamba_proj_bias=True), dict(mamba_conv_bias=False)):
        with pytest.raises(NotImplementedError, match="jamba is built for"):
            JambaConfig(**bad)


def test_it_is_the_one_stack_with_a_mamba_kind(model):
    assert isinstance(model, latent_moe.LatentMoEForCausalLM)
    assert type(model.model) is latent_moe.LatentMoEModel
    mixers = [type(layer.self_attn) for layer in model.model.layers]
    assert mixers == [latent_moe.MambaMixer] * 2 \
        + [latent_moe.SinkGQAttention] + [latent_moe.MambaMixer] * 3 \
        + [latent_moe.SinkGQAttention, latent_moe.MambaMixer]
    assert all(type(layer.mlp) is latent_moe.GatedFFN
               for layer in model.model.layers)
    assert model.lm_head is None
    layouts = model.kv_cache_layouts()
    assert layouts[0] == (StatePool((3, 128)), StatePool((8, 128), "float32"))
    assert layouts[2] == ((1, 32), (1, 32))
    assert model.kv_cache_windows() == (None,) * 8


def test_forward_agrees_with_the_reference(model, weights, cfg):
    ids = _prompts(cfg, [37])[0]
    got = np.asarray(as_array(model(paddle.to_tensor(ids[None]))))[0]
    want = _ref_logits(weights, cfg, ids)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("change", ["state", "conv", "norms"])
def test_each_mechanism_is_live_in_the_reference(weights, cfg, change):
    """The checks below would notice each part going missing: the scan's
    state carrying across tokens, the conv's taps, the mixer's norms (a
    hundred times float32's round-off here)."""
    ids = _prompts(cfg, [30], seed=3)[0]
    base = _ref_logits(weights, cfg, ids)
    w = dict(weights)
    for k in w:
        if change == "state" and k.endswith("A_log"):
            w[k] = w[k] + 20.0           # every channel forgets at once
        if change == "conv" and k.endswith("conv_weight"):
            w[k] = w[k].at[:-1].set(0.0)
        if change == "norms" and k.endswith(("b_layernorm.weight",
                                             "c_layernorm.weight")):
            w[k] = 2.0 * w[k]
    assert np.abs(_ref_logits(w, cfg, ids) - base).max() > 1e-4


def test_the_tied_head_gives_float32_logits_in_a_bf16_model(cfg):
    model = family.build_model(dict(cfg, dtype="bfloat16"), SEED)
    ids = paddle.to_tensor(np.arange(9)[None])
    logits = as_array(model(ids))
    assert logits.dtype == jnp.float32
    last, _ = model.forward_prefill(ids, model.init_kv_caches(
        1, 9, dtype=jnp.bfloat16), jnp.asarray([9], jnp.int32))
    assert last.dtype == jnp.float32 and last.shape == (1, 96)
    np.testing.assert_allclose(np.asarray(last[0]),
                               np.asarray(logits[0, -1]), atol=1e-2)


# -- the state through a cache ------------------------------------------------


def test_a_padded_prefill_is_the_unpadded_prefill(model, cfg):
    """Prompts of different true lengths in one padded prefill (and a row
    of length 0): each row's first-token logits, its K/V at live positions
    and its Mamba states are what the prompt gives alone: the padding does
    not move the state."""
    prompts = _prompts(cfg, [21, 6, 13], seed=5)
    nb, bucket = 4, 32
    ids = np.zeros((nb, bucket), np.int64)
    for row, p in enumerate(prompts):
        ids[row, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts] + [0], jnp.int32)
    with paddle.no_grad():
        last, caches = model.forward_prefill(
            paddle.to_tensor(ids), model.init_kv_caches(nb, bucket), lens)
    states = [li for li, layout in enumerate(model.kv_cache_layouts())
              if isinstance(layout[0], StatePool)]
    for row, p in enumerate(prompts):
        with paddle.no_grad():
            last1, caches1 = model.forward_prefill(
                paddle.to_tensor(p[None]), model.init_kv_caches(1, len(p)),
                jnp.asarray([len(p)], jnp.int32))
        np.testing.assert_allclose(np.asarray(last[row]),
                                   np.asarray(last1[0]), atol=2e-5)
        for li, (c, c1) in enumerate(zip(caches, caches1)):
            for a, a1 in zip(c, c1):
                a, a1 = np.asarray(as_array(a)), np.asarray(as_array(a1))
                got = a[row] if li in states else a[row, :len(p)]
                np.testing.assert_allclose(got, a1[0], atol=2e-5)


def test_prefill_then_burst_decode_through_the_engine(model, weights, cfg):
    """Logits, not tokens: every served token's reference logit is the
    reference's best, to round-off, for prompts admitted together whose
    rows carry their states through many bursts; the engine's streams are
    generate()'s (dense caches, zero states, one pass)."""
    eng = _engine(model, cfg)
    assert eng._states == (True, True, False, True) * 2
    assert eng._layer_writes and eng._mixed_layout
    prompts = _prompts(cfg, [5, 17, 30], seed=7)
    rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    done = {f.request_id: f.output_ids for f in eng.run()}
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(done[rid])
        assert len(out) == 12
        assert _gaps(weights, cfg, prompt, out).max() < 2e-4
    tokens, _ = model.generate(paddle.to_tensor(prompts[1][None]),
                               max_new_tokens=12)
    np.testing.assert_array_equal(np.asarray(tokens._data)[0], done[rids[1]])
    assert len(eng._free_pages) == eng._n_pages_total


def test_the_engine_keeps_a_state_a_slot_outside_the_allocator(model, cfg):
    eng = _engine(model, cfg)
    e = cfg["engine"]
    conv, ssm = eng.k_pages[0], eng.v_pages[0]
    assert conv.shape == (e["max_batch"], 3, 128) and conv.dtype == jnp.float32
    assert ssm.shape == (e["max_batch"], 8, 128) and ssm.dtype == jnp.float32
    assert eng.k_pages[2].shape == (1, eng._n_pages_total, e["page_size"], 32)
    assert eng.kv_pool_bytes() == family.pool_bytes(cfg, e, itemsize=4)
    bf16 = _engine(family.build_model(dict(cfg, dtype="bfloat16"), SEED), cfg)
    assert bf16.k_pages[0].dtype == jnp.bfloat16
    assert bf16.v_pages[0].dtype == jnp.float32


def test_a_reused_slot_starts_from_its_own_prefill(model, cfg):
    """One slot serves a long request and then a short one: the short
    one's stream and the slot's state after it are a fresh engine's."""
    long_, short = _prompts(cfg, [40, 7], seed=9)
    eng = _engine(model, cfg, max_batch=1)
    eng.add_request(long_, max_new_tokens=10)
    eng.run()
    rid = eng.add_request(short, max_new_tokens=9)
    (done,) = eng.run()
    fresh = _engine(model, cfg, max_batch=1)
    fresh.add_request(short, max_new_tokens=9)
    (want,) = fresh.run()
    np.testing.assert_array_equal(done.output_ids, want.output_ids)
    assert done.request_id == rid
    for li in (0, 1, 3):
        for got, ref in ((eng.k_pages[li], fresh.k_pages[li]),
                         (eng.v_pages[li], fresh.v_pages[li])):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-6)


def test_a_preempted_row_streams_as_the_uninterrupted_one(model, cfg):
    """A row preempted mid-stream is recomputed from its context (prompt
    and the tokens it had) into whatever slot it gets, and its stream is
    the one it would have had."""
    p1, p2 = _prompts(cfg, [11, 19], seed=11)
    whole = _engine(model, cfg)
    r1, r2 = (whole.add_request(p, max_new_tokens=16) for p in (p1, p2))
    want = {f.request_id: f.output_ids for f in whole.run()}
    eng = _engine(model, cfg)
    r1, r2 = (eng.add_request(p, max_new_tokens=16) for p in (p1, p2))
    eng.step()
    eng.step()
    victim = next(i for i, s in enumerate(eng.slots)
                  if s.active and s.request_id == r2)
    assert 0 < len(eng.slots[victim].tokens) < 16
    eng._preempt(victim)
    # another request takes the victim's slot first
    eng.add_request(p1[:5], max_new_tokens=3)
    got = {f.request_id: f.output_ids for f in eng.run()}
    for rid in (r1, r2):
        np.testing.assert_array_equal(got[rid], want[rid])


def test_generate_from_a_state_is_one_pass(model, cfg):
    """`generate` prefills into zero states and steps a token at a time
    from them; its greedy tokens are the argmax of one full forward pass
    over the sequence so far."""
    prompt = _prompts(cfg, [14], seed=13)[0]
    tokens, _ = model.generate(paddle.to_tensor(prompt[None]),
                               max_new_tokens=8)
    seq = list(prompt)
    for _ in range(8):
        logits = as_array(model(paddle.to_tensor(np.asarray(seq)[None])))
        seq.append(int(np.argmax(np.asarray(logits)[0, -1])))
    np.testing.assert_array_equal(np.asarray(tokens._data)[0], seq[14:])


@pytest.mark.parametrize("asked, sentence", [
    (dict(kv_cache_quant="int8"), "kv_cache_quant='int8' is not built"),
    (dict(prefix_cache=1), "prefix_cache=1 is not built"),
    (dict(prefix_cache=1, kv_host_cache_mb=8), "prefix_cache=1 is not built"),
    (dict(spec_decode=4), "spec_decode=4 is not built"),
    (dict(prefill_chunk=16), "prefill_chunk=16 is not built"),
])
def test_what_a_state_layout_cannot_do_yet_raises_at_construction(
        model, cfg, asked, sentence):
    with pytest.raises(ValueError, match=sentence + ".*state of some layers"):
        _engine(model, cfg, **asked)


def test_a_state_layout_is_not_sharded_and_not_handed_off(model, cfg, mesh8):
    with pytest.raises(ValueError, match="keep a state.*cannot be sharded"):
        _engine(model, cfg, mesh=mesh8)
    import paddle_tpu.distributed.mesh as mesh_mod

    mesh_mod.set_mesh(None)
    eng = _engine(model, cfg)
    rid = eng.add_request(np.arange(5), max_new_tokens=4)
    eng.step()
    with pytest.raises(NotImplementedError, match="states.*no hand-off"):
        eng.detach_request(rid)


def test_a_layout_of_states_alone_writes_a_layer_at_a_time(cfg):
    """Every layer a Mamba layer (no K/V anywhere): still a layer's own
    write program, and the reference's logits."""
    c = tiny_cfg(num_hidden_layers=3, attn_layer_offset=5)
    m = family.build_model(c, SEED)
    w = family.make_weights(c, SEED, "float32")
    eng = _engine(m, c, max_batch=2)
    assert all(eng._states) and eng._layer_writes and eng._mixed_layout
    prompt = _prompts(c, [19], seed=17)[0]
    eng.add_request(prompt, max_new_tokens=10)
    (done,) = eng.run()
    assert _gaps(w, c, prompt, np.asarray(done.output_ids)).max() < 2e-4


# -- the scan -----------------------------------------------------------------


def _scan_inputs(rows, seq, e, n, seed, dtype=jnp.bfloat16, zero=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(k[0], (rows, seq, e)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, seq, e)) - 3.0)
    a = -jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (1, e))
    b = jax.random.normal(k[2], (rows, seq, n))
    c = jax.random.normal(k[3], (rows, seq, n))
    h0 = jnp.zeros((rows, n, e)) if zero \
        else jax.random.normal(k[4], (rows, n, e))
    return u, dt, a, b, c, 1.0 + 0.1 * jnp.arange(e) / e, h0


def _sequential(u, dt, a, b, c, d, h0, lens):
    """The recurrence a token at a time in float64, each row from its own
    state and over its own real tokens: (y of the real tokens, a row each;
    the states at the rows' last real tokens)."""
    u, dt, a, b, c, d = (np.asarray(x, np.float64) for x in
                         (jnp.asarray(u, jnp.float32), dt, a, b, c, d))
    ys, hs = [], []
    for row, n in enumerate(np.asarray(lens)):
        state = np.asarray(h0[row], np.float64)
        y = np.zeros((n, u.shape[2]))
        for t in range(n):
            state = np.exp(dt[row, t][None] * a) * state \
                + (dt[row, t] * u[row, t])[None] * b[row, t][:, None]
            y[t] = (state * c[row, t][:, None]).sum(0) + d * u[row, t]
        ys.append(y)
        hs.append(state)
    return ys, np.stack(hs)


@pytest.mark.parametrize("seq, e, lens, zero", [
    (256, 1024, (256, 256), True),    # whole chunks, from a zero state
    (256, 1024, (256, 131), False),   # a row shorter than the chunks it spans
    (200, 1024, (200, 77), False),    # a length no chunk divides
    (300, 1024, (1, 0), True),        # a row of one token and one of none
    (150, 1280, (150, 97), False),    # two blocks of 640 lanes
])
def test_the_kernel_in_interpret_mode_is_the_sequential_scan(seq, e, lens,
                                                             zero):
    args = _scan_inputs(2, seq, e, 16, seed=seq, zero=zero)
    lens = jnp.asarray(lens, jnp.int32)
    want_y, want_h = _sequential(*args, lens)
    y, h = ss.selective_scan(*args, lens)
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(np.asarray(y[row, :n], np.float32),
                                   want_y[row], atol=2e-2, rtol=1e-2)
        # past its length a row's y is zeros from a skipped chunk or the
        # state held still; its state is the one at its last real token
        assert np.isfinite(np.asarray(y[row], np.float32)).all()
    np.testing.assert_allclose(np.asarray(h), want_h, atol=1e-4, rtol=1e-4)
    # a row of no tokens hands its starting state back
    for row, n in enumerate(np.asarray(lens)):
        if n == 0:
            np.testing.assert_array_equal(np.asarray(h[row]),
                                          np.asarray(args[-1][row]))


def test_the_kernel_takes_whole_lane_tiles():
    assert ss.scan_block(5120) == ss.SCAN_BLOCK_E == 1024
    assert ss.scan_block(1280) == 640
    assert ss.scan_block(128) == 128
    assert ss.scan_supports(5120, 16) and ss.scan_supports(128, 8)
    assert not ss.scan_supports(5000, 16)
    assert not ss.scan_supports(128, 12)
    args = _scan_inputs(1, 8, 64, 8, seed=1)
    with pytest.raises(ValueError, match="whole tiles of 128 lanes"):
        ss.selective_scan(*args, jnp.asarray([8], jnp.int32))


def test_the_scan_runs_under_a_tape_and_has_no_backward():
    u, dt, a, b, c, d, h0 = _scan_inputs(1, 8, 128, 8, seed=2,
                                         dtype=jnp.float32)
    lens = jnp.asarray([8], jnp.int32)

    def loss(u):
        return ss.selective_scan(u, dt, a, b, c, d, h0, lens)[0].sum()

    value, pullback = jax.vjp(loss, u)
    assert np.isfinite(float(value))
    with pytest.raises(NotImplementedError, match="no backward"):
        pullback(jnp.ones((), jnp.float32))
