"""The page write after a batched prefill (serving.py `_get_page_write_fn`
/ `_write_prefill_pages`): one compiled program per (batch bucket, token
bucket) whose pools are donated, called once per layer, in place of the
eager per-layer `prefill_paged_kv_cache` loop. Held to: the same bytes as
that loop, donation and aliasing, one program for every `n` of a bucket,
the tp page sharding with no re-pin, and the recovery path when a write
raises."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed.mesh as mesh_mod
from paddle_tpu.inference import ServingEngine
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

OOM_MSG = ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
           "1073741824 bytes.")
QUANTS = [None, "int8"]


def _engine(vocab=97, hidden=32, **kw):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=hidden, layers=2, heads=4,
                           seq=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("decode_strategy", "greedy_search")
    return ServingEngine(m, **kw)


def _pools(eng):
    return eng.k_pages + eng.v_pages + (eng.k_scales or []) + \
        (eng.v_scales or [])


def _fill(pools, rng):
    """Pools of the same shapes and dtypes holding a pattern, so a page
    that must stay untouched can be told from one written with zeros."""
    return [jnp.asarray(rng.integers(-100, 100, p.shape), p.dtype)
            for p in pools]


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("lens", [(11, 3), (16, 9, 5), (24,)],
                         ids=["n2_in_page", "n3_page_end", "n1_full"])
def test_compiled_write_equals_eager_loop_bit_for_bit(quant, lens):
    eng = _engine(kv_cache_quant=quant)
    rng = np.random.default_rng(7)
    L, nb, bucket, n = len(eng.k_pages), 4, 24, len(lens)
    kvh, _, page, hd = eng.k_pages[0].shape
    ks = jnp.asarray(rng.standard_normal((L, nb, bucket, kvh, hd)),
                     jnp.float32)
    vs = jnp.asarray(rng.standard_normal((L, nb, bucket, kvh, hd)),
                     jnp.float32)
    # live rows own pages 1.. (never page 0, which a padded row's table
    # of zeros names); a padded row has length 0
    tables = np.zeros((nb, eng.pages_per_seq), np.int32)
    nxt = 1
    for row, ln in enumerate(lens):
        for j in range(-(-ln // page)):
            tables[row, j] = nxt
            nxt += 1
    write_lens = np.zeros((nb,), np.int32)
    write_lens[:n] = lens
    eng.k_pages, eng.v_pages = (_fill(eng.k_pages, rng),
                                _fill(eng.v_pages, rng))
    if quant:
        eng.k_scales, eng.v_scales = (_fill(eng.k_scales, rng),
                                      _fill(eng.v_scales, rng))
    before = [np.asarray(p) for p in _pools(eng)]

    # the eager loop this program replaced: n rows, sliced per layer
    want = []
    for li in range(L):
        if quant:
            want.append(pa.prefill_paged_kv_cache_q8(
                eng.k_pages[li], eng.k_scales[li], eng.v_pages[li],
                eng.v_scales[li], ks[li][:n], vs[li][:n],
                jnp.asarray(tables[:n]), jnp.asarray(write_lens[:n])))
        else:
            want.append(pa.prefill_paged_kv_cache(
                eng.k_pages[li], eng.v_pages[li], ks[li][:n], vs[li][:n],
                jnp.asarray(tables[:n]), jnp.asarray(write_lens[:n])))
    want = [[np.asarray(a) for a in layer] for layer in want]

    fn = eng._get_page_write_fn(nb, bucket)
    assert eng._write_prefill_pages(
        lambda li, pools: fn(pools, ks, vs, jnp.asarray(tables),
                             jnp.asarray(write_lens), np.int32(li)),
        eng.k_pages, eng.v_pages, eng.k_scales, eng.v_scales)
    for li in range(L):
        got = (eng.k_pages[li], eng.k_scales[li], eng.v_pages[li],
               eng.v_scales[li]) if quant else \
            (eng.k_pages[li], eng.v_pages[li])
        for g, w in zip(got, want[li]):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), w)
    # pages no live row owns (page 0 among them) hold what they held, and
    # the positions past a length that ends inside a page do too
    used = sorted(set(tables[:n][tables[:n] > 0].tolist()))
    free = [p for p in range(eng.k_pages[0].shape[1]) if p not in used]
    for b, a in zip(before, [np.asarray(p) for p in _pools(eng)]):
        np.testing.assert_array_equal(a[:, free], b[:, free])
        for row, ln in enumerate(lens):
            if ln % page:
                last = tables[row, ln // page]
                np.testing.assert_array_equal(a[:, last, ln % page:],
                                              b[:, last, ln % page:])


@pytest.mark.parametrize("quant", QUANTS)
def test_prefill_donates_every_pool_and_the_program_aliases_it(quant):
    eng = _engine(kv_cache_quant=quant)
    old = _pools(eng)
    eng.add_request(np.arange(11), max_new_tokens=4)
    eng.add_request(np.arange(5), max_new_tokens=4)
    eng.add_request(np.arange(9), max_new_tokens=4)
    eng._admit()
    assert all(p.is_deleted() for p in old)
    assert not any(p.is_deleted() for p in _pools(eng))
    (key, fn), = eng._page_write_fns.items()
    assert key == (4, 16, "target")
    # every donated pool is aliased to a result: written in place
    L, nb, bucket = len(eng.k_pages), 4, 16
    kvh, _, _, hd = eng.k_pages[0].shape
    layer = (eng.k_pages[0], eng.k_scales[0], eng.v_pages[0],
             eng.v_scales[0]) if quant else (eng.k_pages[0], eng.v_pages[0])
    kv = jax.ShapeDtypeStruct((L, nb, bucket, kvh, hd), jnp.float32)
    text = fn.lower(
        layer, kv, kv,
        jax.ShapeDtypeStruct((nb, eng.pages_per_seq), jnp.int32),
        jax.ShapeDtypeStruct((nb,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    header = text[:text.index("\n")]
    assert "pure_page_write" in header
    assert "pure_prefill" not in header and "pure_burst" not in header
    for i in range(len(layer)):
        assert f"{{{i}}}: ({i}, {{}}, may-alias)" in header or \
            f"{{{i}}}: ({i}, {{}}, must-alias)" in header, header
    out = eng.run()
    assert len(out) == 3 and all(len(f.output_ids) == 4 for f in out)


def test_one_program_for_every_n_of_a_bucket():
    eng = _engine()
    for n in (3, 4, 3):
        for _ in range(n):
            eng.add_request(np.arange(6), max_new_tokens=2)
        eng.run()
    assert list(eng._page_write_fns) == [(4, 8, "target")]
    assert eng._page_write_fns[(4, 8, "target")]._cache_size() == 1
    # the prefill programs' own key, never n
    assert {k[:2] for k in eng._prefill_fns} == {(4, 8)}


def test_draft_model_pools_take_the_same_path():
    paddle.seed(1)
    dcfg = LlamaConfig.tiny(vocab=97, hidden=16, layers=1, heads=2, seq=64)
    draft = LlamaForCausalLM(dcfg)
    draft.eval()
    eng = _engine(draft_model=draft, spec_decode=2)
    old = eng._draft_k_pages + eng._draft_v_pages
    eng.add_request(np.arange(7), max_new_tokens=5)
    eng._admit()
    assert sorted(eng._page_write_fns) == [(1, 8, "draft"),
                                           (1, 8, "target")]
    assert all(p.is_deleted() for p in old)
    # the draft's pages hold the prompt: positions 0..6 of page 1 written
    slot = next(i for i, s in enumerate(eng.slots) if s.active)
    page = int(eng.block_tables[slot, 0])
    got = np.asarray(eng._draft_k_pages[0])[:, page]
    assert np.abs(got[:, :7]).min() > 0 and not got[:, 7:].any()
    out = eng.run()
    assert len(out) == 1 and len(out[0].output_ids) == 5


@pytest.mark.parametrize("quant", QUANTS)
def test_tp_pools_keep_their_sharding_with_no_repin(quant, monkeypatch):
    mesh_mod.set_mesh(None)
    mesh = mesh_mod.set_mesh(mesh_mod.build_mesh(
        tp=4, devices=np.asarray(jax.devices("cpu")[:4])))
    try:
        # tests/test_serving_tp.py's widths: the vocabulary divides by tp
        eng = _engine(vocab=128, hidden=64, mesh=mesh, kv_cache_quant=quant)
        assert eng._page_sharding is not None
        pins = []
        monkeypatch.setattr(eng, "_pin_pages", lambda: pins.append(1))
        old = _pools(eng)
        eng.add_request(np.arange(11), max_new_tokens=3)
        eng.add_request(np.arange(4), max_new_tokens=3)
        eng._admit()
        assert not pins
        assert all(p.is_deleted() for p in old)
        assert all(p.sharding == eng._page_sharding for p in _pools(eng))
        out = eng.run()
        assert len(out) == 2 and not pins
    finally:
        mesh_mod.set_mesh(None)


@pytest.fixture
def memwatch_on(tmp_path):
    """FLAGS_memwatch on with dumps routed to tmp, no recovery backoff;
    restored after."""
    flags = {"FLAGS_memwatch": True,
             "FLAGS_memwatch_dump_dir": str(tmp_path),
             "FLAGS_serving_recovery_backoff_s": 0.0}
    prev = paddle.get_flags(list(flags))
    paddle.set_flags(flags)
    yield tmp_path
    paddle.set_flags(prev)


def _failing_write(eng, exc, delete):
    """The engine's page write replaced, once, by one that raises `exc`
    (after deleting the pools it was given, as a donating call that
    failed on the device would have)."""
    real = eng._get_page_write_fn

    def getter(*key):
        eng._get_page_write_fn = real   # the re-admission's write is real

        def fn(pools, *a):
            if delete:
                for p in pools:
                    p.delete()
            raise exc

        return fn

    eng._get_page_write_fn = getter


def test_write_raising_after_donation_recovers_through_poison_if_donated(
        memwatch_on, monkeypatch):
    eng = _engine()
    rid = eng.add_request(np.arange(6), max_new_tokens=4)
    _failing_write(eng, RuntimeError("INTERNAL: device halted"), delete=True)
    whys = []
    real = eng._poison_if_donated
    monkeypatch.setattr(
        eng, "_poison_if_donated",
        lambda why, *pages: (whys.append(why), real(why, *pages)))
    with pytest.raises(RuntimeError, match="device halted"):
        eng.step()
    assert whys == ["prefill page write raised after donating the KV pages"]
    assert eng._recoveries == 1 and not eng._poisoned
    assert not eng._buffers_deleted(eng.k_pages)
    assert not glob.glob(str(memwatch_on / "oom_*"))    # not an OOM
    out = eng.run()                  # the request re-prefills and finishes
    assert [f.request_id for f in out] == [rid]
    assert len(out[0].output_ids) == 4


def test_write_raising_before_donation_leaves_the_pools_alone(memwatch_on):
    eng = _engine()
    eng.add_request(np.arange(6), max_new_tokens=4)
    _failing_write(eng, RuntimeError("INVALID_ARGUMENT: shape"),
                   delete=False)
    old = _pools(eng)
    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        eng.step()
    assert eng._recoveries == 0 and not eng._poisoned
    assert all(a is b for a, b in zip(old, _pools(eng)))


@pytest.mark.parametrize("delete", [False, True],
                         ids=["pools_alive", "pools_donated"])
def test_oom_in_the_write_dumps_and_requeues_the_round(memwatch_on, delete):
    eng = _engine()
    rids = [eng.add_request(np.arange(6), max_new_tokens=4),
            eng.add_request(np.arange(9), max_new_tokens=3)]
    _failing_write(eng, RuntimeError(OOM_MSG), delete=delete)
    assert eng.step() == []          # absorbed: no raise, nothing decoded
    assert eng._recoveries == 1 and not eng._poisoned
    assert not any(s.active for s in eng.slots)
    assert len(eng._pending) == 2
    dumps = glob.glob(str(memwatch_on / "oom_serving_prefill_page_write_*"))
    assert len(dumps) == 1
    assert "== kv page table ==" in open(dumps[0]).read()
    out = eng.run()
    assert sorted(f.request_id for f in out) == rids
    assert sorted(len(f.output_ids) for f in out) == [3, 4]
