"""The page-grid decode kernel (kernels/paged_attention.py `paged_attention`)
at pages that fill a K tile: every kv head of a page in one block, operands
in the pool's type, and only the LIVE pages fetched. Interpret mode against
the dense-gather reference; the dispatch rule; the engine's page counts."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingEngine
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import tracing

HEAD_DIM, PAGES_PER_SEQ = 128, 3


def _case(rng, lens, kv_heads, group, page, dtype=jnp.float32):
    """Rows of the given lengths over shuffled pages; page 0 belongs to
    nobody."""
    b = len(lens)
    n_pages = b * PAGES_PER_SEQ + 1
    q = jnp.asarray(rng.standard_normal((b, kv_heads * group, HEAD_DIM)),
                    dtype)
    kp, vp = (jnp.asarray(rng.standard_normal(
        (kv_heads, n_pages, page, HEAD_DIM)), dtype) for _ in range(2))
    tables = 1 + rng.permutation(n_pages - 1).reshape(b, PAGES_PER_SEQ)
    return q, kp, vp, jnp.asarray(tables, jnp.int32), \
        jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("ctx", ["0", "1", "page-1", "page", "page+1",
                                 "full"])
@pytest.mark.parametrize("page", [128, 256])
@pytest.mark.parametrize("group", [1, 8])
def test_kernel_equals_the_dense_gather(group, page, ctx):
    """The row under test stands between a live row and a row with nothing
    to read, so its pages are fetched after another row's and before a
    carried block."""
    n = {"0": 0, "1": 1, "page-1": page - 1, "page": page,
         "page+1": page + 1, "full": PAGES_PER_SEQ * page}[ctx]
    rng = np.random.default_rng(page + group + n)
    q, kp, vp, tables, lens = _case(rng, [page + 7, n, 0, 3], 2, group, page)
    out = np.asarray(pa.paged_attention(q, kp, vp, tables, lens))
    ref = np.asarray(pa.paged_attention_xla(q, kp, vp, tables, lens))
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    # a row of length 0 reads nothing and gives zeros (the reference gives
    # the mean of whatever its table maps)
    assert not out[~live].any()


def test_bf16_pools_go_to_the_products_as_they_are():
    """bf16 operands, float32 scores and accumulator: within bf16 rounding
    of the float32 reference over the same (bf16) values."""
    rng = np.random.default_rng(3)
    q, kp, vp, tables, lens = _case(rng, [300, 0, 512, 41], 2, 1, 256,
                                    jnp.bfloat16)
    out = pa.paged_attention(q, kp, vp, tables, lens)
    assert out.dtype == jnp.bfloat16
    ref = pa.paged_attention_xla(q.astype(jnp.float32),
                                 kp.astype(jnp.float32),
                                 vp.astype(jnp.float32), tables, lens)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref)[live], atol=2e-2)


def test_dead_pages_and_stale_table_entries_are_never_read():
    """Every page no live row owns holds NaN, and so does every page that
    the table entries beyond a row's length, and a retired row's whole
    table row, point at: the output is finite and equals the clean one."""
    page = 128
    rng = np.random.default_rng(11)
    lens = [page + 5, 0, 2 * page, 0, 9]
    q, kp, vp, tables, lens = _case(rng, lens, 2, 1, page)
    clean = np.asarray(pa.paged_attention(q, kp, vp, tables, lens))
    used = -(-np.asarray(lens) // page)
    live_pages = {int(tables[r, j]) for r in range(len(used))
                  for j in range(used[r])}
    dead = np.asarray([p for p in range(kp.shape[1])
                       if p not in live_pages])
    kp, vp = kp.at[:, dead].set(jnp.nan), vp.at[:, dead].set(jnp.nan)
    out = np.asarray(pa.paged_attention(q, kp, vp, tables, lens))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)
    # what the index maps are handed: live entries and nothing else, each
    # row's tail repeating its last live page, a row with nothing to read
    # repeating the block the row before it ended on
    ids = np.asarray(pa._live_page_ids(tables, lens, page))
    assert set(ids.ravel()) <= live_pages
    t = np.asarray(tables)
    np.testing.assert_array_equal(ids[0], [t[0, 0], t[0, 1], t[0, 1]])
    np.testing.assert_array_equal(ids[1], [t[0, 1]] * 3)
    np.testing.assert_array_equal(ids[3], [t[2, 1]] * 3)
    np.testing.assert_array_equal(ids[4], [t[4, 0]] * 3)
    # rows before the first live one take its first page; no live row: 0
    ids = np.asarray(pa._live_page_ids(
        tables, jnp.asarray([0, 0, 5, 0, 0], jnp.int32), page))
    assert (ids == t[2, 0]).all()
    assert not np.asarray(pa._live_page_ids(
        tables, jnp.zeros((5,), jnp.int32), page)).any()


def test_any_head_count_a_call_as_under_tp(monkeypatch):
    """The pools arrive sharded on kv heads under tp: each shard's call
    gives its heads of the whole call's output; a block holds as many
    heads as fit `_BLOCK_BYTES`, down to one."""
    rng = np.random.default_rng(5)
    q, kp, vp, tables, lens = _case(rng, [200, 0, 384, 129], 4, 2, 128)
    whole = np.asarray(pa.paged_attention(q, kp, vp, tables, lens))
    for lo, hi in [(0, 1), (1, 4), (0, 2)]:
        part = pa.paged_attention(q[:, 2 * lo:2 * hi], kp[lo:hi], vp[lo:hi],
                                  tables, lens)
        np.testing.assert_allclose(np.asarray(part),
                                   whole[:, 2 * lo:2 * hi], rtol=1e-6,
                                   atol=1e-6)
    # three heads of a page pass the bound, two do not: blocks of two
    monkeypatch.setattr(pa, "_BLOCK_BYTES", 2 * 128 * HEAD_DIM * 4)
    np.testing.assert_allclose(
        np.asarray(pa.paged_attention(q, kp, vp, tables, lens)), whole,
        rtol=1e-6, atol=1e-6)


def _dispatched(monkeypatch, page, quant, pages_per_seq=8, interpret=False):
    """Which path the dispatch takes for pools of this page size and
    type."""
    taken = []
    monkeypatch.setattr(pa, "_interpret", lambda: interpret)
    for name in ("paged_attention", "paged_attention_xla"):
        monkeypatch.setattr(
            pa, name, lambda *a, _n=name, **k: taken.append(_n))
    pool = jnp.zeros((2, 4, page, HEAD_DIM), jnp.int8 if quant
                     else jnp.bfloat16)
    scales = jnp.zeros((2, 4, pa._SCALE_LANES), jnp.float32)
    kw = dict(k_scales=scales, v_scales=scales) if quant else {}
    pa.paged_attention_dispatch(
        jnp.zeros((2, 2, HEAD_DIM), jnp.bfloat16), pool, pool,
        jnp.zeros((2, pages_per_seq), jnp.int32),
        jnp.zeros((2,), jnp.int32), **kw)
    return taken[0]


def test_dispatch_follows_page_size_pool_type_and_interpret_mode(
        monkeypatch):
    # interpret mode: the reference, whatever the pools
    for page, quant in [(256, False), (128, True), (16, False)]:
        assert _dispatched(monkeypatch, page, quant, interpret=True) \
            == "paged_attention_xla"
    # float pools at pages of 128 and more: the kernel whatever the mapped
    # context (8 pages of 128 are under the crossover)
    assert _dispatched(monkeypatch, 128, False) == "paged_attention"
    assert _dispatched(monkeypatch, 256, False) == "paged_attention"
    # pages under 128 and int8 pools: the crossover of mapped context
    assert _dispatched(monkeypatch, 16, False, pages_per_seq=128) \
        == "paged_attention_xla"                      # 2,048 mapped
    assert _dispatched(monkeypatch, 16, False, pages_per_seq=136) \
        == "paged_attention"
    assert _dispatched(monkeypatch, 64, False, pages_per_seq=32) \
        == "paged_attention_xla"
    assert _dispatched(monkeypatch, 128, True, pages_per_seq=16) \
        == "paged_attention_xla"
    assert _dispatched(monkeypatch, 128, True, pages_per_seq=17) \
        == "paged_attention"


def test_the_token_write_is_the_scatter_it_replaced():
    """`update_paged_kv_cache` scatters rows of the pools' flat view: the
    same bytes as indexing (page, slot) on every head, inactive rows
    dropped although their table row points at a live page."""
    rng = np.random.default_rng(2)
    kvh, n_pages, page, d, b = 3, 7, 8, 16, 4
    kp, vp = (jnp.asarray(rng.standard_normal((kvh, n_pages, page, d)),
                          jnp.float32) for _ in range(2))
    kn, vn = (jnp.asarray(rng.standard_normal((b, kvh, d)), jnp.float32)
              for _ in range(2))
    tables = jnp.asarray([[1, 2], [3, 4], [1, 2], [5, 6]], jnp.int32)
    lens = jnp.asarray([9, 0, 9, 15], jnp.int32)
    active = jnp.asarray([True, True, False, True])
    nk, nv = pa.update_paged_kv_cache(kp, vp, kn, vn, tables, lens,
                                      active=active)
    ek, ev = np.array(kp), np.array(vp)
    for row, (pg, slot) in {0: (2, 1), 1: (3, 0), 3: (6, 7)}.items():
        ek[:, pg, slot], ev[:, pg, slot] = kn[row], vn[row]
    np.testing.assert_array_equal(np.asarray(nk), ek)
    np.testing.assert_array_equal(np.asarray(nv), ev)


def test_a_gpt_burst_counts_the_pages_it_reads_and_maps(monkeypatch):
    """`attn_pages_read` and `attn_pages_mapped` on `serving.emit`, summed
    over layers and steps inside the program, against the counts worked
    out here from the rows' lengths alone."""
    seen = []
    real = tracing.phase

    def phase(name, **attrs):
        if name == "serving.emit" and attrs:
            seen.append(attrs)
        return real(name, **attrs)

    monkeypatch.setattr(tracing, "phase", phase)
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    page, burst, rows, layers = 8, 4, 3, 2
    eng = ServingEngine(m, max_batch=rows, max_seq_len=32, page_size=page,
                        decode_burst=burst)
    requests = [(5, 7), (14, 4), (8, 10)]     # prompt tokens, new tokens
    for n, new in requests:
        eng.add_request(np.arange(n) + 1, max_new_tokens=new)
    done = eng.run()
    assert [len(r.output_ids) for r in done] == [4, 7, 10]
    assert seen and all(set(a) == {"attn_pages_read", "attn_pages_mapped"}
                        for a in seen)
    # the first token comes from the prefill; decode step j of a request
    # attends over its prompt, the j tokens before and the one just written
    read = layers * sum(-(-(n + j + 1) // page)
                        for n, new in requests for j in range(new - 1))
    assert sum(a["attn_pages_read"] for a in seen) == read
    # every program step maps rows x pages a row a layer, whatever lives
    steps = {a["attn_pages_mapped"] // (layers * rows * eng.pages_per_seq)
             for a in seen}
    assert steps <= {1, burst}
    assert all(a["attn_pages_read"] <= a["attn_pages_mapped"] for a in seen)
