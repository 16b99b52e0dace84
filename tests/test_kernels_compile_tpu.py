"""Every Pallas kernel, compiled by Mosaic for a TPU v5e — on the CPU.

libtpu builds a compile-only client from a topology description
(`jax.experimental.topologies`), and `jit(f).lower(<ShapeDtypeStructs
sharded to its devices>).compile()` then runs Mosaic and XLA:TPU for real,
with no chip attached. So a kernel the compiler refuses (a block that does
not fit the 16 MiB of scoped VMEM, an illegal tile, a `shard_map` that
rejects the kernel's out_shape) fails HERE, in tier-1, instead of on the
chip budget. Interpret mode is switched off for the test; nothing executes.

Covered: the kernel table `chip_smoke.py` runs on the chip (same shapes,
GPT-3 1.3B head geometry), each kernel at the corners of its `supports()`
range, the Pallas paged kernel inside the tp=4 `shard_map` of
models/paged_step.py over the four topology devices, and the flash kernel
inside a `check_vma=True` shard_map like distributed/pipeline.py's.

libtpu's stderr chatter about TPU_ACCELERATOR_TYPE / worker hostnames is
harmless: there is no TPU VM metadata here to read.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from conftest import load_repo_script
from paddle_tpu.kernels import expert_grouped as eg
from paddle_tpu.kernels import expert_hit as eh
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import quant_matmul as qm
from paddle_tpu.kernels import rms_norm as rn

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


chip_smoke = load_repo_script("chip_smoke.py")
CASES = {c.name: c for c in chip_smoke.kernel_cases()}


@pytest.fixture(scope="module")
def v5e():
    """Four compile-only `TPU v5 lite` devices (one 2x2 host)."""
    devs = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    assert [d.device_kind for d in devs] == ["TPU v5 lite"] * 4
    return devs


@pytest.fixture(autouse=True)
def mosaic(monkeypatch):
    """interpret=False in every kernel module although the default backend
    is the CPU: the programs are lowered for the topology's devices."""
    for mod in (fa, pa, rn, qm, eh, eg):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def compile_for(dev, fn, *args):
    """AOT-compile fn for one topology device; `args` are arrays or
    ShapeDtypeStructs (only shape and dtype are used). Returns the number
    of Mosaic custom calls in the compiled program."""
    sharding = SingleDeviceSharding(dev)
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in args]
    flags = chip_smoke.pallas_interpret_flags(fn, *specs)
    assert flags and not any(flags), flags
    text = jax.jit(fn).lower(*specs).compile().as_text()
    n = text.count("tpu_custom_call")
    assert n >= 1
    return n


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# the chip phase's table, shape for shape
# ---------------------------------------------------------------------------


_ARGS = {}  # make_args -> its arrays: fwd and fwd+bwd cases share them


@pytest.mark.parametrize("name", sorted(CASES))
def test_chip_smoke_kernel_case_compiles(v5e, name):
    case = CASES[name]
    if case.make_args not in _ARGS:
        _ARGS[case.make_args] = case.make_args(np.random.default_rng(7))
    compile_for(v5e[0], case.fn, *_ARGS[case.make_args])


# ---------------------------------------------------------------------------
# supports() corners
# ---------------------------------------------------------------------------


def _sum_grad(f, n):
    return jax.grad(lambda *a: jnp.sum(f(*a).astype(F32)),
                    argnums=tuple(range(n)))


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("cols", [2048, 4096, 5120, 8192])
def test_rms_norm_fits_scoped_vmem(v5e, cols, dtype):
    """Rows per block follow width and dtype: 4096 and 5120 (LLaMA-2
    7B/13B) and 8192 columns were refused at the old fixed 256 rows
    ("Scoped allocation ... exceeded scoped vmem limit"). The refusals
    begin at 8192 rows, so a 2048-row probe would miss them."""
    rows = 32768
    assert rn.supports(rows, cols, itemsize=jnp.dtype(dtype).itemsize)
    x, w = S((rows, cols), dtype), S((cols,), dtype)
    compile_for(v5e[0], rn.rms_norm, x, w)
    compile_for(v5e[0], _sum_grad(rn.rms_norm, 2), x, w)


def test_rms_norm_supports_is_the_gate():
    # the default block shrinks with width and itemsize: 1 MiB a block
    assert [rn._block(8192, c, 2) for c in (2048, 4096, 5120, 8192)] \
        == [256, 128, 64, 64]
    assert [rn._block(8192, c, 4) for c in (2048, 4096, 5120, 8192)] \
        == [128, 64, 32, 32]
    assert not rn.supports(8192, 8320)  # beyond 8192 columns
    # few rows: one block of all of them
    assert rn.supports(8, 2048) and rn._block(8, 2048, 4) == 8


def test_rms_norm_decode_rows(v5e):
    compile_for(v5e[0], rn.rms_norm, S((8, 2048), BF16), S((2048,), BF16))


@pytest.mark.parametrize("head_dim,block", [(128, 512), (256, 128),
                                            (256, 512)])
def test_flash_block_and_head_dim_corners(v5e, monkeypatch, head_dim, block):
    """The largest blocks and a 256-wide head, fwd and bwd."""
    s = 1024
    assert fa.supports(s, s, head_dim, block, block)
    q = S((1, s, 4, head_dim), BF16)

    def f(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True, block_q=block,
                                       block_k=block)

    compile_for(v5e[0], f, q, q, q)
    # force the streamed backward below the threshold of 128 x 128 blocks
    monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
    assert compile_for(v5e[0], _sum_grad(f, 3), q, q, q) == 3


def test_flash_train_cell_shape_under_checkpoint(v5e):
    """gpt3-1.3b-l12.train-2k's attention, [4, 2,048, 16, 128] bf16, as the
    trainer runs it: Mosaic takes the forward, the forward recomputed under
    `jax.checkpoint` and both backward passes at the blocks `_flash_tiling`
    answers (1,024 x 1,024 float32 score tiles need more than the default
    16 MiB of scoped VMEM: `_compiler_params`)."""
    q = S((4, 2048, 16, 128), BF16)
    assert fa.use_flash(2048, 2048, 128, 0.0, BF16)
    assert fa._flash_tiling(2048, 2048, 128, BF16) \
        == ((1024, 1024), (512, 512), (1024, 1024))

    def loss(q, k, v):
        out = jax.checkpoint(lambda *a: fa.flash_attention_bshd(
            *a, causal=True))(q, k, v)
        return jnp.sum(out.astype(F32) ** 2)   # the primal output is used

    sharding = SingleDeviceSharding(v5e[0])
    specs = [jax.ShapeDtypeStruct(q.shape, q.dtype, sharding=sharding)] * 3
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        *specs).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]+)"', text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd", "flash_fwd"]
    # the scope a trace books, bare or inside a transform's name
    assert all(re.search(r"[/(]flash[/)]", c) for c in calls)
    assert any("rematted_computation" in c for c in calls)


def test_flash_cross_attention_lengths(v5e):
    """seq_q != seq_kv (bottom-right aligned causal), non-causal too."""
    q, kv = S((2, 256, 4, 128), BF16), S((2, 1024, 4, 128), BF16)
    for causal in (True, False):
        compile_for(v5e[0], lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, causal=causal), q, kv, kv)


def test_flash_with_lse(v5e):
    q = S((1, 512, 4, 128), BF16)
    compile_for(v5e[0], lambda q, k, v: fa.flash_attention_with_lse_bshd(
        q, k, v, causal=True), q, q, q)


def _paged_specs(batch, q_heads, kv_heads, page, pages_per_seq, quant):
    n_pages = batch * pages_per_seq
    pool = S((kv_heads, n_pages, page, 128), I8 if quant else BF16)
    out = [S((batch, q_heads, 128), BF16), pool, pool,
           S((batch, pages_per_seq), I32), S((batch,), I32)]
    if quant:
        sc = S((kv_heads, n_pages, pa._SCALE_LANES), F32)
        out += [sc, sc]
    return out


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("page,pages_per_seq", [(16, 256), (128, 32)])
def test_paged_decode_page_sizes(v5e, page, pages_per_seq, quant):
    """ctx 4096, batch 8, both page sizes, float and int8-KV pools: the
    int8 scale tile (1, 1, 1, 128) that Mosaic rejected as (1, 1, 128) on
    the 993efc6 record lowers."""
    def f(q, kp, vp, tables, lens, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return pa.paged_attention(q, kp, vp, tables, lens, **kw)

    compile_for(v5e[0], f, *_paged_specs(8, 16, 16, page, pages_per_seq,
                                         quant))


def test_paged_decode_at_the_chat_cell_shape(v5e):
    """`gpt3-1.3b.chat-open`: batch 8, 16 + 16 heads of 128, pages of 256,
    8 pages a row, bf16. One block holds all 16 heads of a page (1 MiB of
    K and of V, double-buffered); both products are batched over them."""
    assert compile_for(
        v5e[0], pa.paged_attention,
        *_paged_specs(8, 16, 16, 256, 8, False)) == 1


def test_burst_holds_a_kernel_a_layer_and_copies_no_pool(v5e):
    """The engine's decode burst at the chat cell's cache shape, lowered
    for the v5e: one `tpu_custom_call` a layer, and inside the `while`
    body neither a copy of a pool's size (XLA:TPU lays the operand of an
    `.at[:, pages, slots]` scatter out with the indexed dimensions
    outermost, the custom call pins the default layout: the token write
    scatters rows of the flat view instead) nor anything float32 of the
    mapped context's size."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    layers, rows, burst = 2, 8, 16
    cfg = GPTConfig(vocab_size=512, hidden_size=2048,
                    num_hidden_layers=layers, num_attention_heads=16,
                    intermediate_size=2048,
                    max_position_embeddings=2048)
    model = GPTForCausalLM(cfg)
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    model.eval()
    eng = ServingEngine(model, max_batch=rows, max_seq_len=2048,
                        page_size=256, decode_burst=burst)
    pool = eng.k_pages[0]
    assert pool.shape == (16, 64, 256, 128) and pool.dtype == BF16
    one = SingleDeviceSharding(v5e[0])

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    row = lambda dt: S((rows,), dt)  # noqa: E731
    params, buffers = eng._cached_params()
    fn = eng._get_burst_fn(True, burst)
    text = getattr(fn, "_fn", fn).lower(*described((
        params, buffers, tuple(eng.k_pages), tuple(eng.v_pages), (), (),
        row(jnp.int64), S((rows, eng.pages_per_seq), I32), row(I32),
        row(jnp.bool_), row(I32), row(I32),
        jax.random.key_data(jax.random.key(0)), row(jnp.bool_), row(F32),
        row(I32), row(F32)))).compile().as_text()
    assert text.count("tpu_custom_call") == layers
    body = re.search(r"while\(.*body=%?([\w.\-]+)", text).group(1)
    body = text[text.index(f"%{body} ("):]
    body = body[:body.index("\n}\n")]
    pool_bytes, mapped = pool.size * 2, rows * 2048 * 128
    for shape, op in re.findall(
            r" = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", body):
        dtype, dims = shape[:-1].split("[")
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        assert not (op.startswith("copy") and n * 2 >= pool_bytes), shape
        assert not (dtype == "f32" and n >= mapped), (shape, op)


def test_latent_burst_streams_hit_experts_and_copies_no_stack(v5e,
                                                              monkeypatch):
    """`openpangu-ultra-moe-ep16-l5.decode-closed`'s decode burst (16 rows
    x 16 steps, published widths, one expert layer of 16 held experts),
    lowered for the v5e on the hit path and on the dense one: a
    `tpu_custom_call` an expert layer whose stacked weights arrive in the
    layout they are stored in; inside the `while` body no copy, transpose
    or convert of an array the size of an expert stack; temporaries within
    64 MB of the dense program's."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM

    rows, burst = 16, 16
    zeros = lambda shape, _dtype: jnp.zeros(tuple(shape), BF16)  # noqa: E731
    paddle.nn.initializer.set_global_initializer(zeros, zeros)
    try:
        model = LatentMoEForCausalLM(LatentMoEConfig(
            vocab_size=512, num_hidden_layers=1, first_k_dense_replace=0,
            max_position_embeddings=2048, ep_degree=16, dtype="bfloat16"))
    finally:
        paddle.nn.initializer.set_global_initializer(None, None)
    model.eval()
    experts = model.model.layers[0].mlp.experts
    stack = tuple(experts.w_gate.shape)
    assert stack == (16, 7680, 2048) and experts.w_gate._data.dtype == BF16
    one = SingleDeviceSharding(v5e[0])

    def compiled():
        eng = ServingEngine(model, max_batch=rows, max_seq_len=2048,
                            page_size=256, decode_burst=burst)
        described = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        row = lambda dt: S((rows,), dt)  # noqa: E731
        params, buffers = eng._cached_params()
        fn = eng._get_burst_fn(True, burst)
        return getattr(fn, "_fn", fn).lower(*described((
            params, buffers, tuple(eng.k_pages), (), (), (),
            row(jnp.int64), S((rows, eng.pages_per_seq), I32), row(I32),
            row(jnp.bool_), row(I32), row(I32),
            jax.random.key_data(jax.random.key(0)), row(jnp.bool_),
            row(F32), row(I32), row(F32)))).compile()

    hit = compiled()
    text = hit.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(ln for ln in text.split("\n")
                if 'custom_call_target="tpu_custom_call"' in ln)
    assert "bf16[16,7680,2048]{2,1,0}, bf16[16,7680,2048]{2,1,0}, " \
        "bf16[16,2048,7680]{2,1,0}" in call
    body = re.search(r"while\(.*body=%?([\w.\-]+)", text).group(1)
    body = text[text.index(f"%{body} ("):]
    body = body[:body.index("\n}\n")]
    for name, shape, op in re.findall(
            r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", body):
        n = int(np.prod([int(d) for d in
                         shape[:-1].split("[")[1].split(",") if d]))
        moved = any(k in name or k in op
                    for k in ("copy", "transpose", "convert"))
        assert not (moved and n >= int(np.prod(stack))), (name, shape, op)
    monkeypatch.setattr(eh, "use_hit_path", lambda *a: False)
    dense = compiled()
    assert "tpu_custom_call" not in dense.as_text()
    assert hit.memory_analysis().temp_size_in_bytes \
        <= dense.memory_analysis().temp_size_in_bytes + (64 << 20)


@pytest.mark.parametrize("pages_per_seq", [9, 36], ids=["ring", "full"])
def test_paged_decode_with_a_window_at_the_mixed_cell_shape(v5e,
                                                            pages_per_seq):
    """`trinity-mini-ep8.mixed-closed`: batch 8, 32 query heads on 4 kv
    heads of 128, pages of 256, bf16, the first visible position a row as a
    third prefetched scalar: a window layer's ring of 9 pages, and the same
    over a full layer's 36."""
    def f(q, kp, vp, tables, lens, first):
        return pa.paged_attention(q, kp, vp, tables, lens, first=first)

    assert compile_for(v5e[0], f, *_paged_specs(8, 32, 4, 256, pages_per_seq,
                                                False), S((8,), I32)) == 1


@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window"])
@pytest.mark.parametrize("rows,seq", [(1, 8192), (1, 4096), (1, 9216),
                                      (1, 2048), (1, 1024)])
def test_gqa_prefill_kernel_at_the_mixed_cell_shapes(v5e, rows, seq, window):
    """The grouped-query causal forward at the cell's prefill buckets from
    `GQA_MIN_SEQ` on: 32 query heads on 4 kv heads of 128, bf16, eight
    query heads a kv head as the rows of one [1024, 128] block."""
    assert fa.use_gqa_flash(seq, 128) and not fa.use_gqa_flash(512, 128)
    q, kv = S((rows, seq, 32, 128), BF16), S((rows, seq, 4, 128), BF16)
    assert compile_for(v5e[0], lambda q, k, v: fa.flash_attention_gqa_bshd(
        q, k, v, window=window), q, kv, kv) == 1


@pytest.mark.parametrize("kv_heads,pages_per_seq,extra", [
    (4, 68, ()), (4, 68, ("sink",)), (8, 2, ("first",)),
    (8, 2, ("first", "sink"))],
    ids=["full", "full-sink", "ring", "ring-sink"])
@pytest.mark.parametrize("key", [192, 256], ids=["key192", "stored256"])
def test_paged_decode_with_a_wide_key_at_the_long_cell_shape(
        v5e, kv_heads, pages_per_seq, extra, key):
    """`mimo-v2.5-ep16-l11.long-closed`: batch 8, 64 query heads of 192
    over values of 128, pages of 256, bf16: a full layer's 68 pages a row
    on 4 kv heads (16 query heads a block's row group) and a window layer's
    ring of 2 on 8, with the first visible position a row and a sink a
    head; at the key's own 192 and at the 256 its pool stores."""
    def f(q, kp, vp, tables, lens, *rest):
        kw = dict(zip(extra, rest))
        return pa.paged_attention(q, kp, vp, tables, lens,
                                  scale=192 ** -0.5, **kw)

    n_pages = 8 * pages_per_seq
    more = {"first": S((8,), I32), "sink": S((64,), F32)}
    assert compile_for(
        v5e[0], f, S((8, 64, key), BF16),
        S((kv_heads, n_pages, 256, key), BF16),
        S((kv_heads, n_pages, 256, 128), BF16), S((8, pages_per_seq), I32),
        S((8,), I32), *(more[e] for e in extra)) == 1


@pytest.mark.parametrize("kv_heads,window,sink", [
    (4, None, False), (4, None, True), (8, 128, True), (8, 128, False)],
    ids=["full", "full-sink", "window-sink", "window"])
@pytest.mark.parametrize("seq", [2048, 16384])
def test_gqa_prefill_kernel_at_the_long_cell_shapes(v5e, seq, kv_heads,
                                                    window, sink):
    """The grouped-query causal forward at the cell's smallest and largest
    prefill buckets: 64 query heads of 192 over values of 128, on 4 kv heads
    (16 query heads a kv head, two blocks of 8) or on 8 with a window of 128
    (key blocks of 128, two a query block) and a sink a head."""
    assert fa.use_gqa_flash(seq, 192, 128)
    assert fa._gqa_tiling(seq, 64 // kv_heads, window) \
        == ((8, 128, 2) if window else (8, 512, None))

    def f(q, k, v, *rest):
        return fa.flash_attention_gqa_bshd(q, k, v, window=window,
                                           sink=rest[0] if rest else None)

    assert compile_for(
        v5e[0], f, S((1, seq, 64, 192), BF16), S((1, seq, kv_heads, 192), BF16),
        S((1, seq, kv_heads, 128), BF16),
        *((S((64,), F32),) if sink else ())) == 1


def test_hit_ffn_at_the_long_cell_shape(v5e):
    """8 rows through 16 held experts of 4,096 x 2,048 in bf16: blocks of
    256 columns."""
    assert eh.use_hit_path(8, 4096, 2048, BF16, BF16)
    assert eh._block_width(4096, 2048, 2) == 256
    w = S((16, 4096, 2048), BF16)
    assert compile_for(v5e[0], eh.hit_ffn, S((8, 4096), BF16),
                       S((8, 16), F32), w, w, S((16, 2048, 4096), BF16)) == 1


def test_hit_ffn_at_the_mixed_cell_shape(v5e):
    """8 rows through 16 held experts of 2,048 x 1,024 in bf16: blocks of
    512 columns, three of them double-buffered in 12 MB of VMEM."""
    assert eh.use_hit_path(8, 2048, 1024, BF16, BF16)
    assert eh._block_width(2048, 1024, 2) == 512
    w = S((16, 2048, 1024), BF16)
    assert compile_for(v5e[0], eh.hit_ffn, S((8, 2048), BF16),
                       S((8, 16), F32), w, w, S((16, 1024, 2048), BF16)) == 1


# (tokens, hidden, expert width): the expert cells' prefill buckets, either
# side of the tile's step and beyond one block of tokens
GROUPED = {"decode-closed-512": (512, 7680, 2048),
           "decode-closed-1024": (1024, 7680, 2048),
           "decode-closed-2048": (2048, 7680, 2048),
           "decode-closed-16384": (16384, 7680, 2048),
           "mixed-closed-512": (512, 2048, 1024),
           "mixed-closed-8192": (8192, 2048, 1024),
           "long-closed-2048": (2048, 4096, 2048),
           "long-closed-16384": (16384, 4096, 2048)}


@pytest.mark.parametrize("row", sorted(GROUPED))
def test_grouped_ffn_at_the_expert_cells_prefill_shapes(v5e, row):
    """16 held experts in bf16, tiles of 128 rows: experts of 2,048 x
    1,024 whole in VMEM, of 7,680 x 2,048 in `hit_ffn`'s blocks of 128
    columns; one kernel in the chunks' loop, beyond 2,048 tokens inside the
    loop over blocks of tokens."""
    n, d, f = GROUPED[row]
    assert eg.use_grouped_path(n, d, f, BF16, BF16)
    assert eg.ROW_TILE == 128 and eg.TOKEN_BLOCK == 2048
    assert (6 * d * f * 2 <= eg._WHOLE_EXPERT_VMEM_BYTES) == (d == 2048)
    assert eh._block_width(7680, 2048, 2) == 128
    w = S((16, d, f), BF16)
    assert compile_for(v5e[0], eg.grouped_ffn, S((n, d), BF16),
                       S((n, 16), F32), w, w, S((16, f, d), BF16),
                       S((n,), jnp.bool_)) == 1


def test_latent_prefill_groups_its_pairs_and_copies_no_stack(v5e,
                                                             monkeypatch):
    """`openpangu-ultra-moe-ep16-l5.decode-closed`'s prefill of 2 x 1,024
    tokens (published widths, one expert layer of 16 held experts), lowered
    for the v5e on the grouped path and on the dense one: a
    `tpu_custom_call` an expert layer whose stacked weights arrive in the
    layout they are stored in; no copy, transpose or convert of an array
    the size of an expert stack anywhere in the program; temporaries within
    64 MB of the dense program's (whose own are its two float32 `[2048,
    16 x 2048]` blocks; the grouped form's are a chunk's, and the
    program's largest lie elsewhere)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM

    nb, bucket = 2, 1024
    zeros = lambda shape, _dtype: jnp.zeros(tuple(shape), BF16)  # noqa: E731
    paddle.nn.initializer.set_global_initializer(zeros, zeros)
    try:
        model = LatentMoEForCausalLM(LatentMoEConfig(
            vocab_size=512, num_hidden_layers=1, first_k_dense_replace=0,
            max_position_embeddings=2048, ep_degree=16, dtype="bfloat16"))
    finally:
        paddle.nn.initializer.set_global_initializer(None, None)
    model.eval()
    stack = tuple(model.model.layers[0].mlp.experts.w_gate.shape)
    assert stack == (16, 7680, 2048)
    one = SingleDeviceSharding(v5e[0])

    def compiled():
        eng = ServingEngine(model, max_batch=16, max_seq_len=2048,
                            page_size=256, decode_burst=16)
        described = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        row = lambda dt: S((nb,), dt)  # noqa: E731
        params, buffers = eng._cached_params()
        fn = eng._get_prefill_fn(nb, bucket, True)
        return getattr(fn, "_fn", fn).lower(*described((
            params, buffers, S((nb, bucket), jnp.int64), row(I32),
            jax.random.key_data(jax.random.key(0)), row(jnp.bool_),
            row(F32), row(I32), row(F32)))).compile()

    grouped = compiled()
    text = grouped.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    call = next(ln for ln in text.split("\n")
                if 'custom_call_target="tpu_custom_call"' in ln)
    assert "bf16[16,7680,2048]{2,1,0}, bf16[16,7680,2048]{2,1,0}, " \
        "bf16[16,2048,7680]{2,1,0}" in call
    for name, shape, op in re.findall(
            r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", text):
        n = int(np.prod([int(d) for d in
                         shape[:-1].split("[")[1].split(",") if d]))
        moved = any(k in name or k in op
                    for k in ("copy", "transpose", "convert"))
        assert not (moved and n >= int(np.prod(stack))), (name, shape, op)
    monkeypatch.setattr(eg, "use_grouped_path", lambda *a: False)
    dense = compiled()
    assert "tpu_custom_call" not in dense.as_text()
    assert grouped.memory_analysis().temp_size_in_bytes \
        <= dense.memory_analysis().temp_size_in_bytes + (64 << 20)


def test_paged_decode_gqa(v5e):
    """32 query heads over 4 kv heads (group 8)."""
    compile_for(v5e[0], pa.paged_attention,
                *_paged_specs(8, 32, 4, 16, 256, False))


@pytest.mark.parametrize("weight_dtype,group_size,block,k", [
    ("int8", -1, 128, 2048), ("int8", -1, 512, 2048),
    ("int4", -1, 128, 2048), ("int4", -1, 512, 2048),
    # grouped scales: an 8-row scale tile (512 / 64) ...
    ("int8", 64, 512, 2048), ("int4", 64, 512, 2048),
    # ... or one k block, whose scale tile is the whole scale array
    ("int8", 128, 512, 512), ("int4", 64, 128, 128)])
@pytest.mark.parametrize("m", [8, qm._MAX_M])
def test_quant_matmul_fused_corners(v5e, m, weight_dtype, group_size,
                                    block, k):
    n = 8192
    assert qm.supports(m, k, n, weight_dtype, group_size, block, block)
    rows = k // 2 if weight_dtype == "int4" else k
    scales = S((n,), F32) if group_size == -1 \
        else S((k // group_size, n), F32)

    def f(x, qw, sc):
        return qm.quant_matmul_fused(x, qw, sc, weight_dtype, group_size,
                                     block, block)

    compile_for(v5e[0], f, S((m, k), BF16), S((rows, n), I8), scales)


def test_quant_matmul_grouped_scale_tiles_are_gated():
    """A [block_k // group_size, block_n] scale tile of 2 or 4 rows is
    refused by Mosaic ("last two dimensions of your block shape are
    divisible by 8 and 128"): supports() says no, the dispatch takes the
    XLA dequant expression."""
    assert not qm.supports(8, 2048, 8192, "int8", 64, 128, 128)
    assert not qm.supports(8, 2048, 8192, "int8", 128, 512, 512)
    assert not qm.supports(8, 2048, 8192, "int4", 128, 256, 256)
    assert qm.supports(8, 2048, 8192, "int8", 64, 256, 512)


# ---------------------------------------------------------------------------
# kernels inside shard_map over the four devices
# ---------------------------------------------------------------------------


def _lower_on_mesh(mesh, fn, specs_and_pspecs):
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype,
                                  sharding=NamedSharding(mesh, p))
             for s, p in specs_and_pspecs]
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("quant,page,pages_per_seq", [
    (False, 16, 256), (True, 16, 256), (False, 256, 8), (False, 128, 16)],
    ids=["bf16", "int8kv", "bf16-page256", "bf16-page128"])
def test_paged_kernel_inside_tp4_shard_map(v5e, quant, page, pages_per_seq):
    """The TP decode step of models/paged_step.py with the kernel lowered,
    by the dispatch's own choice (small pages and int8 pools: a mapped
    context of 4,096, above the crossover; the chat cell's pages of 256
    and chip_smoke.py's tp=4 engine at pages of 128: from the page size
    alone, each chip's step over its 4 heads): under
    `check_vma=True` (jax.shard_map's default) the kernel's out_shape is
    refused at trace time; the step states check_vma=False."""
    from paddle_tpu.models.paged_step import paged_attention_step
    from paddle_tpu.tensor import Tensor, as_array

    mesh = Mesh(np.asarray(v5e), ("tp",))
    batch, heads = 8, 16
    q = S((batch, 1, heads, 128), BF16)
    _, pool, _, tables, lens, *scales = _paged_specs(
        batch, heads, heads, page, pages_per_seq, quant)

    def step(q, k, v, kp, vp, tables, lens, *scales):
        out, cache = paged_attention_step(
            Tensor(q), Tensor(k), Tensor(v), (kp, vp, *scales), tables,
            lens, mesh=mesh)
        return as_array(out), tuple(as_array(c) for c in cache)

    heads_p, pool_p, rep = P(None, None, "tp"), P("tp"), P()
    text = _lower_on_mesh(mesh, step, [
        (q, heads_p), (q, heads_p), (q, heads_p), (pool, pool_p),
        (pool, pool_p), (tables, rep), (lens, rep),
        *[(s, pool_p) for s in scales]])
    assert text.count("tpu_custom_call") >= 1


def test_flash_kernel_inside_check_vma_shard_map(v5e):
    """distributed/pipeline.py keeps check_vma=True on its shard_maps (the
    schedule's collectives and autodiff need the vma types); a stage may
    hold the flash kernel, whose out_shape carries the vma of its
    operands (kernels/flash_attention._sds)."""
    mesh = Mesh(np.asarray(v5e), ("pp",))

    def stage(q, k, v):
        return jax.shard_map(
            lambda q, k, v: fa.flash_attention_bshd(q, k, v, causal=True),
            mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
            axis_names=frozenset({"pp"}), check_vma=True)(q, k, v)

    q = S((4, 512, 4, 128), BF16)
    text = _lower_on_mesh(mesh, stage, [(q, P("pp"))] * 3)
    assert text.count("tpu_custom_call") >= 1
