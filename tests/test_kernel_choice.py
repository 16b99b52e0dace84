"""Which implementation each operation with a Pallas kernel takes, from what
the call can see: shapes, dtypes, the pool's type and `_interpret()`. One
table an operation; the implementation is the one TRACED, counted as
`chip_smoke.count_calls` counts (the chosen function is replaced by a
recorder), never read back from a flag. Nothing is computed: every call is
traced under `jax.eval_shape`.

The rows hold the four benchmark cells' own shapes and both sides of every
boundary the choice has (`paged_attention._KERNEL_MIN_PAGE`,
`_XLA_DECODE_MAX_CTX`, `flash_attention._PALLAS_MIN_SEQ` /
`_PALLAS_SMALL_BLOCK_MIN_SEQ` / `GQA_MIN_SEQ`, `expert_hit._HIT_MAX_TOKENS`,
`expert_grouped._GROUPED_MIN_TOKENS`, the kernels' `supports`)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import config as _config
from paddle_tpu.incubate.distributed.models.moe import expert_share
from paddle_tpu.kernels import expert_grouped as eg
from paddle_tpu.kernels import expert_hit as eh
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import quant_matmul as qm
from paddle_tpu.kernels import rms_norm as rn
from paddle_tpu.tensor import Tensor, as_array

sdpa_mod = importlib.import_module("paddle_tpu.nn.functional.attention")
norm_mod = importlib.import_module("paddle_tpu.nn.functional.norm")

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
KERNEL, GATHER = "paged_attention", "paged_attention_xla"
FLASH, XLA = "flash_attention_bshd", "_sdpa_reference"


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def record(monkeypatch, taken, module, name, result):
    """Replace `module.name` by a recorder that returns `result(*args)`."""
    def recorder(*args, **kw):
        taken.append(name)
        return result(*args)

    monkeypatch.setattr(module, name, recorder)


# ---------------------------------------------------------------------------
# a grouped-query prefill: GatedGQAttention asks flash_attention.use_gqa_flash
# ---------------------------------------------------------------------------

GQA_FLASH, GQA_XLA = "flash_attention_gqa_bshd", "gqa_attention"

# (rows, prompt bucket, head size, window) -> compiled and interpreted alike
GQA_PREFILL = {
    # trinity-mini-ep8.mixed-closed's token buckets either side of the
    # threshold (`GQA_MIN_SEQ`, from the sweep on the chip), window and
    # full layers
    "mixed-closed-256-window": ((1, 256, 128, 2048), GQA_XLA),
    "mixed-closed-512-full": ((1, 512, 128, None), GQA_XLA),
    "mixed-closed-1024-window": ((1, 1024, 128, 2048), GQA_FLASH),
    "mixed-closed-2048-window": ((1, 2048, 128, 2048), GQA_FLASH),
    "mixed-closed-2048-full": ((1, 2048, 128, None), GQA_FLASH),
    "mixed-closed-4096-window": ((1, 4096, 128, 2048), GQA_FLASH),
    "mixed-closed-4096-full": ((1, 4096, 128, None), GQA_FLASH),
    "mixed-closed-8192-window": ((1, 8192, 128, 2048), GQA_FLASH),
    "cap-9216-full": ((1, 9216, 128, None), GQA_FLASH),
    # a head size or a length the kernel does not tile
    "head-64": ((1, 4096, 64, 2048), GQA_XLA),
    "length-4352": ((1, 4352, 128, 2048), GQA_XLA),
}


@pytest.mark.parametrize("row", sorted(GQA_PREFILL))
def test_gqa_prefill_choice(monkeypatch, row):
    from paddle_tpu.models import AfmoeConfig, latent_moe

    (b, s, d, window), want = GQA_PREFILL[row]
    taken = []
    record(monkeypatch, taken, fa, GQA_FLASH,
           lambda q, *a: q)
    record(monkeypatch, taken, latent_moe, GQA_XLA,
           lambda q, *a: q.reshape(q.shape[0], q.shape[1], -1))
    cfg = AfmoeConfig(num_hidden_layers=1, hidden_size=256, head_dim=d,
                      num_attention_heads=8, num_key_value_heads=2)
    zeros = lambda shape, _dtype: jnp.zeros(tuple(shape), BF16)  # noqa: E731
    paddle.nn.initializer.set_global_initializer(zeros, zeros)
    try:
        mixer = latent_moe.GatedGQAttention(cfg, window)
    finally:
        paddle.nn.initializer.set_global_initializer(None, None)
    jax.eval_shape(lambda x: as_array(mixer(Tensor(x))),
                   S((b, s, 256), BF16))
    assert taken == [want]


# (prompt bucket, window layer?) -> (what attends, its window, a sink?):
# mimo-v2.5-ep16-l11.long-closed's token buckets, 64 query heads of 192
# over values of 128, on 8 kv heads with a window of 128 and a sink or on 4
# with neither; and a bucket under the kernel's first length
SINK_PREFILL = {
    "long-closed-2048-window": ((2048, True), (GQA_FLASH, 128, True)),
    "long-closed-2048-full": ((2048, False), (GQA_FLASH, None, False)),
    "long-closed-4096-window": ((4096, True), (GQA_FLASH, 128, True)),
    "long-closed-8192-full": ((8192, False), (GQA_FLASH, None, False)),
    "long-closed-16384-window": ((16384, True), (GQA_FLASH, 128, True)),
    "long-closed-16384-full": ((16384, False), (GQA_FLASH, None, False)),
    "under-the-kernel-512-window": ((512, True), (GQA_XLA, 128, True)),
    "under-the-kernel-512-full": ((512, False), (GQA_XLA, None, False)),
}


@pytest.mark.parametrize("row", sorted(SINK_PREFILL))
def test_sink_gqa_prefill_choice(monkeypatch, row):
    from paddle_tpu.models import MiMoV2Config, latent_moe

    (s, window), want = SINK_PREFILL[row]
    taken = []

    def attend(name):
        def f(q, k, v, window=None, offset=0, sink=None, **kw):
            assert (q.shape[-1], k.shape[-1], v.shape[-1]) == (192,
                                                               192, 128)
            assert k.shape[2] == (8 if window else 4)
            taken.append((name, window, sink is not None))
            return q[..., :128].reshape(q.shape[0], q.shape[1], -1)
        return f

    monkeypatch.setattr(fa, GQA_FLASH, attend(GQA_FLASH))
    monkeypatch.setattr(latent_moe, GQA_XLA, attend(GQA_XLA))
    cfg = MiMoV2Config(num_hidden_layers=1, hidden_size=256)
    zeros = lambda shape, _dtype: jnp.zeros(tuple(shape), BF16)  # noqa: E731
    paddle.nn.initializer.set_global_initializer(zeros, zeros)
    try:
        mixer = latent_moe.SinkGQAttention(cfg, window)
    finally:
        paddle.nn.initializer.set_global_initializer(None, None)
    jax.eval_shape(lambda x: as_array(mixer(Tensor(x))),
                   S((1, s, 256), BF16))
    assert taken == [want]


# ---------------------------------------------------------------------------
# decode attention over pages: paged_attention_dispatch
# ---------------------------------------------------------------------------

# (rows, q heads, kv heads, page, pages a row, pool type) -> off interpret mode
PAGED = {
    # gpt3-1.3b.chat-open: 8 slots, 16 heads of 128, pages of 256, bf16
    "chat-open": ((8, 16, 16, 256, 8, BF16), KERNEL),
    "page128-under-crossover": ((8, 16, 16, 128, 8, BF16), KERNEL),
    "page127-under-crossover": ((8, 16, 16, 127, 8, BF16), GATHER),
    "float32-page256": ((2, 4, 4, 256, 4, F32), KERNEL),
    "gqa-tp-shard-page256": ((8, 8, 1, 256, 8, BF16), KERNEL),
    "page16-mapped2048": ((8, 16, 16, 16, 128, BF16), GATHER),
    "page16-mapped2064": ((8, 16, 16, 16, 129, BF16), KERNEL),
    "int8-page16-mapped2048": ((8, 16, 16, 16, 128, I8), GATHER),
    "int8-page16-mapped2064": ((8, 16, 16, 16, 129, I8), KERNEL),
    "int8-page128-mapped2048": ((8, 16, 16, 128, 16, I8), GATHER),
    "int8-page128-mapped2176": ((8, 16, 16, 128, 17, I8), KERNEL),
    # trinity-mini-ep8.mixed-closed: 8 slots, 32 query heads on 4 kv heads
    # of 128, pages of 256: a full layer's 36 pages a row, a window layer's
    # ring of 9 (with the first visible position a row)
    "mixed-closed-full": ((8, 32, 4, 256, 36, BF16), KERNEL),
    "mixed-closed-window": ((8, 32, 4, 256, 9, BF16, "first"), KERNEL),
    "window-page16-ring": ((8, 32, 4, 16, 9, BF16, "first"), GATHER),
    "window-page16-mapped2064": ((8, 32, 4, 16, 129, BF16, "first"), KERNEL),
    # mimo-v2.5-ep16-l11.long-closed: 8 slots, 64 query heads, keys of 192
    # stored 256 wide over values of 128 ("wide"), pages of 256: a full
    # layer's 68 pages a row on 4 kv heads, a window layer's ring of 2 on 8
    # with the first visible position and a sink a head
    "long-closed-full": ((8, 64, 4, 256, 68, BF16, "wide"), KERNEL),
    "long-closed-full-sink": ((8, 64, 4, 256, 68, BF16, "wide", "sink"),
                              KERNEL),
    "long-closed-window": ((8, 64, 8, 256, 2, BF16, "wide", "first", "sink"),
                           KERNEL),
    "long-closed-window-no-sink": ((8, 64, 8, 256, 2, BF16, "wide", "first"),
                                   KERNEL),
    "wide-page16-ring": ((8, 64, 8, 16, 9, BF16, "wide", "first", "sink"),
                         GATHER),
}


def _paged_taken(monkeypatch, row, interpret):
    b, qh, kvh, page, pages_per_seq, pool_dtype, *more = row
    taken = []
    monkeypatch.setattr(pa, "_interpret", lambda: interpret)
    def recorder(name):
        def attend(q, *a, **kw):
            # whichever is taken is handed the window and the sink
            assert {k for k in kw if k in ("first", "sink")} \
                == set(more) - {"wide"}
            taken.append(name)
            return q
        return attend

    for name in (KERNEL, GATHER):
        monkeypatch.setattr(pa, name, recorder(name))
    key = 256 if "wide" in more else 128
    pool = S((kvh, b * pages_per_seq, page, key), pool_dtype)
    values = S((kvh, b * pages_per_seq, page, 128), pool_dtype)
    scales = S((kvh, b * pages_per_seq, pa._SCALE_LANES), F32)
    kw = dict(k_scales=scales, v_scales=scales) if pool_dtype == I8 else {}
    if "first" in more:
        kw["first"] = S((b,), I32)
    if "sink" in more:
        kw["sink"] = S((qh,), F32)
    jax.eval_shape(
        lambda q, kp, vp, tables, lens, **kw: pa.paged_attention_dispatch(
            q, kp, vp, tables, lens, **kw),
        S((b, qh, key), BF16), pool, values, S((b, pages_per_seq), I32),
        S((b,), I32), **kw)
    return taken


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["compiled", "interpret"])
@pytest.mark.parametrize("row", sorted(PAGED))
def test_paged_decode_choice(monkeypatch, row, interpret):
    args, want = PAGED[row]
    # interpret mode (the CPU) takes the reference in every row
    assert _paged_taken(monkeypatch, args, interpret) \
        == [GATHER if interpret else want]


def _burst_traces(monkeypatch, model, page):
    """The attention functions one decode burst of `model`'s engine traces,
    off interpret mode."""
    from paddle_tpu.inference import ServingEngine

    eng = ServingEngine(model, max_batch=2, max_seq_len=128, page_size=page,
                        decode_burst=2)
    taken = []
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    for name in (KERNEL, GATHER):
        record(monkeypatch, taken, pa, name, lambda q, *a: q)
    record(monkeypatch, taken, pa, "paged_latent_attention_xla",
           lambda q, pages, tables, lens, width, scale: q[..., :width])
    params, buffers = eng._cached_params()
    row = lambda dt: S((2,), dt)  # noqa: E731
    jax.eval_shape(
        eng._get_burst_fn(True, 2).__wrapped__, params, buffers,
        tuple(eng.k_pages), tuple(eng.v_pages or ()), (), (),
        row(jnp.int64), S((2, eng.pages_per_seq), I32), row(I32),
        row(jnp.bool_), row(I32), row(I32),
        jax.random.key_data(jax.random.key(0)), row(jnp.bool_), row(F32),
        row(I32), row(F32))
    return taken


@pytest.mark.parametrize("kind,page,want", [
    ("gpt", 128, KERNEL), ("gpt", 16, GATHER),
    # openpangu-ultra-moe-ep16-l5.decode-closed: the latent decoder's own
    # gather over its one pool a layer, whatever the page
    ("latent", 128, "paged_latent_attention_xla")])
def test_engine_burst_traces_one_choice_a_layer(monkeypatch, kind, page,
                                                want):
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   LatentMoEConfig, LatentMoEForCausalLM)

    paddle.seed(0)
    if kind == "gpt":
        cfg = GPTConfig.tiny(seq=128)
        model = GPTForCausalLM(cfg)
    else:
        cfg = LatentMoEConfig.tiny()
        model = LatentMoEForCausalLM(cfg)
    model.eval()
    assert _burst_traces(monkeypatch, model, page) \
        == [want] * cfg.num_hidden_layers


# ---------------------------------------------------------------------------
# one chip's share of an expert layer: expert_hit.use_hit_path, then
# expert_grouped.use_grouped_path
# ---------------------------------------------------------------------------

HIT, GROUPED, DENSE = "hit_ffn", "grouped_ffn", "share_ffn"

# (tokens, hidden, expert width, held, type) -> off interpret mode, no grad
EXPERTS = {
    # openpangu-ultra-moe-ep16-l5.decode-closed: a decode step of 16 rows,
    # and its prefills of 256 to 16 x 1,024 tokens
    "decode-closed-step": ((16, 7680, 2048, 16, BF16), HIT),
    "decode-closed-prefill-256": ((256, 7680, 2048, 16, BF16), DENSE),
    "decode-closed-prefill-512": ((512, 7680, 2048, 16, BF16), GROUPED),
    "decode-closed-prefill-1024": ((1024, 7680, 2048, 16, BF16), GROUPED),
    "decode-closed-prefill-2048": ((2048, 7680, 2048, 16, BF16), GROUPED),
    "decode-closed-prefill-16384": ((16384, 7680, 2048, 16, BF16), GROUPED),
    # trinity-mini-ep8.mixed-closed: a decode step of 8 rows, and its
    # prefills of one prompt, 256 to 8,192 tokens, experts of 2,048 x 1,024
    "mixed-closed-step": ((8, 2048, 1024, 16, BF16), HIT),
    "mixed-closed-prefill-256": ((256, 2048, 1024, 16, BF16), DENSE),
    "mixed-closed-prefill-512": ((512, 2048, 1024, 16, BF16), GROUPED),
    "mixed-closed-prefill-2048": ((2048, 2048, 1024, 16, BF16), GROUPED),
    "mixed-closed-prefill-8192": ((8192, 2048, 1024, 16, BF16), GROUPED),
    # mimo-v2.5-ep16-l11.long-closed: a decode step of 8 rows, and its
    # prefills of one prompt, 2,048 to 16,384 tokens, experts of 4,096 x 2,048
    "long-closed-step": ((8, 4096, 2048, 16, BF16), HIT),
    "long-closed-prefill-2048": ((2048, 4096, 2048, 16, BF16), GROUPED),
    "long-closed-prefill-16384": ((16384, 4096, 2048, 16, BF16), GROUPED),
    "one-token": ((1, 7680, 2048, 16, BF16), HIT),
    "tokens-64": ((64, 7680, 2048, 16, BF16), HIT),
    # between the two kernels the dense products: every held expert is
    # hit, and one read of their weights is all the dense form costs
    "tokens-65": ((65, 7680, 2048, 16, BF16), DENSE),
    "tokens-511": ((511, 7680, 2048, 16, BF16), DENSE),
    "float32": ((16, 1024, 512, 4, F32), HIT),
    "float32-prefill": ((512, 1024, 512, 4, F32), GROUPED),
    # widths Mosaic would pad: the tiny model's
    "hidden-48": ((16, 48, 256, 4, F32), DENSE),
    "width-24": ((16, 128, 24, 4, F32), DENSE),
    "hidden-48-prefill": ((512, 48, 256, 4, F32), DENSE),
    "width-24-prefill": ((512, 128, 24, 4, F32), DENSE),
}


def _experts_taken(monkeypatch, row, interpret, grad=False):
    n, d, f, held, dtype = row
    taken = []
    monkeypatch.setattr(eh, "_interpret", lambda: interpret)
    monkeypatch.setattr(eg, "_interpret", lambda: interpret)
    record(monkeypatch, taken, eh, HIT, lambda x, *a: x)
    record(monkeypatch, taken, eg, GROUPED, lambda x, *a: x)
    record(monkeypatch, taken, expert_share, DENSE, lambda x, *a: x)
    zeros = lambda shape, _dtype: jnp.zeros(tuple(shape), dtype)  # noqa: E731
    paddle.nn.initializer.set_global_initializer(zeros, zeros)
    try:
        layer = expert_share.ExpertShareLayer(d, f, 16 * held, 8,
                                              ep_degree=16)
    finally:
        paddle.nn.initializer.set_global_initializer(None, None)

    def call(x, live):
        with (paddle.enable_grad if grad else paddle.no_grad)():
            return as_array(layer(Tensor(x), live=live))

    jax.eval_shape(call, S((n, d), dtype), S((n,), jnp.bool_))
    return taken


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["compiled", "interpret"])
@pytest.mark.parametrize("row", sorted(EXPERTS))
def test_expert_share_choice(monkeypatch, row, interpret):
    args, want = EXPERTS[row]
    # interpret mode (the CPU) takes the dense reference in every row
    assert _experts_taken(monkeypatch, args, interpret) \
        == [DENSE if interpret else want]


@pytest.mark.parametrize("row", ["decode-closed-step",
                                 "decode-closed-prefill-1024",
                                 "mixed-closed-prefill-8192"])
def test_a_call_that_may_record_a_gradient_keeps_the_dense_products(
        monkeypatch, row):
    """The kernels have no backward; the engine's programs and `generate`
    trace under `no_grad`."""
    args, want = EXPERTS[row]
    assert want != DENSE
    assert _experts_taken(monkeypatch, args, False, grad=True) == [DENSE]


def test_the_expert_choice_reads_no_flag(monkeypatch):
    """Shapes, types and `_interpret()` alone: a registry without a single
    flag chooses as the full one does, and never both kernels."""
    monkeypatch.setattr(eh, "_interpret", lambda: False)
    monkeypatch.setattr(eg, "_interpret", lambda: False)
    monkeypatch.setattr(_config, "_FLAGS", {})
    for (n, d, f, _held, dtype), want in EXPERTS.values():
        assert eh.use_hit_path(n, d, f, dtype, dtype) == (want == HIT)
        assert eg.use_grouped_path(n, d, f, dtype, dtype) \
            == (want == GROUPED)


# ---------------------------------------------------------------------------
# scaled_dot_product_attention: flash_attention.use_flash
# ---------------------------------------------------------------------------

# (batch, s_q, s_kv, heads, head_dim, training, dropout_p, mask) -> traced;
# a ninth entry is the operands' type where it is not bf16
SDPA = {
    # gpt3-1.3b-l12.train-2k: 4 x 2,048 tokens, 16 heads of 128, training
    "train-2k": ((4, 2048, 2048, 16, 128, True, 0.0, False), FLASH),
    # both sides of the crossover at `_flash_tiling`'s large blocks (PR 34's
    # microbenches: 1,024 is the shortest length measured, training and not,
    # causal and not)
    "train-512": ((1, 512, 512, 2, 128, True, 0.0, False), XLA),
    "train-1023": ((1, 1023, 1023, 2, 128, True, 0.0, False), XLA),
    "train-1024": ((1, 1024, 1024, 2, 128, True, 0.0, False), FLASH),
    "eval-512": ((1, 512, 512, 2, 128, False, 0.0, False), XLA),
    "eval-1023": ((1, 1023, 1023, 2, 128, False, 0.0, False), XLA),
    "eval-1024": ((1, 1024, 1024, 2, 128, False, 0.0, False), FLASH),
    "eval-8192": ((1, 8192, 8192, 2, 128, False, 0.0, False), FLASH),
    # where only 128 x 128 blocks serve (a length 256 x 512 does not divide,
    # float32 operands) the kernels start at 4,096, as before PR 34
    "train-2176": ((1, 2176, 2176, 2, 128, True, 0.0, False), XLA),
    "train-4224": ((1, 4224, 4224, 2, 128, True, 0.0, False), FLASH),
    "train-2k-f32": ((1, 2048, 2048, 2, 128, True, 0.0, False, F32), XLA),
    "eval-4096-f32": ((1, 4096, 4096, 2, 128, False, 0.0, False, F32),
                      FLASH),
    # fa.supports false: a head Mosaic cannot tile, a ragged key length
    "head-dim-64": ((1, 4096, 4096, 2, 64, True, 0.0, False), XLA),
    "kv-4100": ((1, 4096, 4100, 2, 128, False, 0.0, False), XLA),
    "masked-4096": ((1, 4096, 4096, 2, 128, False, 0.0, True), XLA),
    # dropout: the in-kernel path waits behind FLAGS_flash_dropout_kernel
    "train-4096-dropout": ((1, 4096, 4096, 2, 128, True, 0.1, False), XLA),
    "eval-4096-dropout": ((1, 4096, 4096, 2, 128, False, 0.1, False),
                          FLASH),
}


def _sdpa_traced(monkeypatch, row, causal=True):
    b, s_q, s_kv, h, d, training, dropout_p, masked = row[:8]
    dtype = row[8] if len(row) > 8 else BF16
    taken = []
    record(monkeypatch, taken, fa, FLASH, lambda q, *a: q)
    record(monkeypatch, taken, sdpa_mod, XLA, lambda q, *a: q)

    def call(q, k, v, *mask):
        return as_array(sdpa_mod.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v),
            attn_mask=Tensor(mask[0]) if mask else None,
            dropout_p=dropout_p, is_causal=causal and not masked,
            training=training))

    kv = S((b, s_kv, h, d), dtype)
    mask = (S((1, 1, s_q, s_kv), jnp.bool_),) if masked else ()
    jax.eval_shape(call, S((b, s_q, h, d), dtype), kv, kv, *mask)
    return taken


@pytest.mark.parametrize("row", sorted(SDPA))
def test_sdpa_choice(monkeypatch, row):
    args, want = SDPA[row]
    assert _sdpa_traced(monkeypatch, args) == [want]


@pytest.mark.parametrize("row", ["train-2k", "train-1023", "train-1024",
                                 "eval-1024", "train-2176", "eval-4096-f32"])
def test_sdpa_choice_is_the_same_without_the_causal_mask(monkeypatch, row):
    """The non-causal call was measured too (encoders, cross-attention, a
    ring's blocks): the kernels lead from 1,024 on there as well, so the
    choice does not read `is_causal`."""
    args, want = SDPA[row]
    assert _sdpa_traced(monkeypatch, args, causal=False) == [want]


@pytest.mark.parametrize("flag,value,row,want", [
    ("FLAGS_flash_dropout_kernel", True, "train-4096-dropout", FLASH),
    ("FLAGS_use_pallas_kernels", False, "train-2k", XLA),
    ("FLAGS_use_pallas_kernels", False, "eval-8192", XLA)])
def test_sdpa_choice_under_the_two_flags_left(monkeypatch, flag, value, row,
                                              want):
    monkeypatch.setattr(_config._FLAGS[flag], "value", value)
    assert _sdpa_traced(monkeypatch, SDPA[row][0]) == [want]


# (s_q, s_kv, head_dim, dtype) -> the blocks of the forward, the dK/dV pass
# and the dQ pass: `flash_attention._flash_tiling`, a row a shape class
LARGE = ((1024, 1024), (512, 512), (1024, 1024))
SMALL = ((128, 128),) * 3
TILING = {
    "train-2k": ((2048, 2048, 128, BF16), LARGE),
    "long-8192": ((8192, 8192, 128, BF16), LARGE),
    "fp16-2048": ((2048, 2048, 128, jnp.float16), LARGE),
    # the measured blocks halved to the largest that divide the lengths
    "halved-1536": ((1536, 1536, 128, BF16), ((512, 512),) * 3),
    "halved-1280": ((1280, 1536, 128, BF16), ((256, 512),) * 3),
    # cross-attention: the query and the key side fit their own length
    "cross-512x1024": ((512, 1024, 128, BF16),
                       ((512, 1024), (512, 512), (512, 1024))),
    "cross-4096x512": ((4096, 512, 128, BF16),
                       ((1024, 512), (512, 512), (1024, 512))),
    # a length that 256 queries x 512 keys do not divide: today's blocks
    "ragged-2176": ((2176, 2176, 128, BF16), SMALL),
    "ragged-keys-2304": ((2048, 2304, 128, BF16), SMALL),
    "short-128": ((128, 128, 128, BF16), SMALL),
    # what the microbench did not see: today's blocks
    "f32-2048": ((2048, 2048, 128, F32), SMALL),
    "head-256": ((2048, 2048, 256, BF16), SMALL),
}


@pytest.mark.parametrize("row", sorted(TILING))
def test_flash_tiling(row):
    (s_q, s_kv, d, dtype), want = TILING[row]
    assert fa._flash_tiling(s_q, s_kv, d, dtype) == want
    # every shape the kernels took at 128 x 128 they still take, and the
    # blocks answered tile it
    assert fa.supports(s_q, s_kv, d)
    assert all(fa.supports(s_q, s_kv, d, bq, bk) for bq, bk in want)
    # the streamed backward starts where the forward's choice does
    assert fa._bwd_use_xla(s_q, want) \
        == (not fa.use_flash(s_q, s_kv, d, 0.0, dtype))


# ---------------------------------------------------------------------------
# rms_norm: rms_norm.supports
# ---------------------------------------------------------------------------

# (leading shape, columns, dtype, weight) -> the kernel traced?
RMS = {
    # the latent decoder's decode step and a 16 x 1,024 prefill, hidden 7,680
    "latent-decode": (((16, 1), 7680, BF16, True), True),
    "latent-prefill": (((16, 1024), 7680, BF16, True), True),
    "f32-2048": (((8, 128), 2048, F32, True), True),
    "cols-8192": (((8192,), 8192, BF16, True), True),
    "cols-8320": (((8192,), 8320, BF16, True), False),   # beyond 8,192
    "cols-100": (((256,), 100, F32, True), False),       # not a lane tile
    "rows-300": (((300,), 2048, F32, True), False),      # not whole blocks
    "no-weight": (((256,), 2048, F32, False), False),
}


@pytest.mark.parametrize("row", sorted(RMS))
def test_rms_norm_choice(monkeypatch, row):
    (lead, cols, dtype, weighted), want = RMS[row]
    taken = []
    record(monkeypatch, taken, rn, "rms_norm", lambda x, *a: x)
    assert rn.supports(int(np.prod(lead)), cols,
                       itemsize=jnp.dtype(dtype).itemsize) \
        == (want or not weighted)
    jax.eval_shape(
        lambda x, *w: as_array(norm_mod.rms_norm(
            Tensor(x), Tensor(w[0]) if w else None)),
        S(lead + (cols,), dtype), *([S((cols,), dtype)] if weighted else []))
    assert taken == (["rms_norm"] if want else [])


def test_rms_norm_takes_the_reference_without_pallas(monkeypatch):
    monkeypatch.setattr(_config._FLAGS["FLAGS_use_pallas_kernels"], "value",
                        False)
    taken = []
    record(monkeypatch, taken, rn, "rms_norm", lambda x, *a: x)
    jax.eval_shape(
        lambda x, w: as_array(norm_mod.rms_norm(Tensor(x), Tensor(w))),
        S((16, 7680), BF16), S((7680,), BF16))
    assert taken == []


# ---------------------------------------------------------------------------
# weight-only quantized linear: FLAGS_quant_matmul and quant_matmul.supports
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,n,want", [
    (None, 256, "quant_matmul_xla"), ("xla", 256, "quant_matmul_xla"),
    ("fused", 256, "quant_matmul_fused"),
    ("fused", 96, "quant_matmul_xla"),      # 96 columns tile no lane
    ("auto", 256, "quant_matmul_xla")])     # the value that went with the tuner
def test_quant_matmul_choice(monkeypatch, mode, n, want):
    if mode is not None:
        monkeypatch.setattr(_config._FLAGS["FLAGS_quant_matmul"], "value",
                            mode)
    taken = []
    for name in ("quant_matmul_xla", "quant_matmul_fused"):
        record(monkeypatch, taken, qm, name,
               lambda x, qw, *a: jnp.zeros((x.shape[0], qw.shape[1]),
                                           x.dtype))
    out = jax.eval_shape(
        lambda x, qw, sc: qm.quant_matmul_dispatch(x, qw, sc, "int8", -1),
        S((2, 4, 128), BF16), S((128, n), I8), S((n,), F32))
    assert taken == [want] and out.shape == (2, 4, n)


# ---------------------------------------------------------------------------
# the flags that chose, and are gone
# ---------------------------------------------------------------------------

GONE = ("FLAGS_autotune", "FLAGS_autotune_cache_dir",
        "FLAGS_paged_xla_max_ctx", "FLAGS_paged_grouped_kernel",
        "FLAGS_flash_fwd_min_seq", "FLAGS_flash_bwd_min_seq")


def test_the_six_flags_are_gone_from_the_registry(monkeypatch):
    """Not declared, so `get_flags` knows none of them, and `set_flags` of
    one does what it does for any unknown name: it declares a flag that no
    code reads (the choice below stays what it was)."""
    assert not set(GONE) & set(_config._FLAGS)
    assert paddle.get_flags(list(GONE)) == {}
    assert _config.get_flag("FLAGS_quant_matmul") == "xla"
    paddle.set_flags({"FLAGS_paged_xla_max_ctx": 1})
    try:
        args, want = PAGED["page16-mapped2048"]
        assert _paged_taken(monkeypatch, args, False) == [want] == [GATHER]
    finally:
        del _config._FLAGS["FLAGS_paged_xla_max_ctx"]
