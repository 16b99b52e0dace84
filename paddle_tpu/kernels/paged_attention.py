"""Pallas paged-attention decode kernel + paged KV cache ops.

Reference parity: the paged/blocked KV cache inside
paddle/fluid/operators/fused/fused_multi_transformer_op (int8/cachekv
variants) — SURVEY.md §2.1 "Fused transformer ops", §7 phase 10 (hard part
#3: paged gather/scatter layouts on TPU).

TPU-native design: KV lives in fixed-size pages `[kv_heads, n_pages,
page_size, head_dim]`; each sequence owns a block table row. The decode
kernel (`paged_attention`) walks a grid of (row, page): the page index of a
step is a prefetched scalar that feeds the BlockSpec index map, so the
gather happens in the pipeline's DMA and nothing of the size of the mapped
context is ever written. A step takes ALL kv heads of its page in one block
(split only where a block would pass `_BLOCK_BYTES`), feeds the MXU the
pool's own type and accumulates the online softmax in float32 scratch. The
index of a page at or beyond a row's context length repeats the row's last
live page (and a row with nothing to read repeats what the row before it
left), so the pipeline sees an unchanged block and copies nothing: the
kernel reads the LIVE pages, whatever the tables map.

Which path decodes (`paged_attention_dispatch`): float pools whose page
fills a K tile (`page_size >= 128`) take the kernel on the chip whatever the
mapped context; pages under 128 tokens and int8 pools keep the crossover
`_XLA_DECODE_MAX_CTX`; interpret mode (the CPU) takes `paged_attention_xla`,
the dense-gather reference the tests compare against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off

NEG_INF = np.float32(-1e30)

_pc = pl.pallas_call


# The crossover of MAPPED context (pages_per_seq * page_size) for pages under
# `_KERNEL_MIN_PAGE` tokens and for int8 pools: the gather up to it, the
# page-grid kernel above it. Taken from a v5e reading that predates the
# ledger (at a mapped context of 1,024 and 16-token pages the gather decoded
# 2.2x faster than the kernel as it then was, and the gather's traffic grows
# with the mapped context); 2,048 extrapolates that reading and was never
# measured itself. No cell of the benchmark stands on either side of it: the
# first int8-KV cell decides it (ROADMAP D2).
_XLA_DECODE_MAX_CTX = 2048

# A page that fills a K tile of the MXU. Float pools at such pages take the
# kernel whatever the mapped context: there the gather writes float32 copies
# of the whole mapped context where the kernel reads the live pages (PERF.md
# section 6, PR 28: `chat-open` at page 256 is the cell above it; no cell
# serves a page under it).
_KERNEL_MIN_PAGE = 128


def decode_uses_kernel(page_size, mapped_tokens, quant):
    """Whether a decode call over pools of `page_size` tokens, `quant` for
    int8 pages, whose tables map `mapped_tokens` a row, takes the page-grid
    kernel (else the XLA dense gather)."""
    if _interpret():
        return False
    if not quant and page_size >= _KERNEL_MIN_PAGE:
        return True
    return mapped_tokens > _XLA_DECODE_MAX_CTX


def paged_attention_dispatch(q, k_pages, v_pages, block_tables,
                             context_lens, scale=None, k_scales=None,
                             v_scales=None, first=None, sink=None):
    """Decode attention, chosen from what the call can see: interpret mode
    takes the XLA dense gather (the Pallas path is emulation there); float
    pools at pages of `_KERNEL_MIN_PAGE` and more take the page-grid kernel
    whatever the mapped context; smaller pages and int8 pools take the
    gather up to `_XLA_DECODE_MAX_CTX` of mapped context and the kernel
    above it. `first` [batch] (a window layer's first visible position a
    row) and `sink` [num_q_heads] (a logit a head in the softmax's
    denominator) go to whichever is taken."""
    page_size = k_pages.shape[2]
    attend = paged_attention if decode_uses_kernel(
        page_size, block_tables.shape[1] * page_size,
        k_scales is not None) else paged_attention_xla
    kw = {} if first is None else {"first": first}
    if sink is not None:
        kw["sink"] = sink
    return attend(q, k_pages, v_pages, block_tables, context_lens,
                  scale=scale, k_scales=k_scales, v_scales=v_scales, **kw)


# ---------------------------------------------------------------------------
# cache management (XLA scatter — one token per sequence per step)
# ---------------------------------------------------------------------------


def alloc_pages(n_pages, page_size, num_kv_heads, head_dim,
                dtype=jnp.float32):
    """Allocate empty K and V page pools."""
    shape = (num_kv_heads, n_pages, page_size, head_dim)
    return jnp.zeros(shape, dtype=dtype), jnp.zeros(shape, dtype=dtype)


# int8 KV cache (reference: fused_multi_transformer's int8 cachekv
# variants — SURVEY.md §2.1): pages store int8, plus one f32 scale per
# (kv_head, page, slot) written at token-write time (dynamic symmetric
# absmax over head_dim). Decode applies K scales to the score COLUMNS
# after q·k_int8 and V scales to the softmax weights before p·v_int8 —
# algebraically exact dequantization without ever materializing float
# pages, so KV HBM traffic and capacity improve ~2x vs bf16.

_SCALE_LANES = 128  # scale pools pad page_size up to the TPU lane width


def alloc_page_scales(n_pages, page_size, num_kv_heads):
    """Scale pools for int8 pages: [kv_heads, n_pages, 128] f32 (slots
    beyond page_size unused — lane-aligned so the Pallas BlockSpec tiles
    cleanly; the overhead is 512 B/page against 4 KB of int8 payload at
    page_size=16, head_dim=128)."""
    if page_size > _SCALE_LANES:
        raise ValueError(f"page_size must be <= {_SCALE_LANES} for int8 KV")
    shape = (num_kv_heads, n_pages, _SCALE_LANES)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def _quant_kv_token(x):
    """Per-(row, head) symmetric int8 quant of [..., head_dim] values."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    # the reciprocal spelled out: XLA compiles `absmax / 127.0` to this
    # multiply (CPU and TPU alike), an eager dispatch divides, and the two
    # differ by an ulp in one scale of twenty; every caller is compiled
    scale = jnp.maximum(absmax * np.float32(1.0 / 127.0),
                        np.float32(1e-12))
    q = jnp.clip(jnp.rint(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def update_paged_kv_cache(k_pages, v_pages, k_new, v_new, block_tables,
                          context_lens, active=None):
    """Write one new token per sequence into its page.

    k_new/v_new: [batch, kv_heads, the pool's width]; context_lens[b] is the number
    of tokens already present (the new token lands at that position).
    active: optional [batch] bool — False rows write nothing (their block
    table row may be stale, e.g. a retired serving slot).

    The write is a scatter of ROWS into the pools' flat
    [kv_heads * n_pages * page_size, head_dim] view (a bitcast): the one
    indexed dimension is the major one of the default layout, which the
    decode kernel's operands are pinned to. Indexed as
    `.at[:, page_ids, slots, :]`, XLA:TPU lays each pool out with the
    indexed dimensions outermost and, inside the decode scan, copies it to
    the kernel's layout and back every step (PERF.md section 6, PR 28)."""
    kv_heads, n_pages, page_size, _ = k_pages.shape
    page_ids = jnp.take_along_axis(
        block_tables, (context_lens // page_size)[:, None], axis=1)[:, 0]
    rows = (jnp.arange(kv_heads, dtype=jnp.int32)[None, :] * n_pages
            + page_ids.astype(jnp.int32)[:, None]) * page_size \
        + (context_lens % page_size).astype(jnp.int32)[:, None]
    if active is not None:
        # redirect inactive rows out of range; mode="drop" discards them
        rows = jnp.where(active[:, None], rows,
                         kv_heads * n_pages * page_size)
    rows = rows.reshape(-1)  # [batch * kv_heads], as k_new's rows

    def put(pages, new):   # a key may be wider than a value
        width = pages.shape[-1]
        return pages.reshape(-1, width).at[rows].set(
            new.reshape(-1, width), mode="drop").reshape(pages.shape)

    return put(k_pages, k_new), put(v_pages, v_new)


def prefill_paged_kv_cache(k_pages, v_pages, k_seq, v_seq, block_tables,
                           seq_lens):
    """Scatter whole prompts into pages.

    k_seq/v_seq: [batch, s, kv_heads, head_dim]; positions j >= seq_lens[b]
    are dropped (padding)."""
    b, s = k_seq.shape[0], k_seq.shape[1]
    page_size = k_pages.shape[2]
    pos = jnp.arange(s)[None, :]  # [1, s]
    page_ids = jnp.take_along_axis(block_tables, pos // page_size,
                                   axis=1)  # [b, s]
    slots = jnp.broadcast_to(pos % page_size, (b, s))
    valid = pos < seq_lens[:, None]
    # drop invalid scatters by redirecting them out of range
    page_ids = jnp.where(valid, page_ids, k_pages.shape[1])
    kk = k_seq.astype(k_pages.dtype).transpose(2, 0, 1, 3).reshape(
        k_seq.shape[2], b * s, -1)
    vv = v_seq.astype(v_pages.dtype).transpose(2, 0, 1, 3).reshape(
        v_seq.shape[2], b * s, -1)
    k_pages = k_pages.at[:, page_ids.reshape(-1), slots.reshape(-1), :].set(
        kk, mode="drop")
    v_pages = v_pages.at[:, page_ids.reshape(-1), slots.reshape(-1), :].set(
        vv, mode="drop")
    return k_pages, v_pages


def update_paged_kv_cache_q8(k_pages, k_scales, v_pages, v_scales,
                             k_new, v_new, block_tables, context_lens,
                             active=None):
    """int8 variant of `update_paged_kv_cache`: quantize the incoming
    token per (seq, head) and scatter value + scale."""
    page_size = k_pages.shape[2]
    page_ids = jnp.take_along_axis(
        block_tables, (context_lens // page_size)[:, None], axis=1)[:, 0]
    if active is not None:
        page_ids = jnp.where(active, page_ids, k_pages.shape[1])
    slots = context_lens % page_size
    kq, ks = _quant_kv_token(k_new)  # [b, kvh, d] int8, [b, kvh] f32
    vq, vs = _quant_kv_token(v_new)
    k_pages = k_pages.at[:, page_ids, slots, :].set(
        kq.transpose(1, 0, 2), mode="drop")
    v_pages = v_pages.at[:, page_ids, slots, :].set(
        vq.transpose(1, 0, 2), mode="drop")
    k_scales = k_scales.at[:, page_ids, slots].set(ks.T, mode="drop")
    v_scales = v_scales.at[:, page_ids, slots].set(vs.T, mode="drop")
    return k_pages, k_scales, v_pages, v_scales


def prefill_paged_kv_cache_q8(k_pages, k_scales, v_pages, v_scales,
                              k_seq, v_seq, block_tables, seq_lens):
    """int8 variant of `prefill_paged_kv_cache` (whole prompts)."""
    b, s = k_seq.shape[0], k_seq.shape[1]
    kvh = k_seq.shape[2]
    page_size = k_pages.shape[2]
    pos = jnp.arange(s)[None, :]
    page_ids = jnp.take_along_axis(block_tables, pos // page_size, axis=1)
    slots = jnp.broadcast_to(pos % page_size, (b, s))
    valid = pos < seq_lens[:, None]
    page_ids = jnp.where(valid, page_ids, k_pages.shape[1])
    kq, ks = _quant_kv_token(k_seq)  # [b, s, kvh, d], [b, s, kvh]
    vq, vs = _quant_kv_token(v_seq)
    flat_pages = page_ids.reshape(-1)
    flat_slots = slots.reshape(-1)
    kk = kq.transpose(2, 0, 1, 3).reshape(kvh, b * s, -1)
    vv = vq.transpose(2, 0, 1, 3).reshape(kvh, b * s, -1)
    k_pages = k_pages.at[:, flat_pages, flat_slots, :].set(kk, mode="drop")
    v_pages = v_pages.at[:, flat_pages, flat_slots, :].set(vv, mode="drop")
    k_scales = k_scales.at[:, flat_pages, flat_slots].set(
        ks.transpose(2, 0, 1).reshape(kvh, b * s), mode="drop")
    v_scales = v_scales.at[:, flat_pages, flat_slots].set(
        vs.transpose(2, 0, 1).reshape(kvh, b * s), mode="drop")
    return k_pages, k_scales, v_pages, v_scales


def _window_write_coords(k_pages, block_tables, start_lens, s,
                         limit_lens, active):
    """Flat (page, slot) write coordinates for a [b, s] token window:
    row b's token w lands at position start_lens[b] + w. Positions at
    or beyond limit_lens[b] (and inactive rows) are redirected to the
    out-of-range page index so mode='drop' discards them — the
    speculative-verify window may overhang a row's token budget, and
    those overhang positions must not touch pages the row never
    reserved. The ONE copy of that budget-safety invariant, shared by
    the float and int8-KV scatter paths."""
    page_size = k_pages.shape[2]
    pos = start_lens[:, None] + jnp.arange(s, dtype=start_lens.dtype)
    page_idx = jnp.minimum(pos // page_size, block_tables.shape[1] - 1)
    page_ids = jnp.take_along_axis(block_tables, page_idx, axis=1)
    slots = pos % page_size
    valid = pos < (start_lens[:, None] + s if limit_lens is None
                   else limit_lens[:, None])
    if active is not None:
        valid = valid & active[:, None]
    page_ids = jnp.where(valid, page_ids, k_pages.shape[1])
    return page_ids.reshape(-1), slots.reshape(-1)


def scatter_paged_kv_window(k_pages, v_pages, k_seq, v_seq, block_tables,
                            start_lens, limit_lens=None, active=None):
    """Scatter a WINDOW of s new tokens per sequence into its pages
    (coordinates + overhang masking: `_window_write_coords`).
    k_seq/v_seq: [b, s, kv_heads, head_dim]."""
    b, s = k_seq.shape[0], k_seq.shape[1]
    kvh = k_seq.shape[2]
    flat_pages, flat_slots = _window_write_coords(
        k_pages, block_tables, start_lens, s, limit_lens, active)
    kk = k_seq.astype(k_pages.dtype).transpose(2, 0, 1, 3) \
        .reshape(kvh, b * s, -1)
    vv = v_seq.astype(v_pages.dtype).transpose(2, 0, 1, 3) \
        .reshape(kvh, b * s, -1)
    k_pages = k_pages.at[:, flat_pages, flat_slots, :].set(kk, mode="drop")
    v_pages = v_pages.at[:, flat_pages, flat_slots, :].set(vv, mode="drop")
    return k_pages, v_pages


def scatter_paged_kv_window_q8(k_pages, k_scales, v_pages, v_scales,
                               k_seq, v_seq, block_tables, start_lens,
                               limit_lens=None, active=None):
    """int8 variant of `scatter_paged_kv_window`: per-(row, token, head)
    symmetric quant, scatter value + scale."""
    b, s = k_seq.shape[0], k_seq.shape[1]
    kvh = k_seq.shape[2]
    kq, ks = _quant_kv_token(k_seq)  # [b, s, kvh, d], [b, s, kvh]
    vq, vs = _quant_kv_token(v_seq)
    flat_pages, flat_slots = _window_write_coords(
        k_pages, block_tables, start_lens, s, limit_lens, active)
    kk = kq.transpose(2, 0, 1, 3).reshape(kvh, b * s, -1)
    vv = vq.transpose(2, 0, 1, 3).reshape(kvh, b * s, -1)
    k_pages = k_pages.at[:, flat_pages, flat_slots, :].set(kk, mode="drop")
    v_pages = v_pages.at[:, flat_pages, flat_slots, :].set(vv, mode="drop")
    k_scales = k_scales.at[:, flat_pages, flat_slots].set(
        ks.transpose(2, 0, 1).reshape(kvh, b * s), mode="drop")
    v_scales = v_scales.at[:, flat_pages, flat_slots].set(
        vs.transpose(2, 0, 1).reshape(kvh, b * s), mode="drop")
    return k_pages, k_scales, v_pages, v_scales


def paged_attention_window_xla(q, k_pages, v_pages, block_tables,
                               context_lens, scale=None, k_scales=None,
                               v_scales=None):
    """Multi-token window attention over the paged cache (the
    speculative-verify forward): query w of row b attends positions
    < context_lens[b] + w + 1 — its own just-written token included,
    matching the single-token path's `lens + 1` convention. Dense
    gather like `paged_attention_xla`; the window is a handful of
    tokens so the verify matmul is [s, S] per head, still tiny.

    q: [b, s, num_q_heads, head_dim] -> [b, s, num_q_heads, head_dim]
    """
    b, s, n_q_heads, head_dim = q.shape
    n_kv_heads, _, page_size, _ = k_pages.shape
    group = n_q_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / float(np.sqrt(head_dim))
    k_dense = k_pages[:, block_tables]
    v_dense = v_pages[:, block_tables]
    S = block_tables.shape[1] * page_size
    k_dense = k_dense.reshape(n_kv_heads, b, S, head_dim)
    v_dense = v_dense.reshape(n_kv_heads, b, S, head_dim)
    if k_scales is not None:
        ks = k_scales[:, block_tables, :page_size].reshape(n_kv_heads, b, S)
        vs = v_scales[:, block_tables, :page_size].reshape(n_kv_heads, b, S)
        k_dense = k_dense.astype(jnp.float32) * ks[..., None]
        v_dense = v_dense.astype(jnp.float32) * vs[..., None]
    qf = q.reshape(b, s, n_kv_heads, group, head_dim).astype(jnp.float32)
    sc = jnp.einsum("bwhgd,hbsd->bhgws", qf,
                    k_dense.astype(jnp.float32)) * scale
    q_pos = context_lens[:, None] + jnp.arange(s)[None, :]  # [b, w]
    mask = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]  # [b, w, S]
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgws,hbsd->bwhgd", p, v_dense.astype(jnp.float32))
    return out.reshape(b, s, n_q_heads, head_dim).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


def _decode_accumulate(q, k, v, base_pos, ctx, scale, m_scr, l_scr, acc,
                       k_col_scale=None, v_col_scale=None, first=None):
    """One online-softmax block update of the page-grid decode kernel:
    scores for a K/V block starting at absolute position `base_pos`,
    masked at `ctx` (and below `first`, a window's first visible position,
    where given), folded into the running (m, l, acc) state. q [.., rows,
    d] and k/v [.., tokens, d] share
    their leading dims (the kv heads of a block), which are batch dims
    of both products; the operands go to the MXU in the type they come
    in, and everything from the scores to the accumulator is float32 (the
    weights take V's type for their product alone). Optional per-COLUMN
    scales implement exact int8 dequantization (K scales after q·k, V
    scales on the weights; the l normalizer uses unscaled pexp)."""
    lead = tuple(range(q.ndim - 2))
    last = q.ndim - 1
    s = jax.lax.dot_general(
        q, k, (((last,), (last,)), (lead, lead)),
        preferred_element_type=jnp.float32) * np.float32(scale)
    if k_col_scale is not None:
        s = s * k_col_scale[..., None, :]
    kpos = base_pos + jax.lax.broadcasted_iota(jnp.int32, s.shape, last)
    seen = kpos < ctx if first is None else (kpos < ctx) & (kpos >= first)
    s = jnp.where(seen, s, NEG_INF)
    m_prev = m_scr[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    if first is not None:
        # NEG_INF is finite: a block whose every column is masked has
        # s == m_new and exp(0) == 1 everywhere; the mask itself zeroes it
        pexp = jnp.where(seen, pexp, np.float32(0.0))
    l_scr[..., :1] = alpha * l_scr[..., :1] + jnp.sum(pexp, axis=-1,
                                                      keepdims=True)
    pw = pexp if v_col_scale is None else pexp * v_col_scale[..., None, :]
    pv = jax.lax.dot_general(
        pw.astype(v.dtype), v, (((last,), (last - 1,)), (lead, lead)),
        preferred_element_type=jnp.float32)
    acc[...] = acc[...] * alpha + pv
    m_scr[..., :1] = m_new


def _decode_init(m_scr, l_scr, acc, sink=None):
    """The online softmax's state before any block: (-inf, 0, 0), or with a
    `sink` (a logit a row that stands in the denominator and carries no
    value) the state after that one column, (sink, 1, 0)."""
    if sink is None:
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
    else:
        m_scr[...] = sink
        l_scr[...] = jnp.ones_like(l_scr)
    acc[...] = jnp.zeros_like(acc)


def _decode_epilogue(l_scr, acc, dtype):
    """acc / l, and zeros for a row that read nothing (l == 0)."""
    l = l_scr[..., :1]
    return (acc[...] / jnp.where(l == 0.0, np.float32(1.0), l)).astype(dtype)


def _decode_kernel(lens_ref, fetch_ref, *rest, page_size, scale, n_pages,
                   quant=False, windowed=False, sunk=False):
    """Online-softmax decode over the page grid dimension, every kv head
    of the block at once. `fetch_ref` is read by the index maps alone.
    `windowed`: a third prefetched scalar a row, the first position the row
    sees, masked inside its page (a caller whose rows see nothing of their
    first pages hands tables that start at the page of that position, as
    `ring_view` does: no page is skipped for it here). `sunk`: one more
    operand after V (and the scales), the sink logit of every query row
    across 128 lanes, which the softmax's state starts from.

    One body serves both storage formats: float pages go to the MXU as
    they are (q in their type); with `quant` the pages hold int8, are
    upcast to float32, and `rest` leads with the per-slot scale refs — K
    scales multiply the score COLUMNS after q·k_int8 and V scales multiply
    the softmax weights before p·v_int8, which is algebraically exact
    dequantization (the l normalizer uses unscaled pexp in both modes).
    """
    first_ref = None
    if windowed:
        first_ref, *rest = rest
    q_ref, k_ref, v_ref, *rest = rest
    if quant:
        ks_ref, vs_ref, *rest = rest
    sink_ref = None
    if sunk:
        sink_ref, *rest = rest
    o_ref, m_scr, l_scr, acc = rest
    b = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _decode_init(m_scr, l_scr, acc,
                     None if sink_ref is None else sink_ref[...])

    ctx = lens_ref[b]
    first = None if first_ref is None else first_ref[b]

    @pl.when(p * page_size < ctx)
    def _():
        k, v = k_ref[:, 0], v_ref[:, 0]
        if quant:
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        _decode_accumulate(
            q_ref[0].astype(k.dtype), k, v,
            p * page_size, ctx, scale, m_scr, l_scr, acc,
            k_col_scale=ks_ref[:, 0, 0, :page_size] if quant else None,
            v_col_scale=vs_ref[:, 0, 0, :page_size] if quant else None,
            **({} if first is None else {"first": first}))

    @pl.when(p == n_pages - 1)
    def _():
        o_ref[0] = _decode_epilogue(l_scr, acc, o_ref.dtype)


_BLOCK_BYTES = 1 << 20  # one K (or V) block: 16 heads of a 256 x 128 bf16 page


def _live_page_ids(block_tables, context_lens, page_size):
    """[batch, pages_per_seq] int32: the page the kernel holds at grid step
    (row, p). The row's own p-th page while that page is live; from its
    last live page on, that page again, so the pipeline sees an unchanged
    block index and copies nothing. A row with nothing to read (length 0:
    an inactive slot) repeats the page the last live row before it ended
    on, and the rows before the first live one repeat its first page.
    Only entries below a live row's length are taken from the tables: a
    retired slot's stale row is never dereferenced."""
    b, pages_per_seq = block_tables.shape
    live = context_lens > 0
    last = jnp.maximum(context_lens - 1, 0) // page_size
    cols = jnp.minimum(jnp.arange(pages_per_seq)[None, :], last[:, None])
    ids = jnp.take_along_axis(block_tables, cols, axis=1)
    prev = jax.lax.cummax(jnp.where(live, jnp.arange(b), -1))
    held = jnp.where(prev >= 0, ids[jnp.maximum(prev, 0), -1],
                     ids[jnp.argmax(live), 0])
    ids = jnp.where(live[:, None], ids, held[:, None])
    return jnp.where(jnp.any(live), ids, 0).astype(jnp.int32)


def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, k_scales=None, v_scales=None, first=None,
                    sink=None):
    """Single-token decode attention over a paged KV cache.

    q: [batch, num_q_heads, head_dim]
    k_pages: [num_kv_heads, n_pages, page_size, head_dim]
    v_pages: [num_kv_heads, n_pages, page_size, value_dim] (a value may be
        narrower than a key; the output is as wide as a value)
    block_tables: [batch, pages_per_seq] int32 (page indices)
    context_lens: [batch] int32 — tokens valid in the cache (q attends over
        these; the current token's K/V must already be written). A row of
        length 0 reads nothing and returns zeros.
    k_scales/v_scales: [num_kv_heads, n_pages, 128] f32 — present iff the
        pages hold int8 (see `alloc_page_scales`)
    first: optional [batch] int32, a window: the row attends positions
        first[b] .. context_lens[b] - 1. The positions below first[b] are
        masked, not skipped: `ring_view` hands tables that start at the
        page of the first visible position
    sink: optional [num_q_heads] float, a logit a head that stands in the
        softmax's denominator and carries no value (a row that reads
        nothing still returns zeros)
    -> [batch, num_q_heads, value_dim]

    Grid (head blocks, batch, pages_per_seq), the pages innermost: a step
    holds one page of `hb` kv heads, `hb` the most heads (a divisor of
    num_kv_heads, which is whatever the caller's shard holds) whose block
    stays within `_BLOCK_BYTES`. Head blocks are outermost so that a row
    with nothing to read finds the block of the row before it resident.
    """
    b, n_q_heads, head_dim = q.shape
    n_kv_heads, _, page_size, _ = k_pages.shape
    value_dim = v_pages.shape[3]
    pages_per_seq = block_tables.shape[1]
    group = n_q_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / float(np.sqrt(head_dim))
    quant = k_scales is not None

    page_bytes = page_size * head_dim * jnp.dtype(k_pages.dtype).itemsize
    hb = max(h for h in range(1, n_kv_heads + 1)
             if n_kv_heads % h == 0
             and (h == 1 or h * page_bytes <= _BLOCK_BYTES))

    # [b, kv_heads, group, d]; pad group to the sublane tile (8)
    qg = q.reshape(b, n_kv_heads, group, head_dim)
    gpad = max(8, ((group + 7) // 8) * 8)
    if gpad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gpad - group), (0, 0)))

    windowed = first is not None
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, scale=scale,
        n_pages=pages_per_seq, quant=quant,
        **({"windowed": True} if windowed else {}), sunk=sink is not None)

    def row_map(h, b, p, lens, fetch, *_):
        return (b, h, 0, 0)

    def page_map(h, b, p, lens, fetch, *_):
        return (h, fetch[b, p], 0, 0)

    in_specs = [pl.BlockSpec((1, hb, gpad, head_dim), row_map),
                pl.BlockSpec((hb, 1, page_size, head_dim), page_map),
                pl.BlockSpec((hb, 1, page_size, value_dim), page_map)]
    operands = [qg, k_pages, v_pages]
    if quant:
        # Scales ride in with a singleton sublane dim: a (1, lanes) trailing
        # tile over the 3D [kvh, n_pages, lanes] pool is illegal on Mosaic
        # (second-to-minor must be a multiple of 8 or the full dim), but
        # (hb, 1, 1, lanes) over [kvh, n_pages, 1, lanes] matches the array
        # dims exactly and lowers clean.
        scale_spec = pl.BlockSpec((hb, 1, 1, _SCALE_LANES), page_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales[:, :, None, :], v_scales[:, :, None, :]]
    if sink is not None:
        # [kv_heads, gpad, 128]: a query row's logit across the lanes of
        # the state it starts (the padded rows' outputs are dropped)
        rows = jnp.pad(sink.astype(jnp.float32).reshape(n_kv_heads, group),
                       ((0, 0), (0, gpad - group)))
        in_specs.append(pl.BlockSpec(
            (hb, gpad, 128), lambda h, b, p, *_: (h, 0, 0)))
        operands.append(jnp.broadcast_to(rows[..., None],
                                         (n_kv_heads, gpad, 128)))

    context_lens = context_lens.astype(jnp.int32)
    scalars = [context_lens,
               _live_page_ids(block_tables, context_lens, page_size)]
    if windowed:
        scalars.append(first.astype(jnp.int32))
    with _x64_off():
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n_kv_heads // hb, b, pages_per_seq),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hb, gpad, value_dim), row_map),
            scratch_shapes=[
                pltpu.VMEM((hb, gpad, 128), jnp.float32),
                pltpu.VMEM((hb, gpad, 128), jnp.float32),
                pltpu.VMEM((hb, gpad, value_dim), jnp.float32),
            ],
        )
        out = _pc(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n_kv_heads, gpad, value_dim),
                                           q.dtype),
            interpret=_interpret(),
        )(*scalars, *operands)
    return out[:, :, :group, :].reshape(b, n_q_heads, value_dim)


def paged_attention_xla(q, k_pages, v_pages, block_tables, context_lens,
                        scale=None, k_scales=None, v_scales=None,
                        first=None, sink=None):
    """Dense-gather reference: materialize [b, S, kv_h, d] then masked
    attention. The tests' reference, the dispatch's choice in interpret
    mode, and below the crossover for small pages and int8 pools. A `sink`
    is one more column of the softmax, dropped after it."""
    b, n_q_heads, head_dim = q.shape
    n_kv_heads, _, page_size, _ = k_pages.shape
    value_dim = v_pages.shape[3]
    group = n_q_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / float(np.sqrt(head_dim))
    # gather pages: [b, pages_per_seq] -> [kv_h, b, pages, ps, d]
    k_dense = k_pages[:, block_tables]  # [kv_h, b, pages, ps, d]
    v_dense = v_pages[:, block_tables]
    S = block_tables.shape[1] * page_size
    k_dense = k_dense.reshape(n_kv_heads, b, S, head_dim)
    v_dense = v_dense.reshape(n_kv_heads, b, S, value_dim)
    if k_scales is not None:  # int8 pages: dequantize the dense gather
        ks = k_scales[:, block_tables, :page_size].reshape(n_kv_heads, b, S)
        vs = v_scales[:, block_tables, :page_size].reshape(n_kv_heads, b, S)
        k_dense = k_dense.astype(jnp.float32) * ks[..., None]
        v_dense = v_dense.astype(jnp.float32) * vs[..., None]
    qf = q.reshape(b, n_kv_heads, group, head_dim).astype(jnp.float32)
    s = jnp.einsum("bhgd,hbsd->bhgs", qf,
                   k_dense.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, :] < context_lens[:, None]  # [b, S]
    if first is not None:
        mask = mask & (jnp.arange(S)[None, :] >= first[:, None])
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    if sink is not None:
        column = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            1, n_kv_heads, group, 1), (b, n_kv_heads, group, 1))
        p = jax.nn.softmax(jnp.concatenate([s, column], -1), axis=-1)[..., :S]
    else:
        p = jax.nn.softmax(s, axis=-1)
    if first is not None:
        # a row that sees nothing returns zeros, as the kernel's does
        p = jnp.where(jnp.any(mask, -1)[:, None, None, None], p, 0.0)
    out = jnp.einsum("bhgs,hbsd->bhgd", p, v_dense.astype(jnp.float32))
    return out.reshape(b, n_q_heads, value_dim).astype(q.dtype)


# ---------------------------------------------------------------------------
# window layers: a RING of pages a row. A layer that attends the last
# `window` positions keeps `ring_pages(window, page_size)` pages a row in
# pools of its own, [kv_heads, rows * ring, page_size, head_dim]: row b owns
# pages b * ring .. b * ring + ring - 1 and position p lives in page
# (p // page_size) % ring of them, at slot p % page_size. One page more than
# the window fills, so that a window that straddles page boundaries is held
# whole; nothing is allocated or freed as a context grows.
# ---------------------------------------------------------------------------


def ring_pages(window, page_size):
    """Pages a row of a window layer keeps: ceil(window / page) + 1."""
    return -(-window // page_size) + 1


def ring_tables(rows, ring):
    """[len(rows), ring] int32: the pages of each ring, in ring order."""
    rows = jnp.asarray(rows, jnp.int32)
    return rows[:, None] * ring + jnp.arange(ring, dtype=jnp.int32)


def ring_view(rows, ring, page_size, context_lens, window):
    """What a decode step over rings hands the attention: (tables [b, ring],
    lens [b], first [b]) in coordinates that start at the first page a row
    still sees. `context_lens` counts the positions written (0: the row
    reads nothing); a row sees its last `window` of them. Entry k of a
    row's table is the ring page of logical page `lo + k`, `lo` the page of
    the first visible position, so `lens` never passes ring * page_size."""
    n = context_lens.astype(jnp.int32)
    first = jnp.maximum(n - window, 0)
    lo = first // page_size
    order = (lo[:, None] + jnp.arange(ring, dtype=jnp.int32)) % ring
    tables = jnp.asarray(rows, jnp.int32)[:, None] * ring + order
    return tables, n - lo * page_size, first - lo * page_size


def update_ring_kv_cache(k_pages, v_pages, k_new, v_new, rows, context_lens,
                         active=None):
    """`update_paged_kv_cache` over rings: the new token of row b lands at
    position context_lens[b] of ring `rows[b]`."""
    ring = k_pages.shape[1] // len(rows)
    return update_paged_kv_cache(
        k_pages, v_pages, k_new, v_new, ring_tables(rows, ring),
        context_lens % (ring * k_pages.shape[2]), active=active)


def ring_tail_start(seq_lens, s, ring, page_size):
    """(oldest, start) [batch] int32 for prompts of `seq_lens` tokens in a
    bucket of `s`: `oldest` is the first position a ring keeps of a prompt
    (the start of the page `ring` pages before the one its last token is
    in; negative for a prompt the ring holds whole), `start` where the
    `min(s, ring * page_size)` positions that hold everything kept begin."""
    lens = seq_lens.astype(jnp.int32)
    oldest = (-(-lens // page_size) - ring) * page_size
    return oldest, jnp.clip(oldest, 0, s - min(s, ring * page_size))


def ring_tail(seq, seq_lens, ring, page_size):
    """The part of a prompt's rows a window layer keeps: of seq [batch, s,
    kv_heads, head_dim] the `min(s, ring * page_size)` positions from
    `ring_tail_start`'s start on, [batch, span, kv_heads, head_dim]."""
    s = seq.shape[1]
    _, start = ring_tail_start(seq_lens, s, ring, page_size)
    return jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
        row, at, min(s, ring * page_size), axis=0))(seq, start)


def prefill_ring_kv_cache(k_pages, v_pages, k_tail, v_tail, rows, seq_lens,
                          ring, s):
    """Write whole prompts into rings, the pages a later step can still see
    and no other: k_tail / v_tail are `ring_tail`s of the prompts' K and V
    in a bucket of `s` positions; row b keeps the positions from
    `(ceil(seq_lens[b] / page) - ring) * page` to seq_lens[b] - 1, in ring
    `rows[b]`; a row of length 0 writes nothing."""
    page_ids, slots = _ring_slots(k_pages, k_tail, rows, seq_lens, ring, s)
    return _ring_put(k_pages, k_tail, page_ids, slots), \
        _ring_put(v_pages, v_tail, page_ids, slots)


def prefill_ring_pool(pages, tail, rows, seq_lens, ring, s):
    """`prefill_ring_kv_cache` for a layout of one pool (latent rows):
    tail [batch, span, heads, width] a `ring_tail` of the prompts' rows."""
    page_ids, slots = _ring_slots(pages, tail, rows, seq_lens, ring, s)
    return _ring_put(pages, tail, page_ids, slots)


def _ring_slots(pages, tail, rows, seq_lens, ring, s):
    """(page ids, slots) [batch x span] where a prefill's `ring_tail`
    lands in the rings of `rows`; a position the ring does not keep (or
    past a prompt's end) names the page past the pool, and is dropped."""
    b, span = tail.shape[:2]
    page_size = pages.shape[2]
    lens = seq_lens.astype(jnp.int32)
    oldest, start = ring_tail_start(lens, s, ring, page_size)
    pos = start[:, None] + jnp.arange(span, dtype=jnp.int32)
    kept = (pos < lens[:, None]) & (pos >= oldest[:, None])
    page_ids = jnp.asarray(rows, jnp.int32)[:, None] * ring \
        + (pos // page_size) % ring
    page_ids = jnp.where(kept, page_ids, pages.shape[1]).reshape(-1)
    return page_ids, (pos % page_size).reshape(-1)


def _ring_put(pages, tail, page_ids, slots):
    b, span, kvh, _ = tail.shape
    tail = tail.astype(pages.dtype).transpose(2, 0, 1, 3).reshape(
        kvh, b * span, -1)
    return pages.at[:, page_ids, slots, :].set(tail, mode="drop")


def ring_pages_live(context_lens, window, page_size):
    """Pages of a window layer that hold a position some row still sees,
    summed over rows, from the lengths alone: those from the page of
    max(context_lens[b] - window, 0) to the page of the last position."""
    n = context_lens.astype(jnp.int32)
    pages = -(-n // page_size) - jnp.maximum(n - window, 0) // page_size
    return jnp.sum(jnp.where(n > 0, pages, 0), dtype=jnp.int32)


def decode_pages_fetched(block_tables, context_lens, page_size):
    """Pages of a pool that one call of the page-grid kernel copies, from
    what the kernel itself is handed: its pipeline copies a block wherever
    the block index (`_live_page_ids`, read in the grid's order, rows then
    pages) differs from the step before, and the one the grid starts on. A
    table that named more pages than are live would count them."""
    fetch = _live_page_ids(block_tables, context_lens.astype(jnp.int32),
                           page_size).reshape(-1)
    return 1 + jnp.sum(fetch[1:] != fetch[:-1], dtype=jnp.int32)


# ---------------------------------------------------------------------------
# latent (MLA) pages: ONE pool a layer, [1, n_pages, page_size, width], whose
# rows are (compressed latent | rope key). In absorbed form decode attention
# is multi-query attention over that one key head, and the first
# `value_width` columns of a row are also its value.
# ---------------------------------------------------------------------------


def update_paged_pool(pages, new, block_tables, context_lens, active=None):
    """`update_paged_kv_cache` for a layout of one pool: new [batch, heads,
    width] lands at position context_lens[b]; inactive rows write
    nothing."""
    page_size = pages.shape[2]
    page_ids = jnp.take_along_axis(
        block_tables, (context_lens // page_size)[:, None], axis=1)[:, 0]
    if active is not None:
        page_ids = jnp.where(active, page_ids, pages.shape[1])
    return pages.at[:, page_ids, context_lens % page_size, :].set(
        new.astype(pages.dtype).transpose(1, 0, 2), mode="drop")


def prefill_paged_pool(pages, seq, block_tables, seq_lens):
    """`prefill_paged_kv_cache` for a layout of one pool: seq [batch, s,
    heads, width]; positions j >= seq_lens[b] are dropped."""
    b, s = seq.shape[0], seq.shape[1]
    page_size = pages.shape[2]
    pos = jnp.arange(s)[None, :]
    page_ids = jnp.take_along_axis(block_tables, pos // page_size, axis=1)
    slots = jnp.broadcast_to(pos % page_size, (b, s))
    page_ids = jnp.where(pos < seq_lens[:, None], page_ids, pages.shape[1])
    rows = seq.astype(pages.dtype).transpose(2, 0, 1, 3).reshape(
        seq.shape[2], b * s, -1)
    return pages.at[:, page_ids.reshape(-1), slots.reshape(-1), :].set(
        rows, mode="drop")


def paged_latent_attention_xla(q, pages, block_tables, context_lens,
                               value_width, scale, first=None):
    """Absorbed-form decode attention over latent pages (dense gather).

    q: [batch, heads, width] (the absorbed query | the rope query); pages:
    [1, n_pages, page_size, width]. Returns [batch, heads, value_width]:
    softmax(q . row * scale) over the first context_lens[b] rows (from
    `first[b]` on where given: a ring's `ring_view`), weighted sum of the
    rows' first `value_width` columns. The rows stay in the pool's dtype:
    both products accumulate in float32 without a float32 copy of the
    mapped context."""
    b = q.shape[0]
    page_size = pages.shape[2]
    S = block_tables.shape[1] * page_size
    rows = pages[0][block_tables].reshape(b, S, pages.shape[3])
    s = jnp.einsum("bhw,bsw->bhs", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(S)[None, :] < context_lens[:, None]
    if first is not None:
        mask = mask & (jnp.arange(S)[None, :] >= first[:, None])
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, NEG_INF), axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", p.astype(rows.dtype),
                     rows[..., :value_width],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
