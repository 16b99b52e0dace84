"""Pallas flash attention (TPU) — the Phi flash_attn kernel equivalent
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party
flashattn — SURVEY.md §2.1 "Phi fusion kernels", §7 phase 9).

Layout: paddle bshd [batch, seq, heads, head_dim]. Forward is the online-
softmax streaming kernel (never materializes [s, s]); backward recomputes
p-blocks from the saved row logsumexp (standard flash backward, two kernels:
dk/dv then dq). Grids put the contraction dim innermost so accumulators live
in VMEM scratch across grid steps; the blocks of each pass come from the
shape (`_flash_tiling`), operands go to the MXU in their own type with
float32 scores and accumulators, and a causal block in the future is
neither fetched nor stepped into.

On the CPU the same kernels run in interpreter mode so CPU CI exercises
identical code paths (SURVEY.md §7 "interpret-mode fallback").
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off

# pallas_call runs under x64-off so index maps / constants stay 32-bit
# (the package enables jax x64 globally for paddle int64 semantics)
_pc = pl.pallas_call

import numpy as np

NEG_INF = np.float32(-1e30)  # f32 scalar: x64 mode must not leak f64 into kernels

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128

# the `jax.named_scope` of the kernels and the backward's prologue, inside a
# model's `attn` (`tracing.SCOPES`): a device trace books them as `attn/flash`
SCOPE = "flash"


# ---------------------------------------------------------------------------
# in-kernel counter-based PRNG for attention dropout
#
# The reference's flashattn applies dropout to the softmax weights inside
# the fused kernel (paddle flash_attn dropout_p — SURVEY.md §2.1 fusion
# row, §5 long-context). TPU-native version: threefry2x32 evaluated with
# plain int32 vector ops (adds/xors/logical shifts), so the SAME bits are
# produced under real Mosaic and interpret mode (pltpu.prng_* has no CPU
# lowering), and the mask is keyed by (seed, batch-head, GLOBAL q pos,
# GLOBAL k pos) — the backward kernels regenerate it bit-exactly from the
# same coordinates regardless of their different grid iteration order.
# ---------------------------------------------------------------------------

_TF_C240 = np.int32(0x1BD11BDA)  # threefry key-schedule parity constant


def _rotl32(x, r):
    return jax.lax.shift_left(x, np.int32(r)) | \
        jax.lax.shift_right_logical(x, np.int32(32 - r))


def _threefry2x32(k0, k1, c0, c1):
    """Standard 20-round threefry2x32; int32 lanes (wraparound adds are
    two's-complement, bit-identical to the uint32 definition)."""
    ks = (k0, k1, k0 ^ k1 ^ _TF_C240)
    x0 = c0 + k0
    x1 = c1 + k1
    rounds = ((13, 15, 26, 6), (17, 29, 16, 24))
    for blk in range(5):
        for r in rounds[blk % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(blk + 1) % 3]
        x1 = x1 + ks[(blk + 2) % 3] + np.int32(blk + 1)
    return x0


def _dropout_keep(seed, bh, i, j, block_q, block_k, rate, transposed=False):
    """Boolean keep-mask for one (block_q, block_k) attention tile, or with
    `transposed` for the same tile as (block_k, block_q). Counters are the
    global (q, k) token positions, key is (seed, bh)."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 if transposed else 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1)
    bits = _threefry2x32(seed, bh, rows, cols)
    # low 23 bits -> uniform [0, 1): non-negative regardless of sign bit
    u = (bits & np.int32(0x7FFFFF)).astype(jnp.float32) * np.float32(
        1.0 / (1 << 23))
    return u >= np.float32(rate)


# ---------------------------------------------------------------------------
# blocks and visibility
# ---------------------------------------------------------------------------

# What the three passes take where the microbench measured them: heads of
# 128 in a two-byte type, (queries, keys) a grid step. One layer's causal
# attention at [4, 2,048, 16, 128] bf16 on a v5e, ms a call (PERF.md section
# 6, PR 34; 1,024 and 4,096 at the same 8,192 tokens rank the same way):
#
#   blocks      128x128  256x512  256x1024  512x512  512x1024  1024x512  1024x1024
#   forward      6.07     1.81     1.26      1.50     1.10      1.76      1.03
#   dK/dV pass   5.62     1.94     1.75      1.42     1.51      1.50      1.44
#   dQ pass      5.18     1.53     1.32      1.21     1.17      1.19      1.11
#
# A grid step has a fixed cost (the state's rescale, the pipeline's turn)
# that a tile of 128 x 128 x 128 products cannot carry; the dK/dV pass holds
# two accumulators and four products a tile and is as fast at 512 x 512.
_FWD_BLOCKS = (1024, 1024)
_DKV_BLOCKS = (512, 512)
_DQ_BLOCKS = (1024, 1024)
_SMALL_BLOCKS = ((DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K),) * 3


def _flash_tiling(seq_q, seq_kv, head_dim, dtype):
    """((block_q, block_k) of the forward, of the dK/dV pass, of the dQ
    pass) from the shape and the operands' type alone: the measured blocks,
    halved to the largest measured ones (256 queries, 512 keys at the
    least) that divide the lengths; 128 x 128 for all three where none
    does, and for a head or a type the microbench did not see (float32
    operands, heads of 256), so that every shape the kernels took before
    PR 34 they still take, as they took it."""
    if head_dim != 128 or jnp.dtype(dtype).itemsize != 2 \
            or seq_q % 256 or seq_kv % 512:
        return _SMALL_BLOCKS

    def fit(block, seq):
        while seq % block:
            block //= 2
        return block

    return tuple((fit(bq, seq_q), fit(bk, seq_kv))
                 for bq, bk in (_FWD_BLOCKS, _DKV_BLOCKS, _DQ_BLOCKS))


def _last_kv_block(i, block_q, block_k, offset):
    """The last key block a causal query block `i` sees (bottom-right
    aligned: query row r sees keys up to r + offset); 0 for a block of
    rows that see nothing, whose steps the kernels skip."""
    return jnp.maximum(((i + 1) * block_q - 1 + offset) // block_k, 0)


def _first_q_block(j, block_q, block_k, offset):
    """The first query block that sees any key of causal key block `j`."""
    return jnp.maximum((j * block_k - offset) // block_q, 0)


def _kv_index_map(causal, block_q, block_k, offset):
    """Index map of a K / V block in a grid (bh, i, j), key blocks
    innermost: a causal block in the future repeats the last visible one,
    so the pipeline copies nothing for the steps the kernel skips."""
    def index(b, i, j):
        if causal:
            j = jnp.minimum(j, _last_kv_block(i, block_q, block_k, offset))
        return (b, j, 0)
    return index


def _q_index_map(causal, block_q, block_k, offset, rows=False):
    """Index map of a query-side block in the dK/dV grid (bh, j, i), query
    blocks innermost: a causal block before the first that sees key block j
    repeats that first one. `rows`: the [bh, 8, s_q] layout of lse / delta."""
    def index(b, j, i):
        if causal:
            i = jnp.maximum(i, _first_q_block(j, block_q, block_k, offset))
        return (b, 0, i) if rows else (b, i, 0)
    return index


def _unpack(refs, n_in, seg, drop):
    """(the first n_in refs, seg_q_ref, seg_k_ref, seed_ref, the rest) of a
    kernel's positional refs: the segment ids and the seed are operands
    only where the call has them."""
    head, refs = refs[:n_in], refs[n_in:]
    seg_q_ref = seg_k_ref = seed_ref = None
    if seg:
        seg_q_ref, seg_k_ref, *refs = refs
    if drop:
        seed_ref, *refs = refs
    return head, seg_q_ref, seg_k_ref, seed_ref, refs


def _on_tiles(causal, i, j, block_q, block_k, offset, tile):
    """Run `tile(cut)` for the grid step's tile: not at all where a causal
    tile lies wholly in the future, with `cut` (the causal mask applied)
    only where the diagonal crosses it."""
    if not causal:
        tile(False)
        return
    seen = j * block_k <= (i + 1) * block_q - 1 + offset
    cut = (j + 1) * block_k - 1 > i * block_q + offset
    pl.when(seen & cut)(lambda: tile(True))
    pl.when(seen & jnp.logical_not(cut))(lambda: tile(False))


def _compiler_params(block_q, block_k, head_dim):
    """The grid's semantics, and the scoped VMEM a call may take: Mosaic
    keeps a handful of float32 score tiles beside the double-buffered
    operand blocks (1,024 x 1,024 tiles need more than the default 16 MiB)."""
    tiles = 8 * block_q * block_k * 4
    blocks = 16 * max(block_q, block_k) * head_dim * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(tiles + blocks, 16 << 20), 96 << 20)))


def _tile_mask(cut, i, j, block_q, block_k, offset, seg_q_ref, seg_k_ref,
               transposed=False):
    """The pairs of a tile that may attend, or None where all may: the
    causal mask where the diagonal crosses the tile (`cut`), the segment
    ids' equality where the call has them; (block_q, block_k), or with
    `transposed` (block_k, block_q)."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_axis = 1 if transposed else 0
    mask = None
    if cut:
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                       q_axis)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                       1 - q_axis)
        mask = q_pos + offset >= k_pos
    if seg_q_ref is not None:
        sq, sk = seg_q_ref[0, 0], seg_k_ref[0, 0]
        seg_m = sk[:, None] == sq[None, :] if transposed \
            else sq[:, None] == sk[None, :]
        mask = seg_m if mask is None else (mask & seg_m)
    return mask


def _dims(q, k, block_q, block_k):
    """(bh, s_q, s_kv, d, query blocks, key blocks, causal offset)."""
    bh, s_q, d = q.shape
    s_kv = k.shape[1]
    return bh, s_q, s_kv, d, s_q // block_q, s_kv // block_k, s_kv - s_q


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-manual-axes (vma) type of
    `like` — required when the kernel runs inside a shard_map manual
    region that checks vma (pipeline stages), harmless otherwise."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _fwd_kernel(*refs, scale, causal, block_q, block_k, n_kv, offset,
                seg=False, dropout=0.0):
    """One (batch-head, query block, key block). Operands go to the MXU in
    their own type; scores, softmax state and the accumulator are
    float32."""
    (q_ref, k_ref, v_ref), seg_q_ref, seg_k_ref, seed_ref, rest = _unpack(
        refs, 3, seg, dropout)
    o_ref, lse_ref, acc, m_scr, l_scr = rest
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    def tile(cut):
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        mask = _tile_mask(cut, i, j, block_q, block_k, offset, seg_q_ref,
                          seg_k_ref)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if mask is not None and (seg or offset < 0):
            # NEG_INF is finite: a row that has seen nothing yet has
            # s == m_new == NEG_INF and exp(0) == 1 everywhere — zero p by
            # the mask itself so l stays 0 and the epilogue's safe_l emits a
            # zero output row. (A causal row with offset >= 0 sees key 0 in
            # the first block it visits, and exp(NEG_INF - m) is 0.)
            p = jnp.where(mask, p, np.float32(0.0))
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        p_v = p
        if dropout:
            # dropout hits the (eventually l-normalized) weights feeding
            # the value matmul; l itself accumulates the UNdropped sum —
            # exactly softmax followed by inverted dropout
            keep = _dropout_keep(seed_ref[0], bh, i, j,
                                 block_q, block_k, dropout)
            p_v = jnp.where(keep, p, np.float32(0.0)) * np.float32(
                1.0 / (1.0 - dropout))
        pv = jax.lax.dot_general(
            p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * alpha + pv
        m_scr[:, :1] = m_new
        l_scr[:, :1] = l_new

    _on_tiles(causal, i, j, block_q, block_k, offset, tile)

    @pl.when(j == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_row = (m_scr[:, :1] + jnp.log(safe_l))[:, 0]
        # (8, block_q) sublane-replicated layout satisfies TPU tiling
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _seed_arg(seed):
    return jnp.asarray(seed, jnp.int32).reshape(1)


def _side_operands(seg_q, seg_k, seed, heads, block_q, block_k, q_major):
    """(in_specs, operands) of the segment ids and the dropout seed (int32
    [1], or None) behind
    a kernel's main operands. The seg arrays are [batch, 8, s] (NOT
    replicated per head): the index map folds the head dim of the [b*h]
    grid axis away. `q_major`: the grid is (bh, i, j), else (bh, j, i)."""
    in_specs, operands = [], []
    if seg_q is not None:
        h_ = heads
        if q_major:
            qi, ki = (lambda b, i, j: (b // h_, 0, i)), \
                (lambda b, i, j: (b // h_, 0, j))
        else:
            qi, ki = (lambda b, j, i: (b // h_, 0, i)), \
                (lambda b, j, i: (b // h_, 0, j))
        in_specs += [pl.BlockSpec((1, 8, block_q), qi),
                     pl.BlockSpec((1, 8, block_k), ki)]
        operands += [seg_q, seg_k]
    if seed is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    return in_specs, operands


_STATIC = ("scale", "causal", "block_q", "block_k", "heads", "dropout",
           "interpret")


def _call(fn):
    """One trace and ONE Mosaic lowering for each (shapes, blocks) a program
    meets, however many layers call it: a `pallas_call` met bare is traced
    and lowered again at every call site (0.2 s each, 48 a train step of
    twelve layers: ten seconds of every set-up, which no compile cache
    holds). `interpret` is an argument so that a cached trace never
    outlives a change of `_interpret`."""
    return functools.partial(jax.jit, static_argnames=_STATIC)(fn)


@_call
def _fwd_call(q, k, v, seg_q, seg_k, seed, *, scale, causal, block_q,
              block_k, heads, dropout, interpret):
    bh, s_q, s_kv, d, n_q, n_kv, offset = _dims(q, k, block_q, block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv=n_kv, offset=offset, seg=seg_q is not None,
        dropout=dropout)
    kv_map = _kv_index_map(causal, block_q, block_k, offset)
    side_specs, side = _side_operands(seg_q, seg_k, seed, heads, block_q,
                                      block_k, q_major=True)
    with _x64_off():
        return _pc(
            kernel,
            grid=(bh, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), kv_map),
                pl.BlockSpec((1, block_k, d), kv_map),
            ] + side_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            ],
            out_shape=[
                _sds((bh, s_q, d), q.dtype, q),
                _sds((bh, 8, s_q), jnp.float32, q),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
            compiler_params=_compiler_params(block_q, block_k, d),
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v, *side)


def _statics(scale, causal, block_q, block_k, heads, dropout):
    return dict(scale=float(scale), causal=bool(causal), block_q=block_q,
                block_k=block_k, heads=heads, dropout=float(dropout),
                interpret=_interpret())


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, seg_q=None,
               seg_k=None, heads=1, dropout=0.0, seed=None):
    with jax.named_scope(SCOPE):
        out, lse = _fwd_call(
            q, k, v, seg_q, seg_k,
            _seed_arg(seed) if dropout > 0.0 else None,
            **_statics(scale, causal, block_q, block_k, heads, dropout))
    return out, lse[:, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, n_q, offset,
                    seg=False, dropout=0.0):
    """One (batch-head, key block, query block), the tile TRANSPOSED: keys
    down the sublanes, queries across the lanes, so that lse and delta
    broadcast as the rows they are stored as and both accumulating
    products (P^T dO, dS^T Q) are plain row-by-column ones. Operands go to
    the MXU in their own type."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), seg_q_ref, \
        seg_k_ref, seed_ref, rest = _unpack(refs, 6, seg, dropout)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    bh = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(cut):
        q, do = q_ref[0], do_ref[0]
        lse = lse_ref[0, 0:1, :]      # [1, block_q]
        delta = delta_ref[0, 0:1, :]
        s = jax.lax.dot_general(
            k_ref[0], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        mask = _tile_mask(cut, i, j, block_q, block_k, offset, seg_q_ref,
                          seg_k_ref, transposed=True)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None and (seg or offset < 0):
            # mask p (not just s): fully-masked rows have lse == NEG_INF and
            # exp(s - lse) == 1, which would leak garbage into dk/dv
            p = jnp.where(mask, p, np.float32(0.0))
        # regenerate the forward's dropout tile: dv sees the DROPPED
        # normalized weights; the softmax-grad dot product folds into the
        # SAME delta = rowsum(do*o), so only dp gets masked in ds
        p_d = p
        dp_mask = None
        if dropout:
            keep = _dropout_keep(seed_ref[0], bh, i, j, block_q, block_k,
                                 dropout, transposed=True)
            inv = np.float32(1.0 / (1.0 - dropout))
            p_d = jnp.where(keep, p, np.float32(0.0)) * inv
            dp_mask = (keep, inv)
        # dv += p^T do
        dv_acc[:] += jax.lax.dot_general(
            p_d.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do v^T ; ds = p * (dp - delta), the scale on the way out
        dp = jax.lax.dot_general(
            v_ref[0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dp_mask is not None:
            dp = jnp.where(dp_mask[0], dp, np.float32(0.0)) * dp_mask[1]
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_tiles(causal, i, j, block_q, block_k, offset, tile)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * np.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, n_kv, offset,
                   seg=False, dropout=0.0):
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), seg_q_ref, \
        seg_k_ref, seed_ref, rest = _unpack(refs, 6, seg, dropout)
    dq_ref, dq_acc = rest
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(cut):
        k = k_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        mask = _tile_mask(cut, i, j, block_q, block_k, offset, seg_q_ref,
                          seg_k_ref)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None and (seg or offset < 0):
            p = jnp.where(mask, p, np.float32(0.0))
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout:
            keep = _dropout_keep(seed_ref[0], bh, i, j,
                                 block_q, block_k, dropout)
            dp = jnp.where(keep, dp, np.float32(0.0)) * np.float32(
                1.0 / (1.0 - dropout))
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_tiles(causal, i, j, block_q, block_k, offset, tile)

    @pl.when(j == n_kv - 1)
    def _():
        dq_ref[0] = (dq_acc[:] * np.float32(scale)).astype(dq_ref.dtype)


def _bwd_delta(res, g, d_lse=None):
    """Shared backward prologue: delta = rowsum(do*o) (with the lse
    cotangent folded in) plus the sublane-replicated lse/delta layouts
    both passes stream."""
    q, k, v, out, lse = res
    do = g
    bh, s_q, _ = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # [bh, s_q]
    if d_lse is not None:
        # lse cotangent folds into delta: ds = p*(dp - delta) + p*d_lse
        #                                    = p*(dp - (delta - d_lse))
        delta = delta - d_lse.astype(jnp.float32)
    lse8 = jnp.broadcast_to(lse[:, None, :], (bh, 8, s_q))
    delta8 = jnp.broadcast_to(delta[:, None, :], (bh, 8, s_q))
    return do, lse8, delta8


@_call
def _dkv_call(q, k, v, do, lse8, delta8, seg_q, seg_k, seed, *, scale,
              causal, block_q, block_k, heads, dropout, interpret):
    bh, s_q, s_kv, d, n_q, n_kv, offset = _dims(q, k, block_q, block_k)
    kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_q=n_q, offset=offset, seg=seg_q is not None,
        dropout=dropout)
    q_map = _q_index_map(causal, block_q, block_k, offset)
    row_map = _q_index_map(causal, block_q, block_k, offset, rows=True)
    side_specs, side = _side_operands(seg_q, seg_k, seed, heads, block_q,
                                      block_k, q_major=False)
    with _x64_off():
        return _pc(
            kernel,
            grid=(bh, n_kv, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, 8, block_q), row_map),
                pl.BlockSpec((1, 8, block_q), row_map),
            ] + side_specs,
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            ],
            out_shape=[
                _sds((bh, s_kv, d), q.dtype, q),
                _sds((bh, s_kv, d), q.dtype, q),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=_compiler_params(block_q, block_k, d),
            interpret=interpret,
            name="flash_bwd_dkv",
        )(q, k, v, do, lse8, delta8, *side)


@_call
def _dq_call(q, k, v, do, lse8, delta8, seg_q, seg_k, seed, *, scale,
             causal, block_q, block_k, heads, dropout, interpret):
    bh, s_q, s_kv, d, n_q, n_kv, offset = _dims(q, k, block_q, block_k)
    kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv=n_kv, offset=offset, seg=seg_q is not None,
        dropout=dropout)
    kv_map = _kv_index_map(causal, block_q, block_k, offset)
    side_specs, side = _side_operands(seg_q, seg_k, seed, heads, block_q,
                                      block_k, q_major=True)
    with _x64_off():
        return _pc(
            kernel,
            grid=(bh, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), kv_map),
                pl.BlockSpec((1, block_k, d), kv_map),
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            ] + side_specs,
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=_sds((bh, s_q, d), q.dtype, q),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=_compiler_params(block_q, block_k, d),
            interpret=interpret,
            name="flash_bwd_dq",
        )(q, k, v, do, lse8, delta8, *side)


def _run_dkv_pass(q, k, v, do, lse8, delta8, scale, causal, block_q,
                  block_k, seg_q=None, seg_k=None, heads=1, dropout=0.0,
                  seed=None):
    """dkv backward pass: grid parallel over k blocks (contraction over q
    blocks innermost, accumulators in VMEM scratch) with its OWN
    block_q/block_k choice, independent of the dq pass."""
    with jax.named_scope(SCOPE):
        return _dkv_call(
            q, k, v, do, lse8, delta8, seg_q, seg_k,
            _seed_arg(seed) if dropout > 0.0 else None,
            **_statics(scale, causal, block_q, block_k, heads, dropout))


def _run_dq_pass(q, k, v, do, lse8, delta8, scale, causal, block_q,
                 block_k, seg_q=None, seg_k=None, heads=1, dropout=0.0,
                 seed=None):
    """dq backward pass: grid parallel over q blocks (contraction over k
    blocks innermost) with its OWN block_q/block_k choice."""
    with jax.named_scope(SCOPE):
        return _dq_call(
            q, k, v, do, lse8, delta8, seg_q, seg_k,
            _seed_arg(seed) if dropout > 0.0 else None,
            **_statics(scale, causal, block_q, block_k, heads, dropout))


def _flash_bwd_split(res, g, scale, causal, dq_blocks=(DEFAULT_BLOCK_Q,
                                                       DEFAULT_BLOCK_K),
                     dkv_blocks=(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K),
                     seg_q=None, seg_k=None, heads=1, d_lse=None,
                     dropout=0.0, seed=None):
    """The backward as two passes, dkv then dq, each with its own
    (block_q, block_k). Dropout regenerates the forward's threefry mask
    from GLOBAL (q, k) coordinates, so the mask is bit-identical
    regardless of either pass's block choice."""
    with jax.named_scope(SCOPE):
        do, lse8, delta8 = _bwd_delta(res, g, d_lse)
    q, k, v = res[0], res[1], res[2]
    dk, dv = _run_dkv_pass(q, k, v, do, lse8, delta8, scale, causal,
                           dkv_blocks[0], dkv_blocks[1], seg_q=seg_q,
                           seg_k=seg_k, heads=heads, dropout=dropout,
                           seed=seed)
    dq = _run_dq_pass(q, k, v, do, lse8, delta8, scale, causal,
                      dq_blocks[0], dq_blocks[1], seg_q=seg_q,
                      seg_k=seg_k, heads=heads, dropout=dropout,
                      seed=seed)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP over [bh, s, d])
#
# `blocks` is what `_flash_tiling` answers: the (block_q, block_k) of the
# forward, of the dK/dV pass and of the dQ pass.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, scale, causal, blocks):
    out, _ = _flash_fwd(q, k, v, scale, causal, *blocks[0])
    return out


def _flash_bhsd_fwd(q, k, v, scale, causal, blocks):
    out, lse = _flash_fwd(q, k, v, scale, causal, *blocks[0])
    return out, (q, k, v, out, lse)


# From this sequence length on attention takes the Pallas kernels: `use_flash`
# for the call, and the backward below (streamed passes against XLA's
# recompute grad, which materializes the O(s^2) scores). One layer's
# attention over 8,192 tokens of 16 heads x 128 in bf16 on a v5e, ms, XLA
# (`_sdpa_reference`) / the kernels at `_flash_tiling`'s large blocks (PERF.md
# section 6, PR 34's two microbenches; a training call is the forward + the
# forward recomputed under `jax.checkpoint` + the backward):
#
#   sequence                      1,024         2,048         4,096
#   causal, training           6.05 / 3.73  10.46 / 5.46  19.82 / 8.00
#   causal, forward alone      1.42 / 0.71   2.54 / 1.15   4.85 / 1.64
#   not causal, training       5.92 / 4.23  10.34 / 6.59  19.51 / 11.19
#   not causal, forward alone  1.42 / 0.88   2.54 / 1.36   4.78 / 2.29
#
# (1,024 queries on 4,096 keys, not causal: 10.77 / 6.87 and 2.79 / 1.31; one
# row of 16 heads, causal: 2.90 / 1.42 at 2,048, a tie of 0.70 / 0.72 at
# 1,024.) So at the large blocks every mode starts at 1,024, the shortest
# length measured (512 was not, and stays XLA's). At 128 x 128 blocks XLA is
# the faster at every length measured (bf16: 13.37 / 23.41 / 43.41 ms
# training; float32 operands at [2, 4,096, 16, 128]: XLA 39.5, the kernels
# 47.7), and such a shape takes the kernels from 4,096 on, as it did before
# PR 34, for the memory: that float32 layer's temporaries are 4.52 GB
# through XLA and 0.40 GB through the kernels (`memory_analysis()` on the
# chip), and XLA's grow with s^2.
_PALLAS_MIN_SEQ = 1024
_PALLAS_SMALL_BLOCK_MIN_SEQ = 4096


def _min_seq(blocks):
    """The shortest query length at which a call at `blocks` takes the
    kernels (tests replace it to force them at small shapes)."""
    if (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K) in blocks:
        return _PALLAS_SMALL_BLOCK_MIN_SEQ
    return _PALLAS_MIN_SEQ


def use_flash(seq_q, seq_kv, head_dim, dropout=0.0, dtype=jnp.bfloat16):
    """The one choice `scaled_dot_product_attention` asks for an unmasked
    call, training or not, causal or not: the flash kernel where it
    `supports` the shape and the sequence reaches the threshold of the
    blocks `_flash_tiling` answers; with dropout only under
    FLAGS_flash_dropout_kernel (ROADMAP D2 decides the in-kernel dropout
    path)."""
    from ..framework import config as _config

    blocks = _flash_tiling(seq_q, seq_kv, head_dim, dtype)
    return (supports(seq_q, seq_kv, head_dim)
            and seq_q >= _min_seq(blocks)
            and (dropout == 0.0
                 or bool(_config.get_flag("FLAGS_flash_dropout_kernel",
                                          False))))


def _bwd_use_xla(s_q, blocks):
    """XLA recompute grad below the threshold of the call's blocks,
    streamed Pallas kernels from it on."""
    return s_q < _min_seq(blocks)


def _xla_ref_fwd(q_, k_, v_, scale, causal, seg_q=None, seg_k=None,
                 heads=1):
    """Dense XLA reference forward over [bh, s, d]: (out, lse), for the
    recompute backward's vjp."""
    s_ = jax.lax.dot_general(
        q_, k_, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * np.float32(scale)
    mask = None
    if causal:
        sq, sk = s_.shape[-2], s_.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
    if seg_q is not None:
        # [b, 8, s] -> per-(b*h) rows via repeat on the batch dim
        sq = jnp.repeat(seg_q[:, 0, :], heads, axis=0)
        sk = jnp.repeat(seg_k[:, 0, :], heads, axis=0)
        seg_m = sq[:, :, None] == sk[:, None, :]
        mask = seg_m if mask is None else (mask & seg_m)
    if mask is not None:
        s_ = jnp.where(mask, s_, NEG_INF)
    lse_ = jax.scipy.special.logsumexp(s_, axis=-1)
    p = jnp.exp(s_ - lse_[..., None]).astype(q_.dtype)
    if mask is not None:
        # NEG_INF is finite: a fully-masked row's p is uniform (not
        # NaN) — zero it by the mask so those rows emit 0
        p = jnp.where(mask, p, np.float32(0.0)).astype(q_.dtype)
    o_ = jax.lax.dot_general(
        p, v_, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).astype(q_.dtype)
    return o_, lse_


def _xla_ref_bwd(res, g, scale, causal, seg_q=None, seg_k=None, heads=1,
                 d_lse=None):
    """XLA-fused backward via recompute, below the blocks' `_min_seq`, where
    the O(s^2) score matrix is small. The ONE reference implementation
    also serves the lse-returning variant (d_lse is the lse cotangent,
    zeros when the caller only differentiates the output)."""
    q, k, v, _, _ = res

    def ref(q_, k_, v_):
        return _xla_ref_fwd(q_, k_, v_, scale, causal, seg_q=seg_q,
                            seg_k=seg_k, heads=heads)

    _, vjp = jax.vjp(ref, q, k, v)
    if d_lse is None:
        d_lse = jnp.zeros(g.shape[:2], jnp.float32)
    return vjp((g, d_lse.astype(jnp.float32)))


def _dispatch_bwd(res, g, scale, causal, blocks, d_lse=None, **seg):
    """Backward of the paths without dropout: `seg` is the segment ids'
    (seg_q, seg_k, heads), or nothing."""
    if _bwd_use_xla(res[0].shape[1], blocks):
        return _xla_ref_bwd(res, g, scale, causal, d_lse=d_lse, **seg)
    return _flash_bwd_split(res, g, scale, causal, dq_blocks=blocks[2],
                            dkv_blocks=blocks[1], d_lse=d_lse, **seg)


def _flash_bhsd_bwd(scale, causal, blocks, res, g):
    return _dispatch_bwd(res, g, scale, causal, blocks)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


# segmented (varlen) variant: seg_q8/seg_k8 are [bh, 8, s] int32
# sublane-replicated segment ids; cross-segment pairs are masked in all
# four kernels (fwd, dkv, dq, and the short-seq XLA fallback backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_bhsd_seg(q, k, v, seg_q8, seg_k8, scale, causal, blocks, heads):
    out, _ = _flash_fwd(q, k, v, scale, causal, *blocks[0],
                        seg_q=seg_q8, seg_k=seg_k8, heads=heads)
    return out


def _flash_bhsd_seg_fwd(q, k, v, seg_q8, seg_k8, scale, causal, blocks,
                        heads):
    out, lse = _flash_fwd(q, k, v, scale, causal, *blocks[0],
                          seg_q=seg_q8, seg_k=seg_k8, heads=heads)
    return out, (q, k, v, out, lse, seg_q8, seg_k8)


def _flash_bhsd_seg_bwd(scale, causal, blocks, heads, res, g):
    *res, seg_q8, seg_k8 = res
    dq, dk, dv = _dispatch_bwd(tuple(res), g, scale, causal, blocks,
                               seg_q=seg_q8, seg_k=seg_k8, heads=heads)
    return dq, dk, dv, None, None


_flash_bhsd_seg.defvjp(_flash_bhsd_seg_fwd, _flash_bhsd_seg_bwd)


# dropout variants: the backward ALWAYS runs the Pallas kernels — the
# in-kernel threefry mask must be regenerated bit-exactly, which the XLA
# short-seq fallback cannot do.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bhsd_drop(q, k, v, seed, scale, causal, blocks, dropout):
    out, _ = _flash_fwd(q, k, v, scale, causal, *blocks[0],
                        dropout=dropout, seed=seed)
    return out


def _flash_bhsd_drop_fwd(q, k, v, seed, scale, causal, blocks, dropout):
    out, lse = _flash_fwd(q, k, v, scale, causal, *blocks[0],
                          dropout=dropout, seed=seed)
    return out, (q, k, v, out, lse, seed)


def _flash_bhsd_drop_bwd(scale, causal, blocks, dropout, res, g):
    *res, seed = res
    dq, dk, dv = _flash_bwd_split(
        tuple(res), g, scale, causal, dq_blocks=blocks[2],
        dkv_blocks=blocks[1], dropout=dropout, seed=seed)
    return dq, dk, dv, None


_flash_bhsd_drop.defvjp(_flash_bhsd_drop_fwd, _flash_bhsd_drop_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_bhsd_seg_drop(q, k, v, seg_q8, seg_k8, seed, scale, causal,
                         blocks, heads, dropout):
    out, _ = _flash_fwd(q, k, v, scale, causal, *blocks[0],
                        seg_q=seg_q8, seg_k=seg_k8, heads=heads,
                        dropout=dropout, seed=seed)
    return out


def _flash_bhsd_seg_drop_fwd(q, k, v, seg_q8, seg_k8, seed, scale, causal,
                             blocks, heads, dropout):
    out, lse = _flash_fwd(q, k, v, scale, causal, *blocks[0],
                          seg_q=seg_q8, seg_k=seg_k8, heads=heads,
                          dropout=dropout, seed=seed)
    return out, (q, k, v, out, lse, seg_q8, seg_k8, seed)


def _flash_bhsd_seg_drop_bwd(scale, causal, blocks, heads, dropout, res, g):
    *res, seg_q8, seg_k8, seed = res
    dq, dk, dv = _flash_bwd_split(
        tuple(res), g, scale, causal, dq_blocks=blocks[2],
        dkv_blocks=blocks[1], seg_q=seg_q8, seg_k=seg_k8, heads=heads,
        dropout=dropout, seed=seed)
    return dq, dk, dv, None, None, None


_flash_bhsd_seg_drop.defvjp(_flash_bhsd_seg_drop_fwd,
                            _flash_bhsd_seg_drop_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd_lse(q, k, v, scale, causal, blocks):
    return _flash_fwd(q, k, v, scale, causal, *blocks[0])


def _flash_bhsd_lse_fwd(q, k, v, scale, causal, blocks):
    out, lse = _flash_fwd(q, k, v, scale, causal, *blocks[0])
    return (out, lse), (q, k, v, out, lse)


def _flash_bhsd_lse_bwd(scale, causal, blocks, res, g):
    g_out, g_lse = g
    return _dispatch_bwd(res, g_out, scale, causal, blocks, d_lse=g_lse)


_flash_bhsd_lse.defvjp(_flash_bhsd_lse_fwd, _flash_bhsd_lse_bwd)


def _blocks_for(q, k, block_q, block_k):
    """The three passes' blocks of a call over q / k [b, s, h, d]: the
    caller's at all three where it names them (a varlen call pads to its
    own), `_flash_tiling`'s otherwise; ValueError where they do not tile
    the shape."""
    s_q, d, s_kv = q.shape[1], q.shape[3], k.shape[1]
    if block_q is None and block_k is None:
        blocks = _flash_tiling(s_q, s_kv, d, q.dtype)
    else:
        blocks = ((block_q or DEFAULT_BLOCK_Q,
                   block_k or DEFAULT_BLOCK_K),) * 3
    if not all(supports(s_q, s_kv, d, bq, bk) for bq, bk in blocks):
        raise ValueError(
            f"flash_attention: unsupported shape seq_q={s_q} seq_kv={s_kv} "
            f"d={d} (need multiples of {blocks[0][0]}/{blocks[0][1]}/128)")
    return blocks


def _to_bhsd(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def flash_attention_with_lse_bshd(q, k, v, causal=False, scale=None,
                                  block_q=None, block_k=None):
    """Like flash_attention_bshd but also returns the row logsumexp
    ([b, h, s_q], f32) — the merge statistic ring attention accumulates
    across KV blocks. Both outputs are differentiable (the lse cotangent
    folds into the flash backward's delta term)."""
    b, s_q, h, d = q.shape
    blocks = _blocks_for(q, k, block_q, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    out, lse = _flash_bhsd_lse(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
                               float(scale), bool(causal), blocks)
    return (jnp.swapaxes(out.reshape(b, h, s_q, d), 1, 2),
            lse.reshape(b, h, s_q))


def supports(seq_q, seq_kv, head_dim, block_q=None, block_k=None):
    """The kernels take the shape: at the caller's blocks where it names
    them, else at the smallest (`_flash_tiling` answers those for a length
    its larger ones do not divide)."""
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    return (seq_q % block_q == 0 and seq_kv % block_k == 0
            and head_dim % 128 == 0 and seq_q >= block_q
            and seq_kv >= block_k)


def _seg8(seg, b, s):
    """[b, s] int32 segment ids -> [b, 8, s] sublane-replicated layout
    (per-head replication happens in the BlockSpec index map, not HBM)."""
    seg = jnp.asarray(seg, jnp.int32)
    return jnp.broadcast_to(seg[:, None, :], (b, 8, s))


def flash_attention_bshd(q, k, v, causal=False, scale=None,
                         block_q=None, block_k=None,
                         segment_ids_q=None, segment_ids_k=None,
                         dropout=0.0, dropout_seed=None):
    """q/k/v: [batch, seq, heads, head_dim] (paddle layout) -> same shape.

    The blocks of the three passes come from `_flash_tiling` (the shape and
    the type alone); `block_q` / `block_k` name them for all three.

    segment_ids_q/k ([batch, seq] int32) activate varlen masking: tokens
    attend only within equal segment ids (the packed-sequence contract of
    the reference's flash_attn varlen kernels).

    dropout > 0 applies in-kernel inverted dropout to the softmax weights
    (reference flash_attn dropout_p); `dropout_seed` (int or int32
    scalar) keys the counter-based threefry mask, so the same seed
    reproduces the same mask — pass a fresh seed per training step.

    Raises ValueError for unsupported shapes — callers (F.sdpa) ask
    `supports()` first and take the fused XLA path themselves.
    """
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    blocks = _blocks_for(q, k, block_q, block_k)
    if dropout and dropout_seed is None:
        raise ValueError("flash_attention: dropout requires dropout_seed")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qt, kt, vt = _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)
    if segment_ids_q is not None:
        sq8 = _seg8(segment_ids_q, b, s_q)
        sk8 = _seg8(segment_ids_k, b, s_kv)
        if dropout:
            out = _flash_bhsd_seg_drop(qt, kt, vt, sq8, sk8,
                                       _seed_arg(dropout_seed),
                                       float(scale), bool(causal), blocks,
                                       h, float(dropout))
        else:
            out = _flash_bhsd_seg(qt, kt, vt, sq8, sk8, float(scale),
                                  bool(causal), blocks, h)
    elif dropout:
        out = _flash_bhsd_drop(qt, kt, vt, _seed_arg(dropout_seed),
                               float(scale), bool(causal), blocks,
                               float(dropout))
    else:
        out = _flash_bhsd(qt, kt, vt, float(scale), bool(causal), blocks)
    return jnp.swapaxes(out.reshape(b, h, s_q, d), 1, 2)


# ---------------------------------------------------------------------------
# grouped-query causal forward with an optional window (serving's prefill)
# ---------------------------------------------------------------------------

GQA_BLOCK_Q = 128
GQA_BLOCK_K = 512
# The kernel serves from this many tokens on. One layer's attention at [1, s,
# 32 heads on 4, 128] bf16 on a v5e, the kernel against XLA's blocked
# `gqa_attention` (PERF.md section 6, PR 31): 0.94 against 1.72 ms at 1,024,
# 1.38 / 4.94 at 2,048, 2.78 / 16.8 at 4,096, 5.60 / 773 at 8,192 with a
# window of 2,048, and much the same without one below 4,096. 512 tokens, the
# one shorter length that tiles, was not measured and stays XLA's.
GQA_MIN_SEQ = 1024


def _gqa_key_blocks(i, block_q, block_k, window):
    """(first, last) key block a query block `i` sees: causal, and with a
    window no key more than `window - 1` positions behind the query."""
    last = ((i + 1) * block_q - 1) // block_k
    if window is None:
        return 0, last
    return jnp.maximum(i * block_q - window + 1, 0) // block_k, last


def _gqa_tiling(seq, group, window):
    """(query heads a block, key block, key steps a query block) from the
    shape alone. The query heads of a kv head share a block up to 1,024
    rows of queries (8 heads: the float32 scores of 16 would not fit beside
    their softmax), a larger group in several. A window no wider than
    `GQA_BLOCK_K` has a key block of its own size (of 512 keys a query
    block of 128 with a window of 128 sees 255) and walks ONLY the blocks a
    query block can see, counted from its first (`narrow`: the grid's key
    steps are those, not the sequence's); a wider window and none walk
    every block of the sequence and skip the ones outside."""
    gb = max(g for g in range(1, group + 1)
             if group % g == 0 and g * GQA_BLOCK_Q <= 1024)
    if window is None or window >= GQA_BLOCK_K:
        return gb, GQA_BLOCK_K, None
    bk = 128
    while bk < window:
        bk *= 2

    def seen(i):
        lo = max(i * GQA_BLOCK_Q - window + 1, 0) // bk
        return ((i + 1) * GQA_BLOCK_Q - 1) // bk - lo + 1

    return gb, bk, max(seen(i) for i in range(seq // GQA_BLOCK_Q))


def _gqa_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, block_q, block_k,
                    n_kv, window, narrow=False, sunk=False):
    """One (kv head, query block, key block): the query heads of a block
    are the rows of one [heads * block_q, d] operand, so a key block is
    read once for all of them. Operands go to the MXU in their own type;
    scores, softmax and the accumulator are float32. `narrow`: grid step j
    is the j-th key block the query block SEES (`_gqa_tiling`). `sunk`: an
    operand after V, a sink logit a query head across 128 lanes, which the
    softmax's state starts from (it stands in the denominator and carries
    no value)."""
    if sunk:
        sink_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    i, step = pl.program_id(1), pl.program_id(2)
    group = q_ref.shape[1]
    rows = group * block_q

    @pl.when(step == 0)
    def _():
        if sunk:
            m_scr[...] = jnp.broadcast_to(
                sink_ref[0][:, None, :], (group, block_q, 128)
            ).reshape(rows, 128)
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    lo, hi = _gqa_key_blocks(i, block_q, block_k, window)
    j = lo + step if narrow else step

    @pl.when((j >= lo) & (j <= hi))
    def _():
        q = q_ref[0].reshape(rows, q_ref.shape[-1])
        k, v = k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_q, block_k), 1).reshape(rows, block_k)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        seen = q_pos >= k_pos
        if window is not None:
            seen = seen & (q_pos - k_pos < window)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), np.float32(0.0))
        l_scr[:, :1] = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_new

    @pl.when(step == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        out = acc[...] / jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def gqa_supports(seq, head_dim, value_dim=None):
    """A key of whole or one and a half lane tiles (128, 192, 256, ...), a
    value of whole ones."""
    value_dim = head_dim if value_dim is None else value_dim
    return seq % GQA_BLOCK_K == 0 and head_dim >= 128 \
        and head_dim % 64 == 0 and value_dim % 128 == 0


def use_gqa_flash(seq, head_dim, value_dim=None):
    """The one choice a grouped-query causal prefill asks: this kernel from
    `GQA_MIN_SEQ` tokens on, where the shape tiles; interpret mode (the
    CPU) included, so the tests run the same body."""
    return gqa_supports(seq, head_dim, value_dim) and seq >= GQA_MIN_SEQ


def flash_attention_gqa_bshd(q, k, v, window=None, scale=None, sink=None):
    """Causal self-attention of q [b, s, h, d] over k [b, s, h_kv, d] and
    v [b, s, h_kv, d_v] (query head n reads kv head n // (h / h_kv); a
    value may be narrower than a key, and the output is as wide as a
    value); with `window`, position i sees j only if i - j < window, and
    key blocks wholly outside a query block's window are neither fetched
    nor multiplied; with `sink` [h], a logit a head stands in the softmax's
    denominator and carries no value. Forward only."""
    b, s, h, d = q.shape
    h_kv, d_v = k.shape[2], v.shape[3]
    group = h // h_kv
    if not gqa_supports(s, d, d_v):
        raise ValueError(
            f"flash_attention_gqa: unsupported shape seq={s} d={d} "
            f"d_v={d_v} (need multiples of {GQA_BLOCK_K}/64/128)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bq = GQA_BLOCK_Q
    gb, bk, steps = _gqa_tiling(s, group, window)
    narrow = steps is not None
    n_q, n_kv = s // bq, steps if narrow else s // bk
    n_gb = group // gb
    # [b * h_kv * n_gb, gb, s, d] and [b * h_kv, s, d]
    qt = q.reshape(b, s, h_kv * n_gb, gb, d).transpose(0, 2, 3, 1, 4).reshape(
        b * h_kv * n_gb, gb, s, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h_kv, s, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h_kv, s, d_v)

    def kv_map(g, i, j):
        # a block outside what the query block sees repeats the nearest one
        # inside, so the pipeline copies nothing for it
        lo, hi = _gqa_key_blocks(i, bq, bk, window)
        return (g if n_gb == 1 else g // n_gb,
                jnp.minimum(lo + j, hi) if narrow else jnp.clip(j, lo, hi), 0)

    q_map = lambda g, i, j: (g, 0, i, 0)  # noqa: E731
    kernel = functools.partial(
        _gqa_fwd_kernel, scale=float(scale), block_q=bq, block_k=bk,
        n_kv=n_kv, window=None if window is None else int(window),
        narrow=narrow, sunk=sink is not None)
    in_specs = [pl.BlockSpec((1, gb, bq, d), q_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d_v), kv_map)]
    operands = [qt, kt, vt]
    if sink is not None:
        # [b * h_kv * n_gb, gb, 128]: a query head's logit across the lanes
        # of the state it starts
        in_specs.append(pl.BlockSpec((1, gb, 128),
                                     lambda g, i, j: (g, 0, 0)))
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, h // gb, gb, 1),
            (b, h // gb, gb, 128)).reshape(b * h_kv * n_gb, gb, 128))
    with _x64_off():
        out = _pc(
            kernel,
            grid=(b * h_kv * n_gb, n_q, n_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, gb, bq, d_v), q_map),
            out_shape=jax.ShapeDtypeStruct(qt.shape[:3] + (d_v,), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((gb * bq, d_v), jnp.float32),
                pltpu.VMEM((gb * bq, 128), jnp.float32),
                pltpu.VMEM((gb * bq, 128), jnp.float32),
            ],
            interpret=_interpret(),
        )(*operands)
    return out.reshape(b, h_kv, group, s, d_v).transpose(
        0, 3, 1, 2, 4).reshape(b, s, h, d_v)


def flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale=None, dropout=0.0, causal=False,
                        return_softmax=False, block_q=DEFAULT_BLOCK_Q,
                        block_k=DEFAULT_BLOCK_K, dropout_seed=None):
    """Varlen flash attention over PACKED sequences (reference:
    paddle.nn.functional.flash_attention.flash_attn_unpadded /
    phi flash_attn_varlen kernels — SURVEY.md §2.1 fusion row).

    q/k/v: [total_tokens, heads, head_dim]; cu_seqlens_*: [n_seqs+1] int32
    prefix sums. Returns ([total_tokens, heads, head_dim], None).

    Implementation: the packed stream runs as ONE batch-1 kernel call with
    per-token segment ids; cross-sequence attention is masked inside the
    Pallas kernels. causal=True requires cu_seqlens_q == cu_seqlens_k
    (self-attention packing — global causal + segment equality is then
    exactly per-sequence causal).
    """
    if dropout and dropout_seed is None:
        raise ValueError("flash_attn_unpadded: dropout requires "
                         "dropout_seed")
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    cu_q = jnp.asarray(cu_seqlens_q, jnp.int32)
    cu_k = jnp.asarray(cu_seqlens_k, jnp.int32)
    total_q, h, d = q.shape
    total_k = k.shape[0]
    if causal:
        if cu_q.shape != cu_k.shape:
            raise ValueError(
                "flash_attn_unpadded(causal=True) needs matching q/k packing")
        try:  # value check when concrete (host arrays — the common case)
            if bool(np.any(np.asarray(cu_q) != np.asarray(cu_k))):
                raise ValueError(
                    "flash_attn_unpadded(causal=True) needs cu_seqlens_q == "
                    "cu_seqlens_k (global causal positions must align per "
                    "sequence)")
        except jax.errors.TracerArrayConversionError:
            pass  # traced: caller's responsibility

    pad_q = -(-total_q // block_q) * block_q
    pad_k = -(-total_k // block_k) * block_k
    if causal:
        # the kernel's causal offset is s_kv - s_q; unequal padding would
        # shift the diagonal and leak future tokens
        common = max(pad_q, pad_k)
        lcm = block_q * block_k // math.gcd(block_q, block_k)
        common = -(-common // lcm) * lcm
        pad_q = pad_k = common
    qp = jnp.zeros((pad_q, h, d), q.dtype).at[:total_q].set(q)
    kp = jnp.zeros((pad_k, h, d), k.dtype).at[:total_k].set(k)
    vp = jnp.zeros((pad_k, h, d), v.dtype).at[:total_k].set(v)
    # token -> sequence index; q padding -1, k padding -2 (never equal)
    pos_q = jnp.arange(pad_q, dtype=jnp.int32)
    pos_k = jnp.arange(pad_k, dtype=jnp.int32)
    seg_q = jnp.where(pos_q < total_q,
                      jnp.searchsorted(cu_q[1:], pos_q, side="right")
                      .astype(jnp.int32), -1)
    seg_k = jnp.where(pos_k < total_k,
                      jnp.searchsorted(cu_k[1:], pos_k, side="right")
                      .astype(jnp.int32), -2)
    # causal + equal packing: global causal positions already align per
    # sequence, so the global tril mask composes with segment equality
    out = flash_attention_bshd(
        qp[None], kp[None], vp[None], causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
        segment_ids_q=seg_q[None], segment_ids_k=seg_k[None],
        dropout=dropout, dropout_seed=dropout_seed)
    out = out[0, :total_q]
    if return_softmax:
        return out, None
    return out, None
