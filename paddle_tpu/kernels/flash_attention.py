"""Pallas flash attention (TPU) — the Phi flash_attn kernel equivalent
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party
flashattn — SURVEY.md §2.1 "Phi fusion kernels", §7 phase 9).

Layout: paddle bshd [batch, seq, heads, head_dim]. Forward is the online-
softmax streaming kernel (never materializes [s, s]); backward recomputes
p-blocks from the saved row logsumexp (standard flash backward, two kernels:
dk/dv then dq). Grids put the contraction dim innermost so accumulators live
in VMEM scratch across grid steps; blocks are MXU-aligned (128).

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


def _dropout_keep(seed, bh, i, j, block_q, block_k, rate):
    """Boolean keep-mask for one (block_q, block_k) attention tile.
    Counters are the global (q, k) token positions, key is (seed, bh)."""
    rows = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    bits = _threefry2x32(seed, bh, rows, cols)
    # low 23 bits -> uniform [0, 1): non-negative regardless of sign bit
    u = (bits & np.int32(0x7FFFFF)).astype(jnp.float32) * np.float32(
        1.0 / (1 << 23))
    return u >= np.float32(rate)


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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                scale, causal, block_q, block_k, n_kv, offset,
                seg_q_ref=None, seg_k_ref=None, dropout=0.0, seed_ref=None):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    run = True
    if causal:
        # block fully in the future -> skip (bottom-right aligned)
        run = j * block_k <= (i + 1) * block_q - 1 + offset

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        mask = None
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos + offset >= k_pos
        if seg_q_ref is not None:
            sq = seg_q_ref[0, 0]
            sk = seg_k_ref[0, 0]
            seg_m = sq[:, None] == sk[None, :]
            mask = seg_m if mask is None else (mask & seg_m)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if mask is not None:
            # NEG_INF is finite: a fully-masked row has s == m_new == NEG_INF
            # and exp(0) == 1 everywhere — zero p by the mask itself so l
            # stays 0 and the epilogue's safe_l emits a zero output row
            p = jnp.where(mask, p, np.float32(0.0))
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
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
            p_v, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * alpha + pv
        m_scr[:, :1] = m_new
        l_scr[:, :1] = l_new

    @pl.when(j == n_kv - 1)
    def _():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_row = (m_scr[:, :1] + jnp.log(safe_l))[:, 0]
        # (8, block_q) sublane-replicated layout satisfies TPU tiling
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _fwd_kernel_seg(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref, o_ref,
                    lse_ref, acc, m_scr, l_scr, **params):
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                seg_q_ref=seg_q_ref, seg_k_ref=seg_k_ref, **params)


def _fwd_kernel_drop(q_ref, k_ref, v_ref, seed_ref, o_ref, lse_ref, acc,
                     m_scr, l_scr, **params):
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                seed_ref=seed_ref, **params)


def _fwd_kernel_seg_drop(q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref,
                         seed_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                         **params):
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                seg_q_ref=seg_q_ref, seg_k_ref=seg_k_ref,
                seed_ref=seed_ref, **params)


def _seed_arg(seed):
    return jnp.asarray(seed, jnp.int32).reshape(1)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, seg_q=None,
               seg_k=None, heads=1, dropout=0.0, seed=None):
    bh, s_q, d = q.shape
    s_kv = k.shape[1]
    n_q = s_q // block_q
    n_kv = s_kv // block_k
    seg = seg_q is not None
    drop = dropout > 0.0
    params = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, n_kv=n_kv, offset=s_kv - s_q,
                  dropout=float(dropout))
    kern_fn = {(False, False): _fwd_kernel,
               (True, False): _fwd_kernel_seg,
               (False, True): _fwd_kernel_drop,
               (True, True): _fwd_kernel_seg_drop}[(seg, drop)]
    kernel = functools.partial(kern_fn, **params)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    args = [q, k, v]
    if seg:
        # seg arrays are [batch, 8, s] (NOT replicated per head); the index
        # map folds the head dim of the [b*h] grid axis away
        h_ = heads
        in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b // h_, 0, i)),
            pl.BlockSpec((1, 8, block_k), lambda b, i, j: (b // h_, 0, j)),
        ]
        args += [seg_q, seg_k]
    if drop:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_seed_arg(seed))
    with _x64_off():
        out, lse = _pc(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=in_specs,
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
        interpret=_interpret(),
    )(*args)
    return out, lse[:, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, n_q, offset,
                    seg_q_ref=None, seg_k_ref=None, dropout=0.0,
                    seed_ref=None):
    bh = pl.program_id(0)
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = j * block_k <= (i + 1) * block_q - 1 + offset

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            cmask = q_pos + offset >= k_pos
            s = jnp.where(cmask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(cmask, p, np.float32(0.0))
        if seg_q_ref is not None:
            seg_m = seg_q_ref[0, 0][:, None] == seg_k_ref[0, 0][None, :]
            # mask p (not just s): fully-masked rows have lse == NEG_INF and
            # exp(s - lse) == 1, which would leak garbage into dk/dv
            p = jnp.where(seg_m, p, np.float32(0.0))
        # regenerate the forward's dropout tile: dv sees the DROPPED
        # normalized weights; the softmax-grad dot product folds into the
        # SAME delta = rowsum(do*o), so only dp gets masked in ds
        p_d = p
        dp_mask = None
        if dropout:
            keep = _dropout_keep(seed_ref[0], bh, i, j,
                                 block_q, block_k, dropout)
            inv = np.float32(1.0 / (1.0 - dropout))
            p_d = jnp.where(keep, p, np.float32(0.0)) * inv
            dp_mask = (keep, inv)
        # dv += p^T do
        dv_acc[:] += jax.lax.dot_general(
            p_d, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dp_mask is not None:
            dp = jnp.where(dp_mask[0], dp, np.float32(0.0)) * dp_mask[1]
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, n_kv, offset,
                   seg_q_ref=None, seg_k_ref=None, dropout=0.0,
                   seed_ref=None):
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = j * block_k <= (i + 1) * block_q - 1 + offset

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            cmask = q_pos + offset >= k_pos
            s = jnp.where(cmask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(cmask, p, np.float32(0.0))
        if seg_q_ref is not None:
            seg_m = seg_q_ref[0, 0][:, None] == seg_k_ref[0, 0][None, :]
            p = jnp.where(seg_m, p, np.float32(0.0))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout:
            keep = _dropout_keep(seed_ref[0], bh, i, j,
                                 block_q, block_k, dropout)
            dp = jnp.where(keep, dp, np.float32(0.0)) * np.float32(
                1.0 / (1.0 - dropout))
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel_seg(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        seg_q_ref, seg_k_ref, dk_ref, dv_ref, dk_acc,
                        dv_acc, **params):
    _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    seg_q_ref=seg_q_ref, seg_k_ref=seg_k_ref, **params)


def _bwd_dq_kernel_seg(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       seg_q_ref, seg_k_ref, dq_ref, dq_acc, **params):
    _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, seg_q_ref=seg_q_ref, seg_k_ref=seg_k_ref,
                   **params)


def _bwd_dkv_kernel_drop(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         seed_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                         **params):
    _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, seed_ref=seed_ref,
                    **params)


def _bwd_dq_kernel_drop(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        seed_ref, dq_ref, dq_acc, **params):
    _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, seed_ref=seed_ref, **params)


def _bwd_dkv_kernel_seg_drop(q_ref, k_ref, v_ref, do_ref, lse_ref,
                             delta_ref, seg_q_ref, seg_k_ref, seed_ref,
                             dk_ref, dv_ref, dk_acc, dv_acc, **params):
    _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, seg_q_ref=seg_q_ref,
                    seg_k_ref=seg_k_ref, seed_ref=seed_ref, **params)


def _bwd_dq_kernel_seg_drop(q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, seg_q_ref, seg_k_ref, seed_ref,
                            dq_ref, dq_acc, **params):
    _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, seg_q_ref=seg_q_ref, seg_k_ref=seg_k_ref,
                   seed_ref=seed_ref, **params)


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


def _run_dkv_pass(q, k, v, do, lse8, delta8, scale, causal, block_q,
                  block_k, seg_q=None, seg_k=None, heads=1, dropout=0.0,
                  seed=None):
    """dkv backward pass: grid parallel over k blocks (contraction over q
    blocks innermost, accumulators in VMEM scratch) with its OWN
    block_q/block_k choice, independent of the dq pass."""
    bh, s_q, d = q.shape
    s_kv = k.shape[1]
    n_q = s_q // block_q
    n_kv = s_kv // block_k
    seg = seg_q is not None
    drop = dropout > 0.0
    dkv_params = dict(scale=scale, causal=causal, block_q=block_q,
                      block_k=block_k, n_q=n_q, offset=s_kv - s_q,
                      dropout=float(dropout))
    dkv_fn = {(False, False): _bwd_dkv_kernel,
              (True, False): _bwd_dkv_kernel_seg,
              (False, True): _bwd_dkv_kernel_drop,
              (True, True): _bwd_dkv_kernel_seg_drop}[(seg, drop)]
    dkv_kernel = functools.partial(dkv_fn, **dkv_params)
    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
        pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
    ]
    dkv_args = [q, k, v, do, lse8, delta8]
    h_ = heads
    if seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b // h_, 0, i)),
            pl.BlockSpec((1, 8, block_k), lambda b, j, i: (b // h_, 0, j)),
        ]
        dkv_args += [seg_q, seg_k]
    if drop:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_args.append(_seed_arg(seed))
    with _x64_off():
        dk, dv = _pc(
        dkv_kernel,
        grid=(bh, n_kv, n_q),
        in_specs=dkv_in_specs,
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
        interpret=_interpret(),
    )(*dkv_args)
    return dk, dv


def _run_dq_pass(q, k, v, do, lse8, delta8, scale, causal, block_q,
                 block_k, seg_q=None, seg_k=None, heads=1, dropout=0.0,
                 seed=None):
    """dq backward pass: grid parallel over q blocks (contraction over k
    blocks innermost) with its OWN block_q/block_k choice."""
    bh, s_q, d = q.shape
    s_kv = k.shape[1]
    n_q = s_q // block_q
    n_kv = s_kv // block_k
    seg = seg_q is not None
    drop = dropout > 0.0
    h_ = heads
    dq_params = dict(scale=scale, causal=causal, block_q=block_q,
                     block_k=block_k, n_kv=n_kv, offset=s_kv - s_q,
                     dropout=float(dropout))
    dq_fn = {(False, False): _bwd_dq_kernel,
             (True, False): _bwd_dq_kernel_seg,
             (False, True): _bwd_dq_kernel_drop,
             (True, True): _bwd_dq_kernel_seg_drop}[(seg, drop)]
    dq_kernel = functools.partial(dq_fn, **dq_params)
    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
    ]
    dq_args = [q, k, v, do, lse8, delta8]
    if seg:
        dq_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b // h_, 0, i)),
            pl.BlockSpec((1, 8, block_k), lambda b, i, j: (b // h_, 0, j)),
        ]
        dq_args += [seg_q, seg_k]
    if drop:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_args.append(_seed_arg(seed))
    with _x64_off():
        dq = _pc(
        dq_kernel,
        grid=(bh, n_q, n_kv),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((bh, s_q, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(*dq_args)
    return dq


def _flash_bwd_split(res, g, scale, causal, dq_blocks=(DEFAULT_BLOCK_Q,
                                                       DEFAULT_BLOCK_K),
                     dkv_blocks=(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K),
                     seg_q=None, seg_k=None, heads=1, d_lse=None,
                     dropout=0.0, seed=None):
    """The backward as two passes, dkv then dq, each with its own
    (block_q, block_k). Dropout regenerates the forward's threefry mask
    from GLOBAL (q, k) coordinates, so the mask is bit-identical
    regardless of either pass's block choice."""
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


def _flash_bwd(res, g, scale, causal, block_q, block_k, **kw):
    """The backward every custom VJP here takes: both passes at the
    forward's blocks."""
    return _flash_bwd_split(res, g, scale, causal,
                            dq_blocks=(block_q, block_k),
                            dkv_blocks=(block_q, block_k), **kw)


# ---------------------------------------------------------------------------
# public entry (custom VJP over [bh, s, d])
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_bhsd_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


# From this sequence length on attention takes the Pallas kernels: the
# backward below (streamed passes against XLA's recompute grad, which
# materializes the O(s^2) scores) and `use_flash` for the forward. Both are
# taken from v5e readings that predate the ledger (the flash forward crossed
# XLA's fused attention near 4,096; the streamed backward was the
# memory-safe choice from there). No cell of the benchmark stands on either
# side: `train-2k` trains at 2,048, under both (ROADMAP S3 decides them).
_PALLAS_BWD_MIN_SEQ = 4096
_PALLAS_FWD_MIN_SEQ = 4096


def use_flash(seq_q, seq_kv, head_dim, training, dropout=0.0):
    """The one choice `scaled_dot_product_attention` asks for an unmasked
    call: the flash kernel where it `supports` the shape at the default
    blocks and the sequence reaches the threshold of its mode; with
    dropout only under FLAGS_flash_dropout_kernel (ROADMAP D2 decides
    the in-kernel dropout path)."""
    from ..framework import config as _config

    min_seq = _PALLAS_BWD_MIN_SEQ if training else _PALLAS_FWD_MIN_SEQ
    return (supports(seq_q, seq_kv, head_dim) and seq_q >= min_seq
            and (dropout == 0.0
                 or bool(_config.get_flag("FLAGS_flash_dropout_kernel",
                                          False))))


def _bwd_use_xla(s_q):
    """XLA recompute grad below the threshold, streamed Pallas kernels from
    it on (tests monkeypatch the constant to force the streamed path at
    small seq)."""
    return s_q < _PALLAS_BWD_MIN_SEQ


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
    """XLA-fused backward via recompute: at short sequence the O(s^2)
    score matrix fits comfortably and XLA's fused softmax-grad beats the
    streamed kernels; the Pallas backward takes over for long sequences
    where s^2 memory is the binding constraint. The ONE reference
    implementation also serves the lse-returning variant (d_lse is the lse
    cotangent, zeros when the caller only differentiates the output)."""
    q, k, v, _, _ = res

    def ref(q_, k_, v_):
        return _xla_ref_fwd(q_, k_, v_, scale, causal, seg_q=seg_q,
                            seg_k=seg_k, heads=heads)

    _, vjp = jax.vjp(ref, q, k, v)
    if d_lse is None:
        d_lse = jnp.zeros(g.shape[:2], jnp.float32)
    return vjp((g, d_lse.astype(jnp.float32)))


def _dispatch_bwd(res, g, scale, causal, block_q, block_k, d_lse=None):
    """Backward of the plain (non-seg, non-dropout) path."""
    if _bwd_use_xla(res[0].shape[1]):
        return _xla_ref_bwd(res, g, scale, causal, d_lse=d_lse)
    return _flash_bwd(res, g, scale, causal, block_q, block_k,
                      d_lse=d_lse)


def _flash_bhsd_bwd(scale, causal, block_q, block_k, res, g):
    return _dispatch_bwd(res, g, scale, causal, block_q, block_k)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


# segmented (varlen) variant: seg_q8/seg_k8 are [bh, 8, s] int32
# sublane-replicated segment ids; cross-segment pairs are masked in all
# four kernels (fwd, dkv, dq, and the short-seq XLA fallback backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bhsd_seg(q, k, v, seg_q8, seg_k8, scale, causal, block_q,
                    block_k, heads):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        seg_q=seg_q8, seg_k=seg_k8, heads=heads)
    return out


def _flash_bhsd_seg_fwd(q, k, v, seg_q8, seg_k8, scale, causal, block_q,
                        block_k, heads):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          seg_q=seg_q8, seg_k=seg_k8, heads=heads)
    return out, (q, k, v, out, lse, seg_q8, seg_k8)


def _flash_bhsd_seg_bwd(scale, causal, block_q, block_k, heads, res, g):
    q, k, v, out, lse, seg_q8, seg_k8 = res
    s_q = q.shape[1]
    if _bwd_use_xla(s_q):
        dq, dk, dv = _xla_ref_bwd((q, k, v, out, lse), g, scale, causal,
                                  seg_q=seg_q8, seg_k=seg_k8, heads=heads)
    else:
        dq, dk, dv = _flash_bwd((q, k, v, out, lse), g, scale, causal,
                                block_q, block_k, seg_q=seg_q8,
                                seg_k=seg_k8, heads=heads)
    return dq, dk, dv, None, None


_flash_bhsd_seg.defvjp(_flash_bhsd_seg_fwd, _flash_bhsd_seg_bwd)


# dropout variants: the backward ALWAYS runs the Pallas kernels — the
# in-kernel threefry mask must be regenerated bit-exactly, which the XLA
# short-seq fallback cannot do.


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_bhsd_drop(q, k, v, seed, scale, causal, block_q, block_k,
                     dropout):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        dropout=dropout, seed=seed)
    return out


def _flash_bhsd_drop_fwd(q, k, v, seed, scale, causal, block_q, block_k,
                         dropout):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          dropout=dropout, seed=seed)
    return out, (q, k, v, out, lse, seed)


def _flash_bhsd_drop_bwd(scale, causal, block_q, block_k, dropout, res, g):
    q, k, v, out, lse, seed = res
    dq, dk, dv = _flash_bwd((q, k, v, out, lse), g, scale, causal, block_q,
                            block_k, dropout=dropout, seed=seed)
    return dq, dk, dv, None


_flash_bhsd_drop.defvjp(_flash_bhsd_drop_fwd, _flash_bhsd_drop_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_bhsd_seg_drop(q, k, v, seg_q8, seg_k8, seed, scale, causal,
                         block_q, block_k, heads, dropout):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        seg_q=seg_q8, seg_k=seg_k8, heads=heads,
                        dropout=dropout, seed=seed)
    return out


def _flash_bhsd_seg_drop_fwd(q, k, v, seg_q8, seg_k8, seed, scale, causal,
                             block_q, block_k, heads, dropout):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          seg_q=seg_q8, seg_k=seg_k8, heads=heads,
                          dropout=dropout, seed=seed)
    return out, (q, k, v, out, lse, seg_q8, seg_k8, seed)


def _flash_bhsd_seg_drop_bwd(scale, causal, block_q, block_k, heads,
                             dropout, res, g):
    q, k, v, out, lse, seg_q8, seg_k8, seed = res
    dq, dk, dv = _flash_bwd((q, k, v, out, lse), g, scale, causal, block_q,
                            block_k, seg_q=seg_q8, seg_k=seg_k8,
                            heads=heads, dropout=dropout, seed=seed)
    return dq, dk, dv, None, None, None


_flash_bhsd_seg_drop.defvjp(_flash_bhsd_seg_drop_fwd,
                            _flash_bhsd_seg_drop_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bhsd_lse(q, k, v, scale, causal, block_q, block_k):
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k)


def _flash_bhsd_lse_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return (out, lse), (q, k, v, out, lse)


def _flash_bhsd_lse_bwd(scale, causal, block_q, block_k, res, g):
    g_out, g_lse = g
    q, k, v, out, lse = res
    return _dispatch_bwd((q, k, v, out, lse), g_out, scale, causal,
                         block_q, block_k, d_lse=g_lse)


_flash_bhsd_lse.defvjp(_flash_bhsd_lse_fwd, _flash_bhsd_lse_bwd)


def flash_attention_with_lse_bshd(q, k, v, causal=False, scale=None,
                                  block_q=DEFAULT_BLOCK_Q,
                                  block_k=DEFAULT_BLOCK_K):
    """Like flash_attention_bshd but also returns the row logsumexp
    ([b, h, s_q], f32) — the merge statistic ring attention accumulates
    across KV blocks. Both outputs are differentiable (the lse cotangent
    folds into the flash backward's delta term)."""
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    if not supports(s_q, s_kv, d, block_q, block_k):
        raise ValueError(
            f"flash_attention: unsupported shape seq_q={s_q} seq_kv={s_kv} "
            f"d={d} (need multiples of {block_q}/{block_k}/128)")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, s_q, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, s_kv, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, s_kv, d)
    out, lse = _flash_bhsd_lse(qt, kt, vt, float(scale), bool(causal),
                               block_q, block_k)
    return (jnp.swapaxes(out.reshape(b, h, s_q, d), 1, 2),
            lse.reshape(b, h, s_q))


def supports(seq_q, seq_kv, head_dim, block_q=DEFAULT_BLOCK_Q,
             block_k=DEFAULT_BLOCK_K):
    return (seq_q % block_q == 0 and seq_kv % block_k == 0
            and head_dim % 128 == 0 and seq_q >= block_q
            and seq_kv >= block_k)


def _seg8(seg, b, s):
    """[b, s] int32 segment ids -> [b, 8, s] sublane-replicated layout
    (per-head replication happens in the BlockSpec index map, not HBM)."""
    seg = jnp.asarray(seg, jnp.int32)
    return jnp.broadcast_to(seg[:, None, :], (b, 8, s))


def flash_attention_bshd(q, k, v, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         segment_ids_q=None, segment_ids_k=None,
                         dropout=0.0, dropout_seed=None):
    """q/k/v: [batch, seq, heads, head_dim] (paddle layout) -> same shape.

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
    if not supports(s_q, s_kv, d, block_q, block_k):
        raise ValueError(
            f"flash_attention: unsupported shape seq_q={s_q} seq_kv={s_kv} "
            f"d={d} (need multiples of {block_q}/{block_k}/128)"
        )
    if dropout and dropout_seed is None:
        raise ValueError("flash_attention: dropout requires dropout_seed")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # bshd -> (b*h, s, d)
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, s_q, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, s_kv, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, s_kv, d)
    if segment_ids_q is not None:
        sq8 = _seg8(segment_ids_q, b, s_q)
        sk8 = _seg8(segment_ids_k, b, s_kv)
        if dropout:
            out = _flash_bhsd_seg_drop(qt, kt, vt, sq8, sk8,
                                       _seed_arg(dropout_seed),
                                       float(scale), bool(causal), block_q,
                                       block_k, h, float(dropout))
        else:
            out = _flash_bhsd_seg(qt, kt, vt, sq8, sk8, float(scale),
                                  bool(causal), block_q, block_k, h)
    elif dropout:
        out = _flash_bhsd_drop(qt, kt, vt, _seed_arg(dropout_seed),
                               float(scale), bool(causal), block_q,
                               block_k, float(dropout))
    else:
        out = _flash_bhsd(qt, kt, vt, float(scale), bool(causal), block_q,
                          block_k)
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
