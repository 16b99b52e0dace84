"""Pallas fused dequant-matmul for weight-only quantized linears.

Reference parity: the CUTLASS mixed-dtype GEMMs behind
`paddle.nn.quant.weight_only_linear` (SURVEY.md §2.1) — on GPU the
dequantization happens inside the GEMM mainloop so the weight's HBM
traffic stays int8/int4. The TPU build's original lowering
(`nn/quant/_dequant_jnp`) dequantizes in the traced graph and relies on
XLA fusing the convert into the operand load; in practice the serving
decode profile (SERVING_QUANT_*.json) shows the bf16 weight still being
materialized — int4 bought only 357→426 tok/s because dequant ran
outside the kernel.

This kernel closes that gap: int8 (or nibble-packed int4) weight tiles
and their group scales stream HBM→VMEM, dequantize in registers, and
feed the MXU — the bf16 weight never exists in HBM. Layouts match
`nn/quant.weight_quantize` exactly (int4 packs two rows per byte along
the in dim, low nibble = even row; scales are [n] per-channel or
[groups, n] for group_size 64/128), and `tests/test_quantization.py`'s
int4 round-trip golden is the reference the kernel is checked against.

Dispatch: `quant_matmul_dispatch` is the ONE entry the quantized linears
call: the XLA dequant expression, or with FLAGS_quant_matmul=fused the
kernel at the largest blocks the shape admits, where it admits any.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off

_pc = pl.pallas_call

# the (block_n, block_k) family `_default_blocks` picks from; block_k
# additionally has to divide the scale group
BLOCK_GRID_N = (128, 256, 512)
BLOCK_GRID_K = (128, 256, 512)

# the m (token) dimension of decode is tiny (batch 8..64, or batch*window
# under speculative verify) — one m block, padded to the f32 sublane tile
_M_ALIGN = 8
_MAX_M = 1024


# ---------------------------------------------------------------------------
# XLA dequant reference (the default lowering)
# ---------------------------------------------------------------------------


def unpack_int4(qw):
    """[k//2, n] nibble-packed int8 -> [k, n] int8 in [-7, 7].

    Inverse of nn/quant.weight_quantize's int4 packing (low nibble =
    even row; int8 right shifts are arithmetic, so the high nibble
    sign-extends directly and the low one via the <<4 then >>4 trick).
    """
    lo = jnp.right_shift(jnp.left_shift(qw, 4), 4)
    hi = jnp.right_shift(qw, 4)
    k2, n = qw.shape
    return jnp.stack([lo, hi], axis=1).reshape(k2 * 2, n)


def dequantize(qw, scales, weight_dtype="int8", out_dtype=jnp.float32):
    """Materialized dequant (reference semantics of
    nn/quant.weight_dequantize, minus the Tensor wrapping — kernels must
    not import nn). scales: [n] or [groups, n]."""
    q = unpack_int4(qw) if weight_dtype == "int4" else qw
    k, n = q.shape
    s = scales if scales.ndim == 2 else scales[None, :]
    groups = s.shape[0]
    w = q.reshape(groups, k // groups, n).astype(out_dtype) \
        * s[:, None, :].astype(out_dtype)
    return w.reshape(k, n)


def quant_matmul_xla(x, qw, scales, weight_dtype="int8"):
    """y = x @ dequant(qw) — the traced-dequant lowering the fused
    kernel is benchmarked and numerically checked against."""
    w = dequantize(qw, scales, weight_dtype, x.dtype)
    return jnp.matmul(x, w)


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------


def _qmm_kernel(x_ref, qw_ref, s_ref, o_ref, acc, *, weight_dtype,
                rows_per_group, n_k_blocks):
    """One (n-block, k-block) grid step: dequantize the weight tile in
    VMEM and fold its partial product into the f32 accumulator.

    qw_ref: [bk, bn] int8 (int4: [bk//2, bn] packed). s_ref: the k-block's
    scale rows [bk // rows_per_group... ] shaped [g_rows, bn] — each scale
    row covers `rows_per_group` weight rows (the whole block for
    per-channel scales).
    """
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    qint = qw_ref[:].astype(jnp.int32)
    if weight_dtype == "int4":
        # nibble unpack in i32 (arithmetic shifts sign-extend); the
        # interleave mirrors the pack layout: byte row r holds logical
        # rows 2r (low) and 2r+1 (high)
        lo = jnp.right_shift(jnp.left_shift(qint, 28), 28)
        hi = jnp.right_shift(jnp.left_shift(qint, 24), 28)
        k2, bn = qint.shape
        qint = jnp.stack([lo, hi], axis=1).reshape(k2 * 2, bn)
    wf = qint.astype(jnp.float32)
    s = s_ref[:].astype(jnp.float32)  # [g_rows, bn]
    g_rows = s.shape[0]
    bk, bn = wf.shape
    # expand each scale row over its group's weight rows; for per-channel
    # scales g_rows == 1 and this is a plain broadcast
    w = (wf.reshape(g_rows, rows_per_group, bn) * s[:, None, :]) \
        .reshape(bk, bn)
    acc[:] += jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == n_k_blocks - 1)
    def _():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def supports(m, k, n, weight_dtype="int8", group_size=-1,
             block_n=128, block_k=128):
    """Can the fused kernel run this shape at these blocks? The caller
    falls back to the XLA dequant expression otherwise."""
    if m <= 0 or m > _MAX_M:
        return False
    if k % block_k or n % block_n:
        return False
    if group_size not in (-1, 64, 128):
        return False
    if group_size != -1:
        if block_k % group_size:
            return False  # a k block must cover whole scale groups
        # the k block's scale rows form a [block_k // group_size, block_n]
        # tile: Mosaic takes 8-row tiles, or the whole scale array
        if (block_k // group_size) % 8 and block_k != k:
            return False
    if weight_dtype == "int4":
        # packed rows: block_k//2 int8 rows must hit the (32, 128) tile
        if block_k % 64:
            return False
    elif weight_dtype != "int8":
        return False
    return n % 128 == 0 and block_k >= 128


def quant_matmul_fused(x, qw, scales, weight_dtype="int8",
                       group_size=-1, block_n=256, block_k=256):
    """Fused dequant-matmul: x [m, k] float; qw int8 [k, n] (int4:
    [k//2, n] packed); scales [n] or [groups, n] f32. Returns [m, n] in
    x.dtype. The bf16/f32 weight is never materialized outside VMEM.

    Differentiable in x (custom_vjp): the backward is the XLA
    dequant-then-transposed-matmul — eager layers record a vjp through
    quantized linears (QAT-style grads w.r.t. activations), and
    pallas_call has no jvp rule on this jax. The quantized storage
    itself is non-trainable (zero cotangents)."""
    return _fused_vjp(x, qw, scales, weight_dtype, group_size, block_n,
                      block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_vjp(x, qw, scales, weight_dtype, group_size, block_n,
               block_k):
    return _fused_call(x, qw, scales, weight_dtype, group_size, block_n,
                       block_k)


def _fused_fwd(x, qw, scales, weight_dtype, group_size, block_n,
               block_k):
    out = _fused_call(x, qw, scales, weight_dtype, group_size, block_n,
                      block_k)
    return out, (qw, scales)


def _fused_bwd(weight_dtype, group_size, block_n, block_k, res, g):
    import numpy as np

    qw, scales = res
    w = dequantize(qw, scales, weight_dtype, g.dtype)
    dx = jnp.matmul(g, w.T)
    # int8 storage cotangent is float0 (non-trainable buffer), the f32
    # scales get symbolic zeros
    dqw = np.zeros(qw.shape, dtype=jax.dtypes.float0)
    return dx, dqw, jnp.zeros_like(scales)


_fused_vjp.defvjp(_fused_fwd, _fused_bwd)


def _fused_call(x, qw, scales, weight_dtype="int8",
                group_size=-1, block_n=256, block_k=256):
    m, k = x.shape
    n = qw.shape[1]
    if weight_dtype == "int4":
        if qw.shape[0] * 2 != k:
            raise ValueError(
                f"packed int4 weight rows {qw.shape[0]} != k/2 ({k}//2)")
    elif qw.shape[0] != k:
        raise ValueError(f"weight rows {qw.shape[0]} != k ({k})")
    if not supports(m, k, n, weight_dtype, group_size, block_n, block_k):
        raise ValueError(
            f"unsupported quant_matmul shape m={m} k={k} n={n} "
            f"wd={weight_dtype} gs={group_size} bn={block_n} bk={block_k}")
    s2 = scales if scales.ndim == 2 else scales[None, :]
    groups = s2.shape[0]
    rows_per_group = k // groups          # == group_size, or k when -1
    g_rows = max(block_k // rows_per_group, 1)
    rows_per_group = min(rows_per_group, block_k)

    mp = -(-m // _M_ALIGN) * _M_ALIGN
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x

    n_k_blocks = k // block_k
    kernel = functools.partial(
        _qmm_kernel, weight_dtype=weight_dtype,
        rows_per_group=rows_per_group, n_k_blocks=n_k_blocks)
    qrows = block_k // 2 if weight_dtype == "int4" else block_k
    if groups > 1:
        scale_spec = pl.BlockSpec((g_rows, block_n),
                                  lambda j, kk: (kk, j))
    else:  # per-channel: ONE scale row shared by every k block
        scale_spec = pl.BlockSpec((1, block_n), lambda j, kk: (0, j))
    with _x64_off():
        out = _pc(
            kernel,
            grid=(n // block_n, n_k_blocks),
            in_specs=[
                pl.BlockSpec((mp, block_k), lambda j, kk: (0, kk)),
                pl.BlockSpec((qrows, block_n), lambda j, kk: (kk, j)),
                scale_spec,
            ],
            out_specs=pl.BlockSpec((mp, block_n), lambda j, kk: (0, j)),
            out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
            scratch_shapes=[pltpu.VMEM((mp, block_n), jnp.float32)],
            interpret=_interpret(),
        )(xp, qw, s2)
    return out[:m]


# ---------------------------------------------------------------------------
# dispatch (the one entry the quantized linears call)
# ---------------------------------------------------------------------------


def fused_requested() -> bool:
    from ..framework import config as _config

    return str(_config.get_flag("FLAGS_quant_matmul",
                                "xla")).lower() == "fused"


def quant_matmul_dispatch(x, qw, scales, weight_dtype="int8",
                          group_size=-1):
    """y = x @ dequant(qw), x: [..., k] float: the fused kernel (largest
    blocks the shape admits) if FLAGS_quant_matmul says 'fused' and the
    shape is supported, else the XLA dequant expression."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    n = qw.shape[1]
    if fused_requested():
        bn, bk = _default_blocks(k, n, weight_dtype, group_size)
        if bn is not None and supports(m, k, n, weight_dtype, group_size,
                                       bn, bk):
            out = quant_matmul_fused(x2, qw, scales, weight_dtype,
                                     group_size, bn, bk)
            return out.reshape(lead + (n,))
    return quant_matmul_xla(x2, qw, scales,
                            weight_dtype).reshape(lead + (n,))


def _default_blocks(k, n, weight_dtype, group_size):
    """Largest grid blocks the shape admits."""
    for bk in sorted(BLOCK_GRID_K, reverse=True):
        for bn in sorted(BLOCK_GRID_N, reverse=True):
            if supports(1, k, n, weight_dtype, group_size, bn, bk):
                return bn, bk
    return None, None
