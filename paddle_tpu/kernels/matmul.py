"""Pallas blocked matmul for the MLP/linear family.

The roofline report (stepledger waterfall) puts the MLP as the largest
compute bucket of the train step, yet until ISSUE 12 only attention,
rms_norm and the quantized linears had measured dispatch — the dense
`nn.functional.linear` always took XLA's default lowering. This kernel
gives the autotuner (kernels/autotune.py, op `matmul`) a block-grid
family to race against XLA with the same never-slower-than-XLA
tie-break and persistent winner cache as flash/paged/rms_norm: a
classic (m, n, k)-tiled MXU matmul with an f32 VMEM accumulator,
k-innermost grid so each (m, n) output tile accumulates across k blocks
without leaving VMEM (same structure as quant_matmul minus the dequant).

Differentiable in BOTH operands (custom_vjp with the XLA transposed
matmuls as backward — MLP weights train, unlike the quantized storage),
so the train path can adopt a fused winner without losing grads.
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

# (block_n, block_k) sweep for the autotuner — same grid family as the
# other kernels; block_m is derived from the token count (below)
BLOCK_GRID_N = (128, 256, 512)
BLOCK_GRID_K = (128, 256, 512)

# m (token) blocking: small batches run as ONE padded block (decode /
# small-batch training); larger ones tile at _BLOCK_M
_M_ALIGN = 8
_SINGLE_M_MAX = 512
_BLOCK_M = 256


def matmul_xla(x, w):
    """The XLA reference lowering (also the autotune baseline)."""
    return jnp.matmul(x, w)


def _block_m(m):
    """The m tile for a given token count: one padded block when small,
    _BLOCK_M tiles (m padded up to a multiple) otherwise."""
    mp = -(-m // _M_ALIGN) * _M_ALIGN
    if mp <= _SINGLE_M_MAX:
        return mp
    return _BLOCK_M


def supports(m, k, n, block_n=128, block_k=128):
    """Can the Pallas kernel run this shape at these blocks? The caller
    falls back to the XLA lowering otherwise."""
    if m <= 0 or k <= 0 or n <= 0:
        return False
    if k % block_k or n % block_n:
        return False
    return n % 128 == 0 and block_k >= 128


def _mm_kernel(x_ref, w_ref, o_ref, acc, *, n_k_blocks):
    """One (m-block, n-block, k-block) grid step: fold the tile's partial
    product into the f32 accumulator; write back on the last k block."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == n_k_blocks - 1)
    def _():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def matmul_fused(x, w, block_n=256, block_k=256):
    """Blocked Pallas matmul: x [m, k] @ w [k, n] -> [m, n] in x.dtype.

    Differentiable in both operands (custom_vjp): the backward runs the
    XLA transposed matmuls (dx = g @ w.T, dw = x.T @ g) — pallas_call
    has no jvp rule on this jax, and the backward shapes (k or m in the
    contraction) rarely match the forward's winning blocks anyway."""
    return _fused_vjp(x, w, block_n, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_vjp(x, w, block_n, block_k):
    return _fused_call(x, w, block_n, block_k)


def _fused_fwd(x, w, block_n, block_k):
    return _fused_call(x, w, block_n, block_k), (x, w)


def _fused_bwd(block_n, block_k, res, g):
    x, w = res
    dx = jnp.matmul(g, w.T).astype(x.dtype)
    dw = jnp.matmul(x.T, g).astype(w.dtype)
    return dx, dw


_fused_vjp.defvjp(_fused_fwd, _fused_bwd)


def _fused_call(x, w, block_n=256, block_k=256):
    m, k = x.shape
    kw, n = w.shape
    if kw != k:
        raise ValueError(f"weight rows {kw} != k ({k})")
    if not supports(m, k, n, block_n, block_k):
        raise ValueError(
            f"unsupported matmul shape m={m} k={k} n={n} "
            f"bn={block_n} bk={block_k}")
    bm = _block_m(m)
    mp = -(-m // bm) * bm
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x

    n_k_blocks = k // block_k
    kernel = functools.partial(_mm_kernel, n_k_blocks=n_k_blocks)
    with _x64_off():
        out = _pc(
            kernel,
            grid=(mp // bm, n // block_n, n_k_blocks),
            in_specs=[
                pl.BlockSpec((bm, block_k), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, block_n),
                                   lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
            scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
            interpret=_interpret(),
        )(xp, w)
    return out[:m]
