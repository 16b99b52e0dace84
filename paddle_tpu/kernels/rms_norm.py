"""Pallas fused RMSNorm (reference: phi fusion rms_norm kernel — SURVEY.md
§2.1). Forward+backward fused over row blocks; f32 statistics regardless of
input dtype (matches the reference kernel's accumulate-in-f32)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off

# pallas_call runs under x64-off so index maps / constants stay 32-bit
# (the package enables jax x64 globally for paddle int64 semantics)
_pc = pl.pallas_call

BLOCK_ROWS = 256

# Rows per block shrink with the width and the itemsize so that one input
# block is at most 1 MiB: the kernels' working set (double-buffered x / g /
# dx blocks plus f32 temporaries) then stays inside the 16 MiB of VMEM
# Mosaic scopes to a kernel on v5e. Found by ahead-of-time compiles for
# v5e, forward and backward, bf16 and f32, 2048 to 8192 columns, 2048 to
# 131072 rows: every shape at the bound compiles, and shapes at twice the
# bound are refused from 8192 rows up ("Scoped allocation ... exceeded
# scoped vmem limit"). tests/test_kernels_compile_tpu.py keeps it so.
_MAX_BLOCK_BYTES = 1 << 20


def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + jnp.float32(eps))
    o_ref[:] = (x * rstd * w_ref[0].astype(jnp.float32)).astype(o_ref.dtype)
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref, dw_acc, *,
                n_rows_blocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    wg = g * w
    # dx = rstd * (wg - xhat * mean(wg * xhat))
    mean_term = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (wg - xhat * mean_term)).astype(dx_ref.dtype)
    dw_acc[:] += jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(i == n_rows_blocks - 1)
    def _():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm_2d(x, w, eps):
    out, _ = _fwd(x, w, eps)
    return out


def _block(rows, cols, itemsize):
    """Rows per grid step of both passes, from width and dtype."""
    block = BLOCK_ROWS
    while block * cols * itemsize > _MAX_BLOCK_BYTES:
        block //= 2
    return min(block, rows)


def _fwd(x, w, eps):
    rows, cols = x.shape
    block = _block(rows, cols, x.dtype.itemsize)
    kernel = functools.partial(_fwd_kernel, eps=eps)
    with _x64_off():
        out, rstd = _pc(
        kernel,
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, cols), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, w.reshape(1, -1))
    return out, rstd


def _rms_fwd(x, w, eps):
    out, rstd = _fwd(x, w, eps)
    return out, (x, w, rstd)


def _rms_bwd(eps, res, g):
    x, w, rstd = res
    rows, cols = x.shape
    block = _block(rows, cols, x.dtype.itemsize)
    n_blocks = rows // block
    kernel = functools.partial(_bwd_kernel, n_rows_blocks=n_blocks)
    with _x64_off():
        dx, dw = _pc(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, cols), lambda i: (0, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, cols), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x.dtype),
            jax.ShapeDtypeStruct((1, cols), w.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, cols), jnp.float32)],
        interpret=_interpret(),
    )(x, w.reshape(1, -1), rstd, g)
    return dx, dw[0]


rms_norm_2d.defvjp(_rms_fwd, _rms_bwd)


def supports(rows, cols, itemsize=4):
    """Can the kernel run [rows, cols] of `itemsize`-byte elements? The one
    gate: beyond it the callers take the XLA expression, inside it a Mosaic
    refusal is an error."""
    if rows <= 0:
        return False
    block = _block(rows, cols, itemsize)
    return (rows % block == 0 and rows >= block and cols % 128 == 0
            and cols <= 8192
            and block * cols * itemsize <= _MAX_BLOCK_BYTES)


def rms_norm(x, weight, eps=1e-6):
    """x: [..., hidden]; weight: [hidden]."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = rms_norm_2d(x2, weight, float(eps))
    return out.reshape(shape)
