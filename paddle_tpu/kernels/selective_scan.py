"""Selective scan: the recurrence of a Mamba-1 mixer (Jamba's state-space
layers), over a prompt at a time.

For each row and channel e of `d_inner`, a state h [d_state] carried from
token to token:

    h_t = exp(dt_t,e A_e) * h_t-1 + (dt_t,e u_t,e) B_t
    y_t,e = h_t . C_t + D_e u_t,e

with dt, u [seq, d_inner], B, C [seq, d_state], A [d_state, d_inner], D
[d_inner] and h_-1 the state the row starts from. A row's `lens` says how
many of its tokens are real: a token at or past it does not move the state
(the padding of a bucketed prefill), and the state handed back is the one
at the row's last real token.

One Pallas TPU kernel (interpret mode on the CPU) serves every prefill.
The state stays in VMEM as [d_state, block] (16 sublanes by the block's
lanes, `scan_block(d_inner)`) while the tokens stream through in chunks of
`SCAN_CHUNK`: the grid is (row, d_inner block, chunk), parallel over the
first two and sequential over the chunks. A chunk wholly past its row's
length is neither fetched (its index repeats the row's last live chunk, so
the pipeline copies nothing) nor stepped, and its y is written as zeros. B
and C come in transposed, [d_state, seq], so that a token's column is a
lane of the chunk.

A decode step's one-token update is left to XLA's fusion (the mixer's own
`forward_paged`). Forward only: nothing here has a backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off

F32 = jnp.float32

# the most lanes of d_inner one grid step holds: its state is [d_state,
# block]. One layer's scan at d_inner 5,120 and d_state 16 on a v5e (PERF.md
# section 6), blocks of 256 / 512 / 1,024 lanes: 8.91 / 7.57 / 7.11 ms at
# 16,384 tokens, 2.51 / 2.21 / 2.07 at 4,096
SCAN_BLOCK_E = 1024
# tokens one grid step streams, a lane tile of B and C (256: no faster)
SCAN_CHUNK = 128
# tokens of one iteration of the kernel's inner loop: a sublane tile of a
# 16-bit input, stepped one at a time with static indices
GROUP = 16
LANES = 128

# the `jax.named_scope` round the kernel, inside a mixer's own scope: a
# device trace books it as `attn/ssm/scan`
SCOPE = "scan"


def scan_supports(d_inner, d_state):
    """d_inner in whole lane tiles, d_state in whole sublane tiles."""
    return d_inner % LANES == 0 and d_state % 8 == 0


def scan_block(d_inner):
    """The widest block of whole lane tiles, at most `SCAN_BLOCK_E`, that
    divides d_inner."""
    return max(b for b in range(LANES, SCAN_BLOCK_E + 1, LANES)
               if d_inner % b == 0)


def _pad_seq(x, to):
    pad = to - x.shape[1]
    return x if not pad else jnp.pad(
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------


def _scan_kernel(lens_ref, u_ref, dt_ref, bt_ref, ct_ref, a_ref, d_ref,
                 h0_ref, y_ref, h_out_ref, h_scr, *, chunk):
    """One (row, d_inner block, chunk). The state lives in `h_scr` across a
    row's chunks; inside a chunk it is carried through `GROUP`-token
    iterations, each token's B and C columns picked out of the chunk's
    [d_state, chunk] by a lane mask."""
    row, step = pl.program_id(0), pl.program_id(2)
    length = lens_ref[row]
    start = step * chunk

    @pl.when(step == 0)
    def _():
        h_scr[...] = h0_ref[0]

    @pl.when(start < length)
    def _():
        a = a_ref[...]
        dskip = d_ref[...]
        bt, ct = bt_ref[0], ct_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, a.shape[1]), 0)

        def group(g, h):
            at = pl.multiple_of(g * GROUP, GROUP)
            us = u_ref[0, pl.ds(at, GROUP), :].astype(F32)
            dts = dt_ref[0, pl.ds(at, GROUP), :]
            ys = jnp.zeros((GROUP, a.shape[1]), F32)
            for j in range(GROUP):
                t = at + j
                u_t = us[j:j + 1]
                dt_t = jnp.where(start + t < length, dts[j:j + 1], 0.0)
                b_t = jnp.sum(jnp.where(lane == t, bt, 0.0), axis=1,
                              keepdims=True)
                c_t = jnp.sum(jnp.where(lane == t, ct, 0.0), axis=1,
                              keepdims=True)
                h = jnp.exp(dt_t * a) * h + (dt_t * u_t) * b_t
                y_t = jnp.sum(h * c_t, axis=0, keepdims=True) + dskip * u_t
                ys = jnp.where(rows == j, y_t, ys)
            y_ref[0, pl.ds(at, GROUP), :] = ys.astype(y_ref.dtype)
            return h

        h_scr[...] = jax.lax.fori_loop(0, chunk // GROUP, group, h_scr[...])

    @pl.when(start >= length)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        h_out_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret"))
def _kernel_call(lens, u, dt, bt, ct, a, d, h0, *, chunk, block, interpret):
    """One trace and one Mosaic lowering for each shape a program meets,
    however many layers call it."""
    rows, seq, e = u.shape
    n_state = a.shape[0]

    def last(r, lens):   # the row's last chunk with a real token in it
        return jnp.maximum(lens[r] - 1, 0) // chunk

    seq_map = lambda r, i, s, lens: (  # noqa: E731
        r, jnp.minimum(s, last(r, lens)), i)
    col_map = lambda r, i, s, lens: (  # noqa: E731
        r, 0, jnp.minimum(s, last(r, lens)))
    state_map = lambda r, i, s, lens: (r, 0, i)  # noqa: E731
    lane_map = lambda r, i, s, lens: (0, i)      # noqa: E731
    # double-buffered u, dt, y, B and C blocks, the state's in and out
    # blocks, its scratch, the A block
    vmem = 2 * chunk * block * (2 * u.dtype.itemsize + 4) \
        + 4 * n_state * chunk * 4 + 6 * n_state * block * 4 + (4 << 20)
    with _x64_off():
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, e // block, seq // chunk),
            in_specs=[pl.BlockSpec((1, chunk, block), seq_map),
                      pl.BlockSpec((1, chunk, block), seq_map),
                      pl.BlockSpec((1, n_state, chunk), col_map),
                      pl.BlockSpec((1, n_state, chunk), col_map),
                      pl.BlockSpec((n_state, block), lane_map),
                      pl.BlockSpec((1, block), lane_map),
                      pl.BlockSpec((1, n_state, block), state_map)],
            out_specs=[pl.BlockSpec((1, chunk, block),
                                    lambda r, i, s, lens: (r, s, i)),
                       pl.BlockSpec((1, n_state, block), state_map)],
            scratch_shapes=[pltpu.VMEM((n_state, block), F32)])
        return pl.pallas_call(
            functools.partial(_scan_kernel, chunk=chunk),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((rows, seq, e), u.dtype),
                       jax.ShapeDtypeStruct((rows, n_state, e), F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=int(vmem)),
            interpret=interpret,
            name="selective_scan",
        )(lens, u, dt, bt, ct, a, d, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _forward_only(chunk, block, interpret, *args):
    """`_kernel_call`, which a model's forward may meet under a tape: its
    backward raises if it is ever asked for."""
    return _kernel_call(*args, chunk=chunk, block=block, interpret=interpret)


def _forward_only_fwd(chunk, block, interpret, *args):
    return _forward_only(chunk, block, interpret, *args), None


def _forward_only_bwd(chunk, block, interpret, _, __):
    raise NotImplementedError(
        "the selective scan has no backward: a Mamba layer serves and does "
        "not train (ROADMAP R11)")


_forward_only.defvjp(_forward_only_fwd, _forward_only_bwd)


def selective_scan(u, dt, a, b, c, d, h0, lens):
    """u [rows, seq, d_inner] (any float type), dt [rows, seq, d_inner]
    float32 (after its softplus), a [d_state, d_inner] (A, negative), b, c
    [rows, seq, d_state], d [d_inner], h0 [rows, d_state, d_inner] float32,
    lens [rows] -> (y [rows, seq, d_inner] in u's type, the state at each
    row's last real token [rows, d_state, d_inner] float32). The sequence
    is padded to whole chunks and B and C laid out [rows, d_state, seq]."""
    rows, seq, e = u.shape
    n_state = a.shape[0]
    if not scan_supports(e, n_state):
        raise ValueError(
            f"the selective scan takes d_inner in whole tiles of {LANES} "
            f"lanes and d_state in whole tiles of 8: got {e} and {n_state}")
    padded = -(-seq // SCAN_CHUNK) * SCAN_CHUNK

    def cols(x):
        return jnp.swapaxes(_pad_seq(x.astype(F32), padded), 1, 2)

    with jax.named_scope(SCOPE):
        y, h = _forward_only(
            SCAN_CHUNK, scan_block(e), _interpret(),
            jnp.minimum(lens, seq).astype(jnp.int32), _pad_seq(u, padded),
            _pad_seq(dt.astype(F32), padded), cols(b), cols(c),
            a.astype(F32), d.astype(F32).reshape(1, e), h0.astype(F32))
    return y[:, :seq], h
