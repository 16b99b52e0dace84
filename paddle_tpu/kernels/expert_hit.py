"""Pallas kernel for one chip's share of a routed expert layer at decode
sized token counts: the gated-SiLU products of the held experts that some
live token PICKED, and of no other.

`expert_share.share_ffn` (the dense form, and the reference the tests
compare against) takes every held expert's product for every token and
multiplies the hidden activations by a routing weight that is 0 wherever
the token did not pick the expert. With a handful of tokens most held
experts are picked by nobody, and a decode step is bound by reading the
experts' weights: `hit_ffn` walks a static grid of (held slots, blocks of
the expert width) whose weight blocks are indexed through a small table
built in XLA from the routing weights of live tokens alone (`hit_table`:
the hit experts in ascending order, the tail repeating the last) and
prefetched as scalars. A slot beyond the number hit repeats the block index
the last live step left, so the pipeline sees an unchanged block and copies
nothing, and its body is skipped: the kernel streams the weights of the
experts HIT, whatever the chip holds.

Which form an expert layer takes is `use_hit_path`'s choice, from the token
count, the shapes, the dtypes and `_interpret()` alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off

_pc = pl.pallas_call

# The most tokens of a call that takes the hit path. From PR 30's microbench
# on a v5e (PERF.md section 6: the expert FFN alone, 16 held experts of
# 7,680 x 2,048 in bf16, picks drawn by uniform top-8 of 256): at 16 / 32 /
# 64 tokens 6.2 / 10.1 / 14.0 experts are hit and the kernel takes 0.82 /
# 1.35 / 1.86 ms a layer against the dense products' 2.04-2.06; at 128
# tokens 15.7 are hit and it loses by 2 % (2.08 against 2.04). The one cell
# that runs an expert layer stands far on either side: 16 tokens a decode
# step, 256 to 16,384 a prefill.
_HIT_MAX_TOKENS = 64

# VMEM for the three weight blocks of a grid step, double-buffered: at a
# hidden size of 7,680 in bf16 that is blocks of 128 columns, [7680, 128] x
# 3 x 2 = 11.8 MB. Blocks of 256 and 512 columns read 3 % slower in the
# same microbench (a longer first copy that nothing overlaps).
_WEIGHT_VMEM_BYTES = 12 << 20


def use_hit_path(n, d, f, x_dtype, w_dtype):
    """Whether `n` tokens through held experts of [d, f] take `hit_ffn`:
    off interpret mode (the CPU takes the dense reference, the kernel there
    is emulation), at most `_HIT_MAX_TOKENS` tokens, floating operands, and
    widths Mosaic tiles without padding."""
    return (not _interpret() and n <= _HIT_MAX_TOKENS
            and d % 128 == 0 and f % 128 == 0
            and jnp.issubdtype(x_dtype, jnp.floating)
            and jnp.issubdtype(w_dtype, jnp.floating))


def hit_table(dense_w, live=None):
    """(hit_ids [held] int32, n_hit int32) from the routing weights
    `dense_w` [n, held] of the tokens that are `live` ([n] bool; all of
    them where None): the held experts some live token gave a weight above
    0, in ascending order, the tail repeating the last of them (all 0 where
    nobody picked anything), and how many they are."""
    picked = dense_w > 0
    if live is not None:
        picked = picked & jnp.reshape(live, (-1, 1))
    hit = jnp.any(picked, axis=0)
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    slots = jnp.arange(hit.shape[0], dtype=jnp.int32)
    ids = jnp.where(slots < n_hit, order, order[jnp.maximum(n_hit - 1, 0)])
    return ids, n_hit


def _block_width(d, f, itemsize):
    """Columns of the expert width one grid step holds: the largest
    multiple of 128 that divides `f` with the step's three weight blocks,
    double-buffered, inside `_WEIGHT_VMEM_BYTES`; all of `f` where 128
    does not divide it (interpret mode's tiny shapes)."""
    if f % 128:
        return f
    fits = [b for b in range(128, f + 1, 128)
            if f % b == 0 and 6 * d * b * itemsize <= _WEIGHT_VMEM_BYTES]
    return max(fits) if fits else 128


def _hit_kernel(ids_ref, n_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref,
                acc):
    """One (slot, block of the expert width): gate, up, activation, routing
    weight and the down product of that block, the [n, block] hidden never
    leaving VMEM; `acc` [n, d] float32 sums the down products over blocks
    and hit experts. `ids_ref` is read by the index maps alone."""
    s, j = pl.program_id(0), pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(s < n_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[0].astype(x.dtype),
                       preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up * w_ref[s]).astype(x.dtype)
        acc[...] += jnp.dot(hidden, wd_ref[0].astype(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when((s == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def hit_ffn(x, dense_w, w_gate, w_up, w_down, live=None):
    """sum over the HIT experts e of `dense_w[:, e] * E_e(x)`: x [n, d],
    dense_w [n, held] float32, w_gate / w_up [held, d, f], w_down [held, f,
    d], live [n] bool or None. `share_ffn`'s mathematics with the products
    that a weight of 0 would erase left out: per expert `silu(x Wg) * (x
    Wu) * w` in float32, cast to x's type, the down products summed in
    float32 over blocks and experts and cast once. A token that is not live
    adds no expert to the table; its own row of the result holds the sum
    over the experts that live tokens hit."""
    n, d = x.shape
    held, _, f = w_gate.shape
    ids, n_hit = hit_table(dense_w, live)
    # the routing weight of slot s for every token, [held, n, 1]: a column
    # the kernel broadcasts along the block's lanes
    w_slots = dense_w.astype(jnp.float32).T[ids][..., None]
    rows = -n % 16  # whole sublane tiles, whatever the type
    if rows:
        x = jnp.pad(x, ((0, rows), (0, 0)))
        w_slots = jnp.pad(w_slots, ((0, 0), (0, rows), (0, 0)))
    np_ = n + rows
    bf = _block_width(d, f, jnp.dtype(w_gate.dtype).itemsize)
    blocks = f // bf
    # beside the weight blocks: x and the result (two buffers each), the
    # accumulator, the routing columns padded to a lane tile, the block's
    # float32 gate, up and hidden
    vmem = _WEIGHT_VMEM_BYTES + np_ * d * (4 * x.dtype.itemsize + 4) \
        + 2 * held * np_ * 512 + 4 * np_ * bf * 4 + (2 << 20)

    def col_map(s, j, ids, n_hit):   # w_gate, w_up: [held, d, f]
        return (ids[s], 0, jnp.where(s < n_hit[0], j, blocks - 1))

    def row_map(s, j, ids, n_hit):   # w_down: [held, f, d]
        return (ids[s], jnp.where(s < n_hit[0], j, blocks - 1), 0)

    whole2 = lambda s, j, ids, n_hit: (0, 0)        # noqa: E731
    whole3 = lambda s, j, ids, n_hit: (0, 0, 0)     # noqa: E731
    with _x64_off():
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, blocks),
            in_specs=[pl.BlockSpec((np_, d), whole2),
                      pl.BlockSpec((held, np_, 1), whole3),
                      pl.BlockSpec((1, d, bf), col_map),
                      pl.BlockSpec((1, d, bf), col_map),
                      pl.BlockSpec((1, bf, d), row_map)],
            out_specs=pl.BlockSpec((np_, d), whole2),
            scratch_shapes=[pltpu.VMEM((np_, d), jnp.float32)],
        )
        out = _pc(
            _hit_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((np_, d), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=vmem),
            interpret=_interpret(),
        )(ids, n_hit.reshape(1), x, w_slots, w_gate, w_up, w_down)
    return out[:n]
