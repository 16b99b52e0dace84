"""Pallas kernel for one chip's share of a routed expert layer at prefill
sized token counts: the gated-SiLU products of the (live token, held expert
it picked) PAIRS, grouped by expert, and of nothing else.

`expert_share.share_ffn` (the dense form, and the reference the tests
compare against) takes every held expert's product for every token and
multiplies the hidden activations by a routing weight that is 0 wherever
the token did not pick the expert: with 8 picks of 256 experts and 16 held,
31 of every 32 products. `grouped_ffn` gives every pair that carries a
weight above 0 a row: its place among its expert's pairs (a cumulative sum
over the tokens, no sort), each expert's group padded to whole tiles of
`ROW_TILE` rows. It walks the tiles in chunks of `_CHUNK_TILES`: a chunk
gathers its rows of `x`, a Mosaic kernel takes gate, up, activation,
routing weight and down product of each tile against ITS expert's weights
(indexed through a prefetched table, the stacks as they lie, the hidden
activations never leaving VMEM), and each row's down product is added into
its token in float32. The loop runs as many chunks as the pairs MADE fill
and the kernel's grid as many tiles: no capacity, so no pair is dropped at
any skew, and the temporaries are a chunk's, whatever the token count.

Which form an expert layer takes is `use_grouped_path`'s choice (beside
`expert_hit.use_hit_path`), from the token count, the shapes, the dtypes
and `_interpret()` alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import x64_off as _x64_off
from .expert_hit import _block_width

_pc = pl.pallas_call

# The fewest tokens of a call that takes the grouped form. From PR 32's
# microbench on a v5e (PERF.md section 6: the expert FFN alone, 16 held
# experts in bf16, uniform top-8 picks): every held expert is hit from some
# 64 tokens on, so the form's floor is one read of all their weights, which
# is all the dense products cost up to 256 tokens. At 7,680 x 2,048 with
# half a pair a token it takes 2.52 / 2.56 / 2.64 / 3.02 / 3.89 ms a layer
# at 256 / 384 / 512 / 1,024 / 2,048 tokens against the dense 2.28 / 3.17 /
# 4.12 / 8.22 / 16.23; at 2,048 x 1,024 with one pair a token 0.47 / 0.54 /
# 1.20 at 256 / 512 / 2,048 against 0.43 / 0.62 / 2.27. The cells' prefills
# are page multiples of 256 tokens.
_GROUPED_MIN_TOKENS = 512

# rows of a tile: a tile reads its expert's weights once (256 rows were
# 25-34 % slower at 1,024 and 2,048 tokens: twice the padding to gather,
# multiply and add)
ROW_TILE = 128

# tiles a chunk of the loop holds: its gathered rows and float32 products
# are the form's temporaries ([8 x 128, 7680]: 16 MB and 31 MB)
_CHUNK_TILES = 8

# tokens of one pass: the pairs' products are added into their tokens by a
# product with the rows' one-hot places, whose cost grows with the tokens
# of a pass times its rows; and a pass reads every hit expert's weights
TOKEN_BLOCK = 2048

# the one-hot product that adds the rows into their tokens multiplies
# float32 products by exact ones and zeros: nothing below float32
_SUM_PRECISION = jax.lax.Precision.HIGHEST

# VMEM an expert's three matrices may take WHOLE, double-buffered (2,048 x
# 1,024 in bf16: 25.2 MB): consecutive tiles of one expert then see an
# unchanged block and copy nothing. A larger expert goes in `hit_ffn`'s
# blocks of its width (128 columns at a hidden size of 7,680; 256 read 4 %
# slower).
_WHOLE_EXPERT_VMEM_BYTES = 26 << 20


def use_grouped_path(n, d, f, x_dtype, w_dtype):
    """Whether `n` tokens through held experts of [d, f] take
    `grouped_ffn`: off interpret mode (the CPU takes the dense reference),
    at least `_GROUPED_MIN_TOKENS` tokens, floating operands, and widths
    Mosaic tiles without padding."""
    return (not _interpret() and n >= _GROUPED_MIN_TOKENS
            and d % 128 == 0 and f % 128 == 0
            and jnp.issubdtype(x_dtype, jnp.floating)
            and jnp.issubdtype(w_dtype, jnp.floating))


def _token_blocks(n, *arrays):
    """`arrays` (leading axis `n` tokens) as `[blocks, block, ...]`, zero
    padded: one block of `n` up to `TOKEN_BLOCK` tokens, blocks of
    `TOKEN_BLOCK` beyond."""
    block = min(n, TOKEN_BLOCK)
    pad = -n % block
    return tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, block) + a.shape[1:]) for a in arrays)


def _picked(dense_w, live):
    """[n, held] bool: the pairs, a weight above 0 of a live token."""
    picked = dense_w > 0
    return picked if live is None \
        else picked & jnp.reshape(live, (-1, 1))


def grouped_rows(dense_w, live=None):
    """Rows `grouped_ffn` takes expert products for: every (block of
    tokens, held expert)'s pairs, rounded up to whole tiles."""
    n = dense_w.shape[0]
    blocks, = _token_blocks(n, _picked(dense_w, live))
    sizes = jnp.sum(blocks, axis=1, dtype=jnp.int32)
    return jnp.sum((sizes + ROW_TILE - 1) // ROW_TILE,
                   dtype=jnp.int32) * ROW_TILE


def _grouped_kernel(eid_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref):
    """One (tile of rows, block of the expert width): gate, up, activation,
    routing weight and the down product of that block against the tile's
    expert, summed over blocks in the float32 result. `eid_ref` is read by
    the index maps alone."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[0].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[0].astype(x.dtype),
                 preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up * w_ref[...]).astype(x.dtype)
    o_ref[...] += jnp.dot(hidden, wd_ref[0].astype(x.dtype),
                          preferred_element_type=jnp.float32)


def _tile_products(xs, ws, eids, n_tiles, w_gate, w_up, w_down):
    """[rows, d] float32: row r's `ws[r] * E_e(xs[r])` for the expert
    `eids[r // ROW_TILE]`, of the first `n_tiles` tiles (traced; the rest
    of the result is not written)."""
    rows, d = xs.shape
    tm = ROW_TILE
    f = w_gate.shape[2]
    itemsize = jnp.dtype(w_gate.dtype).itemsize
    bf = f if 6 * d * f * itemsize <= _WHOLE_EXPERT_VMEM_BYTES \
        else _block_width(d, f, itemsize)
    # the weight blocks; the tile of x and the float32 result (two buffers
    # each); the routing column padded to a lane tile; the block's float32
    # gate, up and hidden
    vmem = 6 * d * bf * itemsize + 2 * tm * d * (xs.dtype.itemsize + 4) \
        + 2 * tm * 512 + 4 * tm * bf * 4 + (2 << 20)
    with _x64_off():
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles, f // bf),
            in_specs=[
                pl.BlockSpec((tm, d), lambda t, j, e: (t, 0)),
                pl.BlockSpec((tm, 1), lambda t, j, e: (t, 0)),
                pl.BlockSpec((1, d, bf), lambda t, j, e: (e[t], 0, j)),
                pl.BlockSpec((1, d, bf), lambda t, j, e: (e[t], 0, j)),
                pl.BlockSpec((1, bf, d), lambda t, j, e: (e[t], j, 0))],
            out_specs=pl.BlockSpec((tm, d), lambda t, j, e: (t, 0)),
        )
        return _pc(
            _grouped_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=vmem),
            interpret=_interpret(),
        )(eids, xs, ws, w_gate, w_up, w_down)


def grouped_ffn(x, dense_w, w_gate, w_up, w_down, live=None):
    """sum over the held experts e of `dense_w[:, e] * E_e(x)` for the
    pairs with a weight above 0 of live tokens: x [n, d], dense_w [n, held]
    float32, w_gate / w_up [held, d, f], w_down [held, f, d], live [n] bool
    or None. `share_ffn`'s mathematics with the products that a weight of 0
    would erase left out: per pair `silu(x Wg) * (x Wu) * w` in float32,
    cast to x's type, the down product in float32, the pairs of a token
    summed in float32 and cast once. A token that is not live, or picked
    no held expert, gets zeros."""
    n, d = x.shape
    if n <= TOKEN_BLOCK:
        return _grouped_block(x, _picked(dense_w, live), dense_w, w_gate,
                              w_up, w_down)
    out = jax.lax.map(
        lambda block: _grouped_block(*block, w_gate, w_up, w_down),
        _token_blocks(n, x, _picked(dense_w, live), dense_w))
    return out.reshape(-1, d)[:n]


def _grouped_block(x, picked, dense_w, w_gate, w_up, w_down):
    """`grouped_ffn` of one block of tokens, `picked` [n, held] its pairs."""
    n, d = x.shape
    held = w_gate.shape[0]
    tm = ROW_TILE
    tiles = (jnp.sum(picked, axis=0, dtype=jnp.int32) + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_tiles = tile_end[-1]
    # every tile the pairs can fill (n x held pairs, each expert's last
    # tile partly empty), in whole chunks
    max_tiles = -(-n * held // tm) + held
    max_tiles += -max_tiles % _CHUNK_TILES
    # the expert of a tile; `held` beyond the tiles the pairs made
    tile_eid = jnp.sum(jnp.arange(max_tiles, dtype=jnp.int32)[:, None]
                       >= tile_end[None, :], axis=1, dtype=jnp.int32)
    # the token and the routing weight of a row: a pair's row is its place
    # among its expert's pairs (tokens ascending) behind the expert's first
    # tile; token `n` and weight 0 in a row that holds no pair
    place = (tile_end - tiles)[None, :] * tm \
        + jnp.cumsum(picked, axis=0, dtype=jnp.int32) - 1
    place = jnp.where(picked, place, max_tiles * tm).reshape(-1)
    row_tok = jnp.full((max_tiles * tm,), n, jnp.int32).at[place].set(
        jnp.repeat(jnp.arange(n, dtype=jnp.int32), held), mode="drop",
        unique_indices=True)
    row_w = jnp.zeros((max_tiles * tm,), jnp.float32).at[place].set(
        dense_w.astype(jnp.float32).reshape(-1), mode="drop",
        unique_indices=True)
    chunk = _CHUNK_TILES * tm
    tokens = jnp.arange(n, dtype=jnp.int32)[:, None]

    def body(i, acc):
        eids = jnp.minimum(jax.lax.dynamic_slice(
            tile_eid, (i * _CHUNK_TILES,), (_CHUNK_TILES,)), held - 1)
        tok = jax.lax.dynamic_slice(row_tok, (i * chunk,), (chunk,))
        ws = jax.lax.dynamic_slice(row_w, (i * chunk,), (chunk,))
        ys = _tile_products(
            x[jnp.minimum(tok, n - 1)], ws[:, None], eids,
            jnp.minimum(n_tiles - i * _CHUNK_TILES, _CHUNK_TILES),
            w_gate, w_up, w_down)
        # each row's product into its token, in float32: a product with
        # the rows' one-hot places (a row scatter-add of this size takes
        # the chip ten times as long; PERF.md section 6, PR 32). A row that
        # holds no pair matches no token, and what the kernel left
        # unwritten there is not read
        ys = jnp.where(tok[:, None] < n, ys, 0.0)
        return acc + jnp.dot((tokens == tok[None, :]).astype(jnp.float32),
                             ys, precision=_SUM_PRECISION)

    acc = jax.lax.fori_loop(0, -(-n_tiles // _CHUNK_TILES), body,
                            jnp.zeros((n, d), jnp.float32))
    return acc.astype(x.dtype)
