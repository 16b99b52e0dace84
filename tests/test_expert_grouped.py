"""`kernels/expert_grouped.py`: the products of the (live token, held expert
it picked) PAIRS, grouped by expert, the kernel's body interpreted on the
CPU, against the dense products of every held expert for every token
(`expert_share.share_ffn`, which stays the reference). The two are the same
sum with the terms a routing weight of 0 erases left out, so they agree to
the rounding of a float32 sum taken in another order, at any skew: no
capacity, no pair dropped."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import expert_share
from paddle_tpu.kernels import expert_grouped

HELD, D, F, TOP_K = 16, 128, 256, 8
F32, BF16 = jnp.float32, jnp.bfloat16


def operands(n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, D)), dtype)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(HELD, D, F)) * 0.1, dtype)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(HELD, F, D)) * 0.1, dtype)
    return x, w_gate, w_up, w_down


def routing(n, kind, seed=1):
    """[n, held] float32 routing weights. `uniform`: 8 picks of 128
    experts, so about one pair a token; `one_expert`: EVERY token on one
    held expert (n x 1 pairs); `all_here`: every token's 8 picks are held
    here (n x 8 pairs, the most a call can make); `none`: no pair."""
    rng = np.random.default_rng(seed)
    w = np.zeros((n, HELD), np.float32)
    for r in range(n):
        picks = {"uniform": rng.choice(8 * HELD, TOP_K, replace=False),
                 "one_expert": [5], "all_here": np.arange(TOP_K) * 2,
                 "none": []}[kind]
        for e in picks:
            if e < HELD:
                w[r, e] = 0.2 + rng.random()
    return jnp.asarray(w)


def gap(got, want):
    got, want = (np.asarray(a.astype(F32)) for a in (got, want))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-6)


def tile_rows(dense_w, tm, live=None):
    picked = np.asarray(dense_w) > 0
    if live is not None:
        picked &= np.asarray(live)[:, None]
    return int((-(-picked.sum(0) // tm) * tm).sum())


KINDS = ["all_here", "none", "one_expert", "uniform"]


@pytest.mark.parametrize("kind", KINDS)
def test_the_grouped_products_are_the_dense_products_in_float32(kind):
    """300 tokens are no multiple of a tile; `all_here` makes 2,400 pairs
    in 24 tiles, three chunks of the loop."""
    n = 300
    x, *weights = operands(n, F32)
    dense_w = routing(n, kind)
    pairs = int((np.asarray(dense_w) > 0).sum())
    assert pairs == {"all_here": 8 * n, "none": 0, "one_expert": n}.get(
        kind, pairs) and (kind != "uniform" or 0.5 * n < pairs < 1.5 * n)
    got = expert_grouped.grouped_ffn(x, dense_w, *weights)
    want = expert_share.share_ffn(x, dense_w, *weights)
    assert got.dtype == want.dtype == F32 and got.shape == want.shape
    if kind == "none":
        assert not np.asarray(got).any()   # exactly 0
    else:
        assert np.abs(np.asarray(want)).max() > 0.1
    assert gap(got, want) <= 2e-5


@pytest.mark.parametrize("kind", KINDS)
def test_in_bf16_within_the_dense_forms_own_distance_to_float32(kind):
    n = 300
    x, *weights = operands(n, BF16, seed=3)
    dense_w = routing(n, kind, seed=3)
    exact = expert_share.share_ffn(x.astype(F32), dense_w,
                                   *(w.astype(F32) for w in weights))
    dense = expert_share.share_ffn(x, dense_w, *weights)
    got = expert_grouped.grouped_ffn(x, dense_w, *weights)
    assert got.dtype == BF16
    # the same operands, products and one float32 sum a token, cast once:
    # no further from the float32 result than the dense form is, give or
    # take one rounding step of the result's type
    assert gap(got, exact) <= gap(dense, exact) + 2 ** -8
    assert gap(got, dense) <= 2 ** -7


@pytest.mark.parametrize("n", [65, 128, 129, 1025])
def test_token_counts_either_side_of_a_tile(n):
    assert expert_grouped.ROW_TILE == 128
    x, *weights = operands(n, F32, seed=n)
    dense_w = routing(n, "uniform", seed=n)
    assert gap(expert_grouped.grouped_ffn(x, dense_w, *weights),
               expert_share.share_ffn(x, dense_w, *weights)) <= 2e-5
    assert int(expert_grouped.grouped_rows(dense_w)) \
        == tile_rows(dense_w, 128)


@pytest.mark.parametrize("kind", ["all_here", "uniform"])
def test_over_several_token_blocks(kind, monkeypatch):
    """300 tokens in blocks of 128: three passes, the last zero-padded, a
    token's pairs grouped within its block."""
    monkeypatch.setattr(expert_grouped, "TOKEN_BLOCK", 128)
    n = 300
    x, *weights = operands(n, F32, seed=5)
    dense_w = routing(n, kind, seed=5)
    assert gap(expert_grouped.grouped_ffn(x, dense_w, *weights),
               expert_share.share_ffn(x, dense_w, *weights)) <= 2e-5
    assert int(expert_grouped.grouped_rows(dense_w)) == sum(
        tile_rows(dense_w[i:i + 128], 128) for i in range(0, n, 128))


@pytest.mark.parametrize("kind", ["one_expert", "uniform"])
def test_over_several_blocks_of_the_expert_width(kind, monkeypatch):
    """The served experts of 7,680 x 2,048 take sixteen blocks; here two
    (an expert that fits the budget goes whole)."""
    from paddle_tpu.kernels import expert_hit

    monkeypatch.setattr(expert_grouped, "_WHOLE_EXPERT_VMEM_BYTES", 0)
    monkeypatch.setattr(expert_hit, "_WEIGHT_VMEM_BYTES", 6 * D * 128 * 2)
    x, *weights = operands(200, BF16, seed=8)
    dense_w = routing(200, kind, seed=8)
    assert gap(expert_grouped.grouped_ffn(x, dense_w, *weights),
               expert_share.share_ffn(x, dense_w, *weights)) <= 2 ** -7


@pytest.mark.parametrize("kind", ["all_here", "uniform"])
def test_rows_that_are_not_live_add_no_row(kind):
    """A padded position makes no pair: `expert_rows` counts the live
    tokens' tiles alone, a live token's result is bit-equal with the mask
    and without, a token that is not live gets zeros."""
    n = 300
    x, *weights = operands(n, BF16, seed=9)
    dense_w = routing(n, kind, seed=9)
    live = jnp.arange(n) < 5
    masked = expert_grouped.grouped_ffn(x, dense_w, *weights, live=live)
    whole = expert_grouped.grouped_ffn(x, dense_w, *weights)
    assert np.array_equal(np.asarray(masked[:5].astype(F32)),
                          np.asarray(whole[:5].astype(F32)))
    assert not np.asarray(masked[5:].astype(F32)).any()
    rows = int(expert_grouped.grouped_rows(dense_w, live))
    assert rows == tile_rows(dense_w, 128, live) \
        < int(expert_grouped.grouped_rows(dense_w))
    # a mask of a prefill's shape, [rows, positions]
    assert int(expert_grouped.grouped_rows(dense_w, live.reshape(3, 100))) \
        == rows
    assert not int(expert_grouped.grouped_rows(dense_w, jnp.zeros(n, bool)))


def test_it_traces_under_jit_with_a_traced_mask():
    n = 200
    x, *weights = operands(n, F32, seed=11)
    dense_w = routing(n, "uniform", seed=11)
    live = jnp.arange(n) % 3 > 0
    got = jax.jit(expert_grouped.grouped_ffn)(x, dense_w, *weights, live)
    want = jnp.where(live[:, None],
                     expert_share.share_ffn(x, dense_w, *weights), 0.0)
    assert gap(got, want) <= 2e-5
    out = jax.eval_shape(expert_grouped.grouped_ffn, x, dense_w, *weights)
    assert out.shape == x.shape and out.dtype == x.dtype
