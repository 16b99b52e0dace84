"""`kernels/expert_hit.py`: the products of the experts HIT, the kernel's
body interpreted on the CPU, against the dense products of every held
expert (`expert_share.share_ffn`, which stays the reference and the
prefill's path). The two are the same sum with the terms a routing weight
of 0 erases left out, so they agree to the rounding of a float32 sum taken
in another order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import expert_share
from paddle_tpu.kernels import expert_hit

HELD, D, F = 16, 128, 256
F32, BF16 = jnp.float32, jnp.bfloat16
# a float32 sum in another order; a bf16 result one rounding step apart
TOL = {"float32": 2e-5, "bfloat16": 2 ** -7}


def operands(n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, D)), dtype)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(HELD, D, F)) * 0.1, dtype)
                    for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(HELD, F, D)) * 0.1, dtype)
    return x, w_gate, w_up, w_down


def routing(n, hit, seed=1):
    """[n, held] float32: every token picks among the experts `hit`, each
    of which some token picks."""
    rng = np.random.default_rng(seed)
    w = np.zeros((n, HELD), np.float32)
    for i, e in enumerate(hit):
        w[i % n, e] = 0.2 + rng.random()
    for r in range(n):
        for e in rng.choice(hit, min(len(hit), 3), replace=False) \
                if len(hit) else ():
            w[r, e] = 0.2 + rng.random()
    return jnp.asarray(w)


def close(got, want, dtype):
    got, want = (np.asarray(a.astype(F32)) for a in (got, want))
    scale = max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= TOL[jnp.dtype(dtype).name] * scale


HITS = {"none": [], "one": [11], "six": [0, 3, 4, 9, 10, 15],
        "all": list(range(HELD))}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("hit", sorted(HITS))
def test_the_hit_products_are_the_dense_products(hit, dtype):
    x, *weights = operands(16, dtype)
    dense_w = routing(16, HITS[hit])
    ids, n_hit = expert_hit.hit_table(dense_w)
    assert int(n_hit) == len(HITS[hit])
    got = expert_hit.hit_ffn(x, dense_w, *weights)
    want = expert_share.share_ffn(x, dense_w, *weights)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    if not HITS[hit]:
        assert not np.asarray(got.astype(F32)).any()   # exactly 0
    else:
        assert np.abs(np.asarray(want.astype(F32))).max() > 0.1
    close(got, want, dtype)


@pytest.mark.parametrize("hit", sorted(HITS))
def test_over_several_blocks_of_the_expert_width(hit, monkeypatch):
    """The served widths take sixteen blocks an expert; here two, so that
    a slot beyond the last hit repeats the LAST block of the last hit."""
    monkeypatch.setattr(expert_hit, "_WEIGHT_VMEM_BYTES", 6 * D * 128 * 2)
    assert expert_hit._block_width(D, F, 2) == 128
    x, *weights = operands(16, BF16, seed=8)
    dense_w = routing(16, HITS[hit], seed=8)
    close(expert_hit.hit_ffn(x, dense_w, *weights),
          expert_share.share_ffn(x, dense_w, *weights), BF16)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [1, 16, expert_hit._HIT_MAX_TOKENS])
def test_any_token_count_up_to_the_crossover(n, dtype):
    x, *weights = operands(n, dtype, seed=n)
    dense_w = routing(n, [1, 2, 7, 8, 13], seed=n)
    close(expert_hit.hit_ffn(x, dense_w, *weights),
          expert_share.share_ffn(x, dense_w, *weights), dtype)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_every_row_on_one_expert_drops_nothing(dtype):
    """Skew: sixteen tokens, one expert. Every row gets its own product."""
    x, *weights = operands(16, dtype, seed=3)
    dense_w = jnp.zeros((16, HELD), F32).at[:, 5].set(
        jnp.linspace(0.1, 1.6, 16, dtype=F32))
    got = expert_hit.hit_ffn(x, dense_w, *weights)
    close(got, expert_share.share_ffn(x, dense_w, *weights), dtype)
    assert (np.abs(np.asarray(got.astype(F32))).max(axis=1) > 1e-3).all()


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_rows_that_are_not_live_leave_live_rows_bit_equal(dtype):
    """A row that is not live adds no expert to the table and changes no
    live row: rows never mix in an FFN."""
    x, *weights = operands(16, dtype, seed=4)
    dense_w = np.array(routing(16, [2, 6, 12], seed=4))
    live = np.ones(16, bool)
    live[[3, 8, 9]] = False
    dense_w[[3, 8, 9]] = 0
    dense_w[3, 14] = dense_w[8, 0] = dense_w[9, 14] = 0.7   # theirs alone
    dense_w = jnp.asarray(dense_w)
    assert int(expert_hit.hit_table(dense_w)[1]) == 5
    ids, n_hit = expert_hit.hit_table(dense_w, jnp.asarray(live))
    assert int(n_hit) == 3 and list(np.asarray(ids)[:3]) == [2, 6, 12]
    masked = expert_hit.hit_ffn(x, dense_w, *weights, jnp.asarray(live))
    without = expert_hit.hit_ffn(x[live], dense_w[live], *weights)
    every = expert_hit.hit_ffn(x, dense_w, *weights)
    assert np.array_equal(np.asarray(masked[live].astype(F32)),
                          np.asarray(without.astype(F32)))
    assert np.array_equal(np.asarray(masked[live].astype(F32)),
                          np.asarray(every[live].astype(F32)))
    close(masked[live], expert_share.share_ffn(
        x, dense_w, *weights)[live], dtype)


@pytest.mark.parametrize("hit,live,want_ids,want_n", [
    ([4, 1, 6], None, [1, 4, 6] + [6] * 5, 3),
    ([], None, [0] * 8, 0),
    ([7], None, [7] * 8, 1),
    (list(range(8)), None, list(range(8)), 8),
    ([4, 1, 6], [False] * 4, [0] * 8, 0)])
def test_the_table_lists_the_hit_ascending_and_repeats_the_last(
        hit, live, want_ids, want_n):
    dense_w = np.zeros((4, 8), np.float32)
    for i, e in enumerate(hit):
        dense_w[i % 4, e] = 0.5
    ids, n_hit = expert_hit.hit_table(
        jnp.asarray(dense_w), None if live is None else jnp.asarray(live))
    assert ids.dtype == n_hit.dtype == jnp.int32
    assert list(np.asarray(ids)) == want_ids and int(n_hit) == want_n


def test_the_table_and_the_kernel_trace_under_jit():
    """As the burst calls it: inside a jit, the table a traced value."""
    x, *weights = operands(16, BF16, seed=6)
    dense_w = routing(16, [0, 5, 6], seed=6)
    live = jnp.arange(16) % 3 != 0
    got = jax.jit(expert_hit.hit_ffn)(x, dense_w, *weights, live)
    want = expert_share.share_ffn(x, dense_w, *weights)
    close(got[live], want[live], BF16)


def test_the_block_width_fills_its_vmem_budget():
    """Three weight blocks, double-buffered, inside `_WEIGHT_VMEM_BYTES`:
    blocks of 128 columns at the served widths; a width 128 does not
    divide (interpret mode's shapes) is one block."""
    assert expert_hit._block_width(7680, 2048, 2) == 128
    assert 6 * 7680 * 128 * 2 <= expert_hit._WEIGHT_VMEM_BYTES \
        < 6 * 7680 * 256 * 2
    assert expert_hit._block_width(2048, 2048, 2) == 512
    assert expert_hit._block_width(128, 256, 4) == 256
    assert expert_hit._block_width(48, 24, 4) == 24
