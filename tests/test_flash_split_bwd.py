"""Split dq/dkv flash-attention backward (ISSUE 2 tentpole, second half).

The backward is two Pallas passes, dq and dkv, with INDEPENDENT block
choices (kernels/flash_attention.py `_flash_bwd_split`, which every custom
VJP takes at the blocks `_flash_tiling` answers for its shape). Acceptance:
grad-check against the XLA recompute vjp to <= 1e-3 rel error in
interpret mode across causal / GQA / dropout variants, matching the
rigor of tests/test_flash_dropout.py (finite differences for the dropout
variant, where the XLA vjp cannot regenerate the in-kernel mask)."""
import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _bhsd(q):
    b, s, h, d = q.shape
    return jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)


def _make_res(b, s, h, d, causal, kv_heads=None, seed0=0):
    """(res, g, scale) over [bh, s, d]; kv_heads < h emulates GQA the way
    the training path does (kv heads repeat_interleave'd per group before
    the kernel)."""
    q = _rand((b, s, h, d), seed0)
    kvh = kv_heads or h
    k = _rand((b, s, kvh, d), seed0 + 1)
    v = _rand((b, s, kvh, d), seed0 + 2)
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    g = _rand((b, s, h, d), seed0 + 3)
    scale = 1.0 / math.sqrt(d)
    qt, kt, vt, gt = map(_bhsd, (q, k, v, g))
    out, lse = fa._flash_fwd(qt, kt, vt, scale, causal, 128, 128)
    return (qt, kt, vt, out, lse), gt, scale


BLOCK_COMBOS = [((128, 128), (128, 128)),
                ((128, 256), (256, 128)),
                ((256, 256), (128, 128))]


class TestSplitVsXlaVjp:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("kv_heads", [None, 2])  # None=MHA, 2=GQA 4:2
    def test_grads_match_xla_vjp(self, causal, kv_heads):
        b, s, h, d = 1, 256, 4, 128
        res, g, scale = _make_res(b, s, h, d, causal, kv_heads=kv_heads)
        want = fa._xla_ref_bwd(res, g, scale, causal)
        for dq_blocks, dkv_blocks in BLOCK_COMBOS:
            got = fa._flash_bwd_split(res, g, scale, causal,
                                      dq_blocks=dq_blocks,
                                      dkv_blocks=dkv_blocks)
            for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
                err = _rel_err(a, b_)
                assert err <= 1e-3, \
                    f"{name} blocks={dq_blocks}/{dkv_blocks} " \
                    f"causal={causal} gqa={kv_heads}: rel err {err}"

    def test_each_pass_alone_equals_split(self):
        b, s, h, d = 1, 256, 2, 128
        res, g, scale = _make_res(b, s, h, d, True)
        dq, dk, dv = fa._flash_bwd_split(res, g, scale, True,
                                         dq_blocks=(128, 128),
                                         dkv_blocks=(256, 256))
        do, lse8, delta8 = fa._bwd_delta(res, g)
        dq2 = fa._run_dq_pass(*res[:3], do, lse8, delta8, scale, True,
                              128, 128)
        dk2, dv2 = fa._run_dkv_pass(*res[:3], do, lse8, delta8, scale,
                                    True, 256, 256)
        np.testing.assert_array_equal(np.asarray(dq), np.asarray(dq2))
        np.testing.assert_array_equal(np.asarray(dk), np.asarray(dk2))
        np.testing.assert_array_equal(np.asarray(dv), np.asarray(dv2))

    def test_the_custom_vjp_is_the_split_at_its_blocks(self, monkeypatch):
        """What every custom VJP takes IS the split pair at the blocks it
        was handed (forward's, dK/dV pass's, dQ pass's) — bit-identical."""
        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        b, s, h, d = 1, 256, 2, 128
        res, g, scale = _make_res(b, s, h, d, True)
        blocks = ((128, 128), (256, 128), (128, 256))
        _, vjp = jax.vjp(lambda q, k, v: fa._flash_bhsd(
            q, k, v, scale, True, blocks), *res[:3])
        split = fa._flash_bwd_split(res, g, scale, True,
                                    dq_blocks=(128, 256),
                                    dkv_blocks=(256, 128))
        for a, b_ in zip(vjp(g), split):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_rectangular_seq_kv(self):
        """Cross-attention shape (s_q != s_kv) with asymmetric per-pass
        blocks exercises the causal offset in both grids."""
        b, h, d = 1, 2, 128
        s_q, s_kv = 128, 384
        q = _bhsd(_rand((b, s_q, h, d), 0))
        k = _bhsd(_rand((b, s_kv, h, d), 1))
        v = _bhsd(_rand((b, s_kv, h, d), 2))
        g = _bhsd(_rand((b, s_q, h, d), 3))
        scale = 1.0 / math.sqrt(d)
        out, lse = fa._flash_fwd(q, k, v, scale, True, 128, 128)
        res = (q, k, v, out, lse)
        want = fa._xla_ref_bwd(res, g, scale, True)
        got = fa._flash_bwd_split(res, g, scale, True,
                                  dq_blocks=(128, 384),
                                  dkv_blocks=(128, 128))
        for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
            assert _rel_err(a, b_) <= 1e-3, name


class TestSplitDropout:
    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_dropout_finite_differences(self, causal):
        """The XLA vjp cannot regenerate the in-kernel threefry mask, so
        the dropout variant grad-checks against finite differences — the
        split passes must regenerate the forward's mask bit-exactly from
        GLOBAL coordinates regardless of their (different) block sizes."""
        b, s, h, d = 1, 128, 1, 128
        drop, seed = 0.25, 42
        scale = 1.0 / math.sqrt(d)
        q = _bhsd(_rand((b, s, h, d), 0))
        k = _bhsd(_rand((b, s, h, d), 1))
        v = _bhsd(_rand((b, s, h, d), 2))
        cot = _bhsd(_rand((b, s, h, d), 9))

        @jax.custom_vjp
        def attn(q_, k_, v_):
            out, _ = fa._flash_fwd(q_, k_, v_, scale, causal, 128, 128,
                                   dropout=drop, seed=seed)
            return out

        def attn_fwd(q_, k_, v_):
            out, lse = fa._flash_fwd(q_, k_, v_, scale, causal, 128, 128,
                                     dropout=drop, seed=seed)
            return out, (q_, k_, v_, out, lse)

        def attn_bwd(res, g_):
            return fa._flash_bwd_split(res, g_, scale, causal,
                                       dq_blocks=(128, 128),
                                       dkv_blocks=(128, 128),
                                       dropout=drop, seed=seed)

        attn.defvjp(attn_fwd, attn_bwd)

        def loss(q_, k_, v_):
            return jnp.sum(attn(q_, k_, v_) * cot)

        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        rng = np.random.RandomState(0)
        eps = 1e-3
        for name, x, grad in (("dq", q, dq), ("dk", k, dk), ("dv", v, dv)):
            for _ in range(5):
                idx = tuple(rng.randint(0, dim) for dim in x.shape)
                xp = np.asarray(x).copy()
                xm = np.asarray(x).copy()
                xp[idx] += eps
                xm[idx] -= eps
                args_p = {"dq": (jnp.asarray(xp), k, v),
                          "dk": (q, jnp.asarray(xp), v),
                          "dv": (q, k, jnp.asarray(xp))}[name]
                args_m = {"dq": (jnp.asarray(xm), k, v),
                          "dk": (q, jnp.asarray(xm), v),
                          "dv": (q, k, jnp.asarray(xm))}[name]
                num = (float(loss(*args_p)) - float(loss(*args_m))) \
                    / (2 * eps)
                got = float(np.asarray(grad)[idx])
                assert abs(num - got) < 5e-2 + 0.05 * abs(num), \
                    f"{name}[{idx}]: fd={num} vjp={got}"

    def test_dropout_split_matches_fused(self):
        """Same-mask sanity without finite differences: the split passes
        at DIFFERENT blocks produce (numerically) the fused pair's grads
        for the same seed."""
        b, s, h, d = 1, 256, 2, 128
        res, g, scale = _make_res(b, s, h, d, True)
        fused = fa._flash_bwd_split(res, g, scale, True, dropout=0.3,
                                    seed=7)
        split = fa._flash_bwd_split(res, g, scale, True,
                                    dq_blocks=(256, 128),
                                    dkv_blocks=(128, 256),
                                    dropout=0.3, seed=7)
        for name, a, b_ in zip(("dq", "dk", "dv"), split, fused):
            assert _rel_err(a, b_) <= 1e-3, name


class TestSegmentedSplit:
    def test_varlen_segments_match_xla_vjp(self):
        """Packed 2-sequence stream: split passes honor the segment mask
        at asymmetric blocks."""
        b, s, h, d = 1, 256, 2, 128
        seg = jnp.concatenate([jnp.zeros((128,), jnp.int32),
                               jnp.ones((128,), jnp.int32)])
        seg8 = jnp.broadcast_to(seg[None, None, :], (b, 8, s))
        q, k, v, g = (_bhsd(_rand((b, s, h, d), i)) for i in range(4))
        scale = 1.0 / math.sqrt(d)
        # residuals from the SEGMENTED forward (the xla vjp recomputes a
        # segmented forward internally; out/lse must agree)
        out, lse = fa._flash_fwd(q, k, v, scale, False, 128, 128,
                                 seg_q=seg8, seg_k=seg8, heads=h)
        res = (q, k, v, out, lse)
        want = fa._xla_ref_bwd(res, g, scale, False, seg_q=seg8,
                               seg_k=seg8, heads=h)
        got = fa._flash_bwd_split(res, g, scale, False,
                                  dq_blocks=(128, 256),
                                  dkv_blocks=(256, 128),
                                  seg_q=seg8, seg_k=seg8, heads=h)
        for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
            assert _rel_err(a, b_) <= 1e-3, name


# ---------------------------------------------------------------------------
# the blocks `_flash_tiling` answers (PR 34): the cell's 512-wide ones at a
# length they divide, bf16 and float32 operands
# ---------------------------------------------------------------------------


def _sdpa32(q, k, v, causal):
    from paddle_tpu.nn.functional.attention import _sdpa_reference
    return _sdpa_reference(*(x.astype(jnp.float32) for x in (q, k, v)),
                           causal=causal)


class TestChosenBlocks:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("s_q,s_kv", [(1024, 1024), (512, 1024)])
    def test_fwd_and_grads_match_sdpa_reference(self, monkeypatch, dtype,
                                                causal, s_q, s_kv):
        """Operands go to the MXU in their own type; scores, softmax state
        and accumulators are float32. Against `_sdpa_reference` over the
        same (rounded) operands in float32: float32 operands to 2e-3 of the
        largest value as the split passes above, bf16 to 2e-2 (outputs,
        probabilities and dS are rounded to 8 bits of mantissa once)."""
        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        b, h, d = 1, 2, 128
        # bf16 takes the blocks the shape answers; float32 operands, which
        # `_flash_tiling` keeps at 128 x 128, are NAMED 512 x 512 ones so
        # that the large tiles meet both types
        named = {}
        if dtype == jnp.float32:
            assert fa._flash_tiling(s_q, s_kv, d, dtype) == ((128, 128),) * 3
            named = dict(block_q=512, block_k=512)
        else:
            blocks = fa._flash_tiling(s_q, s_kv, d, dtype)
            assert min(min(pair) for pair in blocks) >= 512
        q = _rand((b, s_q, h, d), 0).astype(dtype)
        k = _rand((b, s_kv, h, d), 1).astype(dtype)
        v = _rand((b, s_kv, h, d), 2).astype(dtype)
        g = _rand((b, s_q, h, d), 3)

        def run(attn):
            out, vjp = jax.vjp(lambda *a: attn(*a).astype(jnp.float32),
                               q, k, v)
            return (out,) + vjp(g)

        got = run(lambda q_, k_, v_: fa.flash_attention_bshd(
            q_, k_, v_, causal=causal, **named))
        want = run(lambda q_, k_, v_: _sdpa32(q_, k_, v_, causal))
        tol = 2e-3 if dtype == jnp.float32 else 2e-2
        for name, a, b_ in zip(("out", "dq", "dk", "dv"), got, want):
            assert a.shape == b_.shape
            assert _rel_err(a, b_) <= tol, (name, _rel_err(a, b_))


GRIDS = [  # (s_q, s_kv, block_q, block_k)
    (2048, 2048, 512, 512), (2048, 2048, 1024, 512),
    (2048, 2048, 256, 1024), (2048, 2048, 128, 128),
    (512, 1024, 256, 512), (1024, 512, 256, 256)]


@pytest.mark.parametrize("s_q,s_kv,bq,bk", GRIDS)
def test_a_future_block_is_never_fetched(s_q, s_kv, bq, bk):
    """The clamped index maps over the whole grid: a step whose tile lies
    in the causal future names the block of the nearest step that runs, so
    the pipeline copies nothing for it; a step that runs names its own."""
    offset = s_kv - s_q
    n_q, n_kv = s_q // bq, s_kv // bk

    def seen(i, j):
        return j * bk <= (i + 1) * bq - 1 + offset

    kv_map = fa._kv_index_map(True, bq, bk, offset)
    q_map = fa._q_index_map(True, bq, bk, offset)
    row_map = fa._q_index_map(True, bq, bk, offset, rows=True)
    fetched_kv, fetched_q = set(), set()
    for i in range(n_q):
        for j in range(n_kv):
            _, jj, _ = (int(x) for x in kv_map(0, i, j))
            _, ii, _ = (int(x) for x in q_map(0, j, i))
            assert int(row_map(0, j, i)[2]) == ii
            if seen(i, j):
                assert (ii, jj) == (i, j)
            # a row of queries that sees no key at all (s_q > s_kv) names
            # block 0 and skips every step
            assert seen(i, jj) or (jj == 0 and not seen(i, 0))
            assert seen(ii, j)
            fetched_kv.add((i, jj))
            fetched_q.add((ii, j))
    visible = {(i, j) for i in range(n_q) for j in range(n_kv) if seen(i, j)}
    assert fetched_q == visible
    assert fetched_kv - visible == {(i, 0) for i in range(n_q)
                                    if not seen(i, 0)}
    # and without a mask every block is its own
    assert tuple(fa._kv_index_map(False, bq, bk, offset)(3, 1, 2)) \
        == (3, 2, 0)
