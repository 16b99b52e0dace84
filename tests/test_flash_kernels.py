"""Pallas flash-attention kernel tests (interpret mode on CPU CI —
SURVEY.md §7 phase 9; reference: phi flash_attn / flash_attn_varlen
kernels). The same kernels run compiled on TPU (chip_smoke.py's kernels
phase validates their numerics on the chip)."""
import math

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # distributed/parity suites: excluded from the fast gate

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa


def dense_ref(q, k, v, causal=False, seg_q=None, seg_k=None):
    """[b, s, h, d] f32 dense reference."""
    d = q.shape[-1]
    qt, kt, vt = (np.swapaxes(np.asarray(x, np.float32), 1, 2)
                  for x in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d)
    if causal:
        s_q, s_k = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((s_q, s_k), bool), k=s_k - s_q)
        s = np.where(mask, s, -1e30)
    if seg_q is not None:
        m = seg_q[:, None, :, None] == seg_k[:, None, None, :]
        s = np.where(m, s, -1e30)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    denom = p.sum(axis=-1, keepdims=True)
    p = np.where(denom > 0, p / np.maximum(denom, 1e-30), 0.0)
    out = np.einsum("bhqk,bhkd->bhqd", p, vt)
    return np.swapaxes(out, 1, 2)


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_parity(self, causal):
        b, s, h, d = 2, 256, 2, 128
        q, k, v = (_rand((b, s, h, d), i) for i in range(3))
        out = fa.flash_attention_bshd(q, k, v, causal=causal)
        ref = dense_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3,
                                   rtol=2e-3)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_bwd_matches_dense_autodiff(self, causal, monkeypatch):
        # force the hand-written Pallas backward (not the XLA fallback)
        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        b, s, h, d = 1, 256, 2, 128
        q, k, v = (_rand((b, s, h, d), i + 10) for i in range(3))
        do = _rand((b, s, h, d), 99)

        def loss_flash(q_, k_, v_):
            return jnp.sum(fa.flash_attention_bshd(
                q_, k_, v_, causal=causal) * do)

        def loss_ref(q_, k_, v_):
            d_ = q_.shape[-1]
            qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q_, k_, v_))
            sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d_)
            if causal:
                mask = jnp.tril(jnp.ones((s, s), bool))
                sc = jnp.where(mask, sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
            return jnp.sum(jnp.swapaxes(o, 1, 2) * do)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-3, rtol=5e-3)


class TestVarlen:
    def test_varlen_fwd_matches_per_sequence(self):
        h, d = 2, 128
        lens = [100, 60, 96]  # total 256 (one block boundary crossed)
        total = sum(lens)
        q = _rand((total, h, d), 1)
        k = _rand((total, h, d), 2)
        v = _rand((total, h, d), 3)
        cu = np.cumsum([0] + lens).astype(np.int32)
        out, _ = fa.flash_attn_unpadded(q, k, v, cu, cu, max(lens),
                                        max(lens))
        out = np.asarray(out)
        for i, ln in enumerate(lens):
            sl = slice(cu[i], cu[i + 1])
            ref = dense_ref(np.asarray(q)[None, sl], np.asarray(k)[None, sl],
                            np.asarray(v)[None, sl])[0]
            np.testing.assert_allclose(out[sl], ref, atol=2e-3, rtol=2e-3)

    def test_varlen_causal_fwd_and_grad(self, monkeypatch):
        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        h, d = 1, 128
        lens = [120, 136]
        total = sum(lens)
        q = _rand((total, h, d), 4)
        k = _rand((total, h, d), 5)
        v = _rand((total, h, d), 6)
        cu = np.cumsum([0] + lens).astype(np.int32)
        do = _rand((total, h, d), 7)

        def loss_packed(q_, k_, v_):
            o, _ = fa.flash_attn_unpadded(q_, k_, v_, cu, cu, max(lens),
                                          max(lens), causal=True)
            return jnp.sum(o * do)

        out, _ = fa.flash_attn_unpadded(q, k, v, cu, cu, max(lens),
                                        max(lens), causal=True)
        out = np.asarray(out)
        g = jax.grad(loss_packed, argnums=(0, 1, 2))(q, k, v)

        # per-sequence reference fwd + grad
        for i, ln in enumerate(lens):
            sl = slice(cu[i], cu[i + 1])
            ref = dense_ref(np.asarray(q)[None, sl], np.asarray(k)[None, sl],
                            np.asarray(v)[None, sl], causal=True)[0]
            np.testing.assert_allclose(out[sl], ref, atol=2e-3, rtol=2e-3)

            def loss_seq(q_, k_, v_):
                d_ = q_.shape[-1]
                qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q_, k_, v_))
                sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d_)
                mask = jnp.tril(jnp.ones((ln, ln), bool))
                sc = jnp.where(mask, sc, -1e30)
                p = jax.nn.softmax(sc, axis=-1)
                o = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
                return jnp.sum(jnp.swapaxes(o, 1, 2) * do[None, sl])

            gr = jax.grad(loss_seq, argnums=(0, 1, 2))(
                q[None, sl], k[None, sl], v[None, sl])
            for a, b_ in zip(g, gr):
                np.testing.assert_allclose(np.asarray(a[sl]),
                                           np.asarray(b_[0]),
                                           atol=5e-3, rtol=5e-3)

    def test_functional_wrapper(self):
        import paddle_tpu as paddle
        from paddle_tpu.nn.functional.attention import flash_attn_unpadded

        h, d = 1, 128
        lens = [64, 64]
        total = sum(lens)
        cu = np.cumsum([0] + lens).astype(np.int32)
        q = paddle.to_tensor(np.asarray(_rand((total, h, d), 8)))
        out, _ = flash_attn_unpadded(q, q, q, paddle.to_tensor(cu),
                                     paddle.to_tensor(cu), 64, 64,
                                     causal=True)
        assert tuple(out.shape) == (total, h, d)


class TestMaskedRowEdgeCases:
    def test_fully_masked_rows_emit_zero(self):
        """A q segment with NO matching k tokens must produce zero output
        and zero gradients (NEG_INF is finite: naive exp(s - m) would give
        uniform weights instead)."""
        b, s, h, d = 1, 256, 1, 128
        q, k, v = (_rand((b, s, h, d), i + 40) for i in range(3))
        seg_q = np.zeros((b, s), np.int32)
        seg_q[0, 128:] = 7  # second half: segment 7
        seg_k = np.zeros((b, s), np.int32)  # k has NO segment-7 tokens
        out = np.asarray(fa.flash_attention_bshd(
            q, k, v, segment_ids_q=seg_q, segment_ids_k=seg_k))
        np.testing.assert_array_equal(out[0, 128:], 0.0)
        assert np.abs(out[0, :128]).max() > 0

        def loss(k_, v_):
            o = fa.flash_attention_bshd(q, k_, v_, segment_ids_q=seg_q,
                                        segment_ids_k=seg_k)
            # only the masked rows contribute to the loss
            return jnp.sum(o[0, 128:] ** 2)

        gk, gv = jax.grad(loss, argnums=(0, 1))(k, v)
        np.testing.assert_array_equal(np.asarray(gk), 0.0)
        np.testing.assert_array_equal(np.asarray(gv), 0.0)

    def test_fully_masked_rows_pallas_bwd(self, monkeypatch):
        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        self.test_fully_masked_rows_emit_zero()

    def test_causal_mismatched_packing_rejected(self):
        h, d = 1, 128
        q = _rand((4, h, d), 1)
        cu_q = np.asarray([0, 2, 4], np.int32)
        cu_k = np.asarray([0, 3, 4], np.int32)
        with pytest.raises(ValueError, match="cu_seqlens_q == cu_seqlens_k"):
            fa.flash_attn_unpadded(q, q, q, cu_q, cu_k, 2, 3, causal=True)

    def test_functional_head_dim_64_fallback(self):
        """head_dim 64 (reference-supported, not MXU-tile aligned) takes
        the XLA segment-masked fallback with the same packed contract."""
        import paddle_tpu as paddle
        from paddle_tpu.nn.functional.attention import flash_attn_unpadded

        h, d = 2, 64
        lens = [5, 7]
        total = sum(lens)
        cu = np.cumsum([0] + lens).astype(np.int32)
        rng = np.random.RandomState(3)
        qn = rng.randn(total, h, d).astype(np.float32)
        q = paddle.to_tensor(qn)
        out, _ = flash_attn_unpadded(q, q, q, paddle.to_tensor(cu),
                                     paddle.to_tensor(cu), 7, 7)
        out = out.numpy()
        for i, ln in enumerate(lens):
            sl = slice(cu[i], cu[i + 1])
            ref = dense_ref(qn[None, sl], qn[None, sl], qn[None, sl])[0]
            np.testing.assert_allclose(out[sl], ref, atol=2e-3, rtol=2e-3)


class TestCausalPadding:
    def test_unequal_blocks_keep_causal_alignment(self):
        """block_q != block_k must not shift the causal diagonal via
        unequal q/k padding."""
        h, d = 1, 128
        lens = [80, 48]
        total = sum(lens)
        q = _rand((total, h, d), 21)
        cu = np.cumsum([0] + lens).astype(np.int32)
        out, _ = fa.flash_attn_unpadded(q, q, q, cu, cu, max(lens),
                                        max(lens), causal=True,
                                        block_q=128, block_k=256)
        out = np.asarray(out)
        for i, ln in enumerate(lens):
            sl = slice(cu[i], cu[i + 1])
            ref = dense_ref(np.asarray(q)[None, sl], np.asarray(q)[None, sl],
                            np.asarray(q)[None, sl], causal=True)[0]
            np.testing.assert_allclose(out[sl], ref, atol=2e-3, rtol=2e-3)


class TestLseVariant:
    @pytest.mark.parametrize("force_pallas_bwd", [False, True])
    def test_out_and_lse_grads(self, force_pallas_bwd, monkeypatch):
        """flash_attention_with_lse_bshd: both outputs differentiable; the
        lse cotangent folds into delta on BOTH backward branches (the
        Pallas d_lse path is forced via the threshold monkeypatch)."""
        if force_pallas_bwd:
            monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        b, s, h, d = 1, 256, 2, 128
        q, k, v = (_rand((b, s, h, d), i + 60) for i in range(3))
        do = _rand((b, s, h, d), 61)
        dl = _rand((b, h, s), 62) * 0.1

        def loss_flash(q_, k_, v_):
            o, lse = fa.flash_attention_with_lse_bshd(q_, k_, v_,
                                                      causal=True)
            return jnp.sum(o * do) + jnp.sum(lse * dl)

        def loss_ref(q_, k_, v_):
            d_ = q_.shape[-1]
            qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q_, k_, v_))
            sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d_)
            mask = jnp.tril(jnp.ones((s, s), bool))
            sc = jnp.where(mask, sc, -1e30)
            lse = jax.scipy.special.logsumexp(sc, axis=-1)  # [b,h,s]
            p = jnp.exp(sc - lse[..., None])
            o = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)
            return jnp.sum(o * do) + jnp.sum(lse * dl)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-3, rtol=5e-3)
