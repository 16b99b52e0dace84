"""In-kernel flash-attention dropout (round-5; reference: paddle
flash_attn dropout_p — SURVEY.md §2.1 fusion row, §5 long-context).

The mask is counter-based threefry2x32 keyed by (seed, batch-head,
global q pos, global k pos), evaluated with plain int32 vector ops so
interpret mode (these tests) and real Mosaic produce identical bits.
Grad checks run the custom VJP against finite differences — which only
passes if forward and backward regenerate bit-identical masks."""
import math

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa


def _rand(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


class TestDropoutForward:
    def test_zero_dropout_matches_base_kernel(self):
        b, s, h, d = 1, 256, 2, 128
        q, k, v = (_rand((b, s, h, d), i) for i in range(3))
        base = fa.flash_attention_bshd(q, k, v, causal=True)
        # dropout=0.0 routes to the base kernel; seed ignored
        same = fa.flash_attention_bshd(q, k, v, causal=True, dropout=0.0)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(same))

    def test_deterministic_per_seed_and_varies_across_seeds(self):
        b, s, h, d = 1, 256, 2, 128
        q, k, v = (_rand((b, s, h, d), i) for i in range(3))
        a1 = fa.flash_attention_bshd(q, k, v, dropout=0.2, dropout_seed=7)
        a2 = fa.flash_attention_bshd(q, k, v, dropout=0.2, dropout_seed=7)
        b1 = fa.flash_attention_bshd(q, k, v, dropout=0.2, dropout_seed=8)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        assert not np.allclose(np.asarray(a1), np.asarray(b1))

    def test_keep_rate_statistics(self):
        # the keep mask itself: fraction kept ~ 1 - rate
        rate = 0.3
        keep = fa._dropout_keep(jnp.int32(123), jnp.int32(0), 0, 0,
                                256, 256, rate)
        frac = float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(frac - (1.0 - rate)) < 0.02

    def test_threefry_blocks_are_decorrelated(self):
        # adjacent blocks / batch-heads draw from disjoint counters
        k1 = fa._dropout_keep(jnp.int32(1), jnp.int32(0), 0, 0, 128, 128,
                              0.5)
        k2 = fa._dropout_keep(jnp.int32(1), jnp.int32(0), 0, 1, 128, 128,
                              0.5)
        k3 = fa._dropout_keep(jnp.int32(1), jnp.int32(1), 0, 0, 128, 128,
                              0.5)
        agree12 = float(jnp.mean((k1 == k2).astype(jnp.float32)))
        agree13 = float(jnp.mean((k1 == k3).astype(jnp.float32)))
        assert 0.4 < agree12 < 0.6
        assert 0.4 < agree13 < 0.6

    def test_mean_preserving_vs_no_dropout(self):
        # inverted dropout: averaging over many seeds approaches the
        # undropped output
        b, s, h, d = 1, 128, 1, 128
        q, k, v = (_rand((b, s, h, d), i) for i in range(3))
        base = np.asarray(fa.flash_attention_bshd(q, k, v))
        acc = np.zeros_like(base)
        n = 24
        for seed in range(n):
            acc += np.asarray(fa.flash_attention_bshd(
                q, k, v, dropout=0.3, dropout_seed=seed))
        err = np.abs(acc / n - base).mean() / np.abs(base).mean()
        assert err < 0.15


class TestDropoutBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grad_matches_finite_differences(self, causal):
        # fixed seed -> deterministic function of (q, k, v); the custom
        # VJP must match numerical gradients, which requires the bwd
        # kernels to regenerate the forward's exact mask
        b, s, h, d = 1, 128, 1, 128
        q, k, v = (_rand((b, s, h, d), i) for i in range(3))
        cot = _rand((b, s, h, d), 9)

        def loss(q_, k_, v_):
            out = fa.flash_attention_bshd(q_, k_, v_, causal=causal,
                                          dropout=0.25, dropout_seed=42)
            return jnp.sum(out * cot)

        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        rng = np.random.RandomState(0)
        eps = 1e-3
        for name, x, g in (("dq", q, dq), ("dk", k, dk), ("dv", v, dv)):
            for _ in range(5):
                idx = tuple(rng.randint(0, dim) for dim in x.shape)
                xp = np.asarray(x).copy()
                xm = np.asarray(x).copy()
                xp[idx] += eps
                xm[idx] -= eps
                args = {"dq": (jnp.asarray(xp), k, v),
                        "dk": (q, jnp.asarray(xp), v),
                        "dv": (q, k, jnp.asarray(xp))}[name]
                argsm = {"dq": (jnp.asarray(xm), k, v),
                         "dk": (q, jnp.asarray(xm), v),
                         "dv": (q, k, jnp.asarray(xm))}[name]
                num = (float(loss(*args)) - float(loss(*argsm))) / (2 * eps)
                got = float(np.asarray(g)[idx])
                assert abs(num - got) < 5e-2 + 0.05 * abs(num), \
                    f"{name}[{idx}]: fd={num} vjp={got}"

    def test_varlen_dropout_grads_finite(self):
        # packed 2-sequence stream with dropout: grads flow, cross-seq
        # entries stay masked
        h, d = 1, 128
        lens = [96, 64]
        total = sum(lens)
        q, k, v = (_rand((total, h, d), i) for i in range(3))
        cu = jnp.asarray(np.cumsum([0] + lens), jnp.int32)

        def loss(q_):
            out, _ = fa.flash_attn_unpadded(
                q_, k, v, cu, cu, max(lens), max(lens), causal=True,
                dropout=0.2, dropout_seed=5)
            return jnp.sum(out ** 2)

        g = jax.grad(loss)(q)
        assert np.isfinite(np.asarray(g)).all()

    def test_sdpa_dropout_training_routes_to_flash(self, monkeypatch):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.framework import config as _config

        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 0)
        # the in-kernel dropout route is opt-in (default off) until
        # validated under real Mosaic — ADVICE.md round-5 policy
        monkeypatch.setattr(
            _config._FLAGS["FLAGS_flash_dropout_kernel"], "value", True)
        paddle.seed(1234)
        b, s, h, d = 1, 256, 2, 128
        q = paddle.to_tensor(np.asarray(_rand((b, s, h, d), 0)))
        k = paddle.to_tensor(np.asarray(_rand((b, s, h, d), 1)))
        v = paddle.to_tensor(np.asarray(_rand((b, s, h, d), 2)))
        out = F.scaled_dot_product_attention(q, k, v, dropout_p=0.3,
                                             is_causal=True, training=True)
        assert out.shape == q.shape
        ref = F.scaled_dot_product_attention(q, k, v, dropout_p=0.0,
                                             is_causal=True, training=True)
        # dropout actually happened (outputs differ from the clean path)
        assert not np.allclose(np.asarray(out.numpy()),
                               np.asarray(ref.numpy()))

    def test_threefry_matches_jax_reference_bits(self):
        # our int32-lane threefry2x32 must equal jax's own threefry for
        # the same key/counter words (spot-check a few lanes)
        from jax._src.prng import threefry_2x32

        k0, k1 = np.uint32(7), np.uint32(3)
        c = np.arange(8, dtype=np.uint32)
        ref = threefry_2x32(jnp.asarray([k0, k1]),
                            jnp.stack([c, c + 100]).ravel())
        # reference returns the concatenated x0 (first half) and x1; our
        # kernel helper returns x0 for counters (c0, c1)
        got = fa._threefry2x32(jnp.int32(7), jnp.int32(3),
                               jnp.asarray(c, jnp.int32),
                               jnp.asarray(c + 100, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(got).astype(np.uint32), np.asarray(ref)[:8])
