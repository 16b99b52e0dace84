"""Fused dequant-matmul Pallas kernel (ISSUE 9 tentpole a;
paddle_tpu/kernels/quant_matmul.py).

Acceptance contract: the fused kernel matches the XLA traced-dequant
reference to <= 1e-2 (int8) / 3e-2 (int4) across {group_size -1/64/128}
x rectangular shapes in interpret mode; and `weight_only_linear` /
`WeightOnlyLinear.forward` route through the dispatcher with zero model
changes. The int4 pack-layout golden in tests/test_quantization.py is
the storage format this kernel consumes."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import config as _config
from paddle_tpu.kernels import quant_matmul as qm
from paddle_tpu.nn.quant import (
    WeightOnlyLinear,
    weight_dequantize,
    weight_only_linear,
    weight_quantize,
)


@pytest.fixture
def fused_calls(monkeypatch):
    """FLAGS_quant_matmul=fused, and the calls the fused kernel gets."""
    monkeypatch.setattr(_config._FLAGS["FLAGS_quant_matmul"], "value",
                        "fused")
    calls, orig = [], qm.quant_matmul_fused

    def spy(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(qm, "quant_matmul_fused", spy)
    return calls


def _quantized(k, n, algo, gs, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(k, n).astype(np.float32)
    qw, sc = weight_quantize(paddle.to_tensor(w), algo=algo,
                             group_size=gs)
    return w, jnp.asarray(qw.numpy()), jnp.asarray(sc.numpy())


class TestKernelParity:
    @pytest.mark.parametrize("algo,wd,atol", [
        ("weight_only_int8", "int8", 1e-2),
        ("weight_only_int4", "int4", 3e-2),
    ])
    @pytest.mark.parametrize("gs", [-1, 64, 128])
    @pytest.mark.parametrize("m,k,n", [(8, 256, 384), (5, 512, 128),
                                       (33, 128, 256)])
    def test_fused_matches_xla_reference(self, algo, wd, atol, gs, m, k,
                                         n):
        """The ISSUE 9 acceptance matrix: fused == xla-dequant reference
        within tolerance across group sizes x rectangular shapes (every
        supported block pair, interpret mode)."""
        _w, qw, sc = _quantized(k, n, algo, gs)
        x = jnp.asarray(np.random.RandomState(1).randn(m, k)
                        .astype(np.float32))
        ref = qm.quant_matmul_xla(x, qw, sc, wd)
        tested = 0
        for bn in qm.BLOCK_GRID_N:
            for bk in qm.BLOCK_GRID_K:
                if not qm.supports(m, k, n, wd, gs, bn, bk):
                    continue
                out = qm.quant_matmul_fused(x, qw, sc, wd, gs, bn, bk)
                np.testing.assert_allclose(np.asarray(out),
                                           np.asarray(ref), atol=atol)
                tested += 1
        assert tested > 0, "no supported block pair for this shape"

    def test_xla_reference_matches_dequantize(self):
        """The 'reference' really is dequant-then-matmul: checked against
        nn.quant.weight_dequantize (whose int4 round-trip golden lives in
        tests/test_quantization.py)."""
        for algo, wd in [("weight_only_int8", "int8"),
                         ("weight_only_int4", "int4")]:
            for gs in (-1, 64):
                _w, qw, sc = _quantized(128, 256, algo, gs)
                x = np.random.RandomState(2).randn(4, 128).astype(
                    np.float32)
                ref = np.asarray(weight_dequantize(
                    paddle.to_tensor(np.asarray(qw)),
                    paddle.to_tensor(np.asarray(sc)), algo=algo,
                    group_size=gs).numpy())
                got = np.asarray(qm.quant_matmul_xla(
                    jnp.asarray(x), qw, sc, wd))
                np.testing.assert_allclose(got, x @ ref, atol=1e-3)

    def test_supports_edges(self):
        # a k block must cover whole scale groups
        assert not qm.supports(8, 256, 256, "int8", 64, 128, 100)
        # ... and its scale rows must form a tile Mosaic takes: 8 rows,
        # or the whole scale array (one k block) — a 2-row tile compiles
        # in interpret mode and is refused on the chip
        # (tests/test_kernels_compile_tpu.py)
        assert not qm.supports(8, 256, 256, "int8", 64, 128, 128)
        assert qm.supports(8, 256, 256, "int8", 64, 128, 256)
        assert qm.supports(8, 1024, 256, "int8", 64, 128, 512)
        # shape must tile
        assert not qm.supports(8, 250, 256, "int8", -1, 128, 128)
        assert not qm.supports(8, 256, 200, "int8", -1, 128, 128)
        # m cap (decode windows are small by construction)
        assert not qm.supports(qm._MAX_M + 1, 256, 256, "int8", -1,
                               128, 128)
        assert not qm.supports(0, 256, 256, "int8", -1, 128, 128)

    def test_unpack_int4_layout(self):
        """unpack_int4 inverts weight_quantize's nibble pack exactly
        (low nibble = even row)."""
        rng = np.random.RandomState(3)
        w = rng.randn(64, 128).astype(np.float32)
        qw, sc = weight_quantize(paddle.to_tensor(w),
                                 algo="weight_only_int4")
        unpacked = np.asarray(qm.unpack_int4(jnp.asarray(qw.numpy())))
        assert unpacked.shape == (64, 128)
        assert unpacked.min() >= -7 and unpacked.max() <= 7
        packed = np.asarray(qw.numpy())
        np.testing.assert_array_equal(unpacked[0::2],
                                      (packed << 4 >> 4))
        np.testing.assert_array_equal(unpacked[1::2], packed >> 4)


class TestDispatch:
    def test_default_is_xla_bit_identical(self, monkeypatch):
        """With no flag set the dispatch IS the traced-dequant
        expression, bit for bit."""
        _w, qw, sc = _quantized(128, 256, "weight_only_int8", -1)
        x = jnp.asarray(np.random.RandomState(4).randn(3, 128)
                        .astype(np.float32))
        got = qm.quant_matmul_dispatch(x, qw, sc, "int8", -1)
        ref = qm.quant_matmul_xla(x, qw, sc, "int8")
        assert np.array_equal(np.asarray(got), np.asarray(ref))

    def test_forced_fused_runs_kernel(self, monkeypatch):
        monkeypatch.setattr(_config._FLAGS["FLAGS_quant_matmul"],
                            "value", "fused")
        _w, qw, sc = _quantized(128, 256, "weight_only_int8", 64)
        x = jnp.asarray(np.random.RandomState(5).randn(4, 128)
                        .astype(np.float32))
        got = qm.quant_matmul_dispatch(x, qw, sc, "int8", 64)
        ref = qm.quant_matmul_xla(x, qw, sc, "int8")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-2)

    def test_forced_fused_unsupported_shape_falls_back(self,
                                                       monkeypatch):
        monkeypatch.setattr(_config._FLAGS["FLAGS_quant_matmul"],
                            "value", "fused")
        # n == 96 does not tile to 128 lanes: dispatch must quietly take
        # the XLA path, not raise
        _w, qw, sc = _quantized(128, 96, "weight_only_int8", -1)
        x = jnp.asarray(np.random.RandomState(6).randn(2, 128)
                        .astype(np.float32))
        got = qm.quant_matmul_dispatch(x, qw, sc, "int8", -1)
        ref = qm.quant_matmul_xla(x, qw, sc, "int8")
        assert np.array_equal(np.asarray(got), np.asarray(ref))

    def test_weight_only_linear_routes_through_dispatcher(
            self, fused_calls):
        """The wiring: with the flag saying fused,
        nn.quant.weight_only_linear reaches the kernel with zero
        call-site changes."""
        rng = np.random.RandomState(7)
        w = rng.randn(128, 256).astype(np.float32)
        qw, sc = weight_quantize(paddle.to_tensor(w), group_size=64)
        x = paddle.to_tensor(rng.randn(4, 128).astype(np.float32))
        y = weight_only_linear(x, qw, None, sc, "int8", group_size=64)
        ref = x.numpy() @ np.asarray(weight_dequantize(
            qw, sc, group_size=64).numpy())
        np.testing.assert_allclose(y.numpy(), ref, atol=1e-2)
        assert len(fused_calls) == 1

    def test_weight_only_layer_forward_uses_dispatch(self, fused_calls):
        """WeightOnlyLinear.forward (the layer quantize_for_inference
        installs) flows through the same dispatcher."""
        from paddle_tpu import nn

        rng = np.random.RandomState(8)
        lin = nn.Linear(128, 256)
        lin.weight.set_value(rng.randn(128, 256).astype(np.float32))
        wol = WeightOnlyLinear.from_source(lin, "weight_only_int8", -1)
        x = paddle.to_tensor(rng.randn(3, 128).astype(np.float32))
        y = wol(x)
        ref = lin(x)
        # int8 weight noise only — the two layers share the bias (none);
        # the bound is the 3-sigma accumulated lattice noise at k=128
        # (this test pins ROUTING, TestKernelParity pins accuracy)
        np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=0.05,
                                   atol=0.35)
        assert len(fused_calls) == 1
