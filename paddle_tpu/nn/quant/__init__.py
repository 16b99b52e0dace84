"""paddle.nn.quant parity: the weight-only quantized linear family.

Reference surface: `python/paddle/nn/quant/quantized_linear.py`
(`weight_quantize` / `weight_dequantize` / `weight_only_linear` /
`llm_int8_linear`), which upstream lowers to CUTLASS mixed-dtype GEMM
kernels tuned per SM architecture (the `arch` argument).

TPU design: decode-phase linears are HBM-bandwidth-bound — every step
streams the full weight matrix through the MXU for a handful of tokens —
so the lever is the number of bytes per weight, not the GEMM itself.
Weights are stored in HBM as int8 (or nibble-packed int4) plus per-channel
(or per-group) float32 scales; the jitted matmul dequantizes inline
(`convert → scale → dot`), which XLA fuses into the operand load. Net
effect: int8 halves and int4 quarters the weight traffic of each decode
step while keeping the MXU compute in bf16. `llm_int8_linear`
additionally runs the non-outlier activation columns through a true
int8×int8 MXU dot (`preferred_element_type=int32`).

The `arch` argument is accepted for signature parity and ignored: there
is no per-SM kernel selection on TPU — XLA owns the lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...tensor import Tensor, _apply_op, as_array
from ..layer_base import Layer

__all__ = [
    "weight_quantize", "weight_dequantize", "weight_only_linear",
    "llm_int8_linear", "WeightOnlyLinear", "quantize_for_inference",
]

_ALGOS = ("weight_only_int8", "weight_only_int4", "llm.int8")


def _check_algo(algo):
    if algo not in _ALGOS:
        raise ValueError(
            f"unsupported quantization algo {algo!r}; TPU build supports "
            f"{_ALGOS} (CUTLASS-arch-specific algos do not apply)")


def _group_shape(k, group_size):
    if group_size == -1:
        return 1, k
    if group_size not in (64, 128):
        raise ValueError("group_size must be -1 (per-channel), 64 or 128")
    if k % group_size:
        raise ValueError(f"in_features {k} not divisible by group_size "
                         f"{group_size}")
    return k // group_size, group_size


def weight_quantize(x, algo="weight_only_int8", arch=None, group_size=-1):
    """Quantize a [in_features, out_features] float weight.

    Returns `(quant_weight, scale)`:
      - int8: quant_weight int8 [k, n], scale float32 [groups, n]
        (squeezed to [n] when group_size == -1, matching upstream's
        per-channel layout)
      - int4: quant_weight int8 [k // 2, n] with two nibbles packed per
        byte along the in dim (low nibble = even row), same scale layout.

    Symmetric absmax quantization, matching the reference semantics of
    `weight_quantize` (upstream additionally permutes for the GPU kernel's
    tile layout; HBM has no such layout, so the logical [k, n] order is
    kept and `weight_dequantize` is the exact inverse).
    """
    _check_algo(algo)
    w = np.asarray(as_array(x), dtype=np.float32)
    if w.ndim != 2:
        raise ValueError(f"weight must be 2-D [in, out], got {w.shape}")
    k, n = w.shape
    bits = 4 if algo == "weight_only_int4" else 8
    qmax = (1 << (bits - 1)) - 1  # 7 or 127
    groups, gsz = _group_shape(k, group_size)
    wg = w.reshape(groups, gsz, n)
    absmax = np.abs(wg).max(axis=1)  # [groups, n]
    scale = (absmax / qmax).astype(np.float32)
    scale = np.maximum(scale, np.finfo(np.float32).tiny)
    q = np.clip(np.rint(wg / scale[:, None, :]), -qmax, qmax)
    q = q.reshape(k, n).astype(np.int8)
    if bits == 4:
        if k % 2:
            raise ValueError("int4 packing needs an even in_features")
        lo, hi = q[0::2], q[1::2]
        q = ((lo & 0xF) | (hi << 4)).astype(np.int8)  # [k//2, n]
    if group_size == -1:
        scale = scale[0]
    return Tensor(q), Tensor(scale)


def _dequant_jnp(qw, scale, weight_dtype, group_size, out_dtype):
    """Inline dequantization (traced; XLA fuses it into the consumer).

    Delegates to `kernels.quant_matmul.dequantize` — ONE copy of the
    layout-critical nibble-unpack + group-scale expansion, shared with
    the fused kernel's reference path (group count is inferred from the
    scale's shape, same as here; `group_size` stays for signature
    parity)."""
    from ...kernels.quant_matmul import dequantize

    return dequantize(qw, scale, weight_dtype, out_dtype)


def weight_dequantize(x, scale, algo="weight_only_int8", group_size=-1,
                      out_dtype="float32"):
    """Exact inverse of `weight_quantize` (reference:
    `weight_dequantize`, same module)."""
    _check_algo(algo)
    wd = "int4" if algo == "weight_only_int4" else "int8"
    return _apply_op(
        lambda q, s: _dequant_jnp(q, s, wd, group_size, jnp.dtype(out_dtype)),
        x, scale, _name="weight_dequantize")


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype="int8", arch=None, group_size=-1):
    """y = x @ dequant(weight) + bias (reference: `weight_only_linear`).

    The matmul routes through `kernels.quant_matmul.quant_matmul_dispatch`:
    the traced dequant (convert + scale, fused into the weight load by XLA)
    or, with FLAGS_quant_matmul=fused, the dequant-in-kernel Pallas path,
    which streams int8/int4 weight tiles + group scales into VMEM and
    dequantizes inside the matmul loop (the bf16 weight never exists in
    HBM).
    """
    if weight_dtype not in ("int8", "int4"):
        raise ValueError("weight_dtype must be 'int8' or 'int4'")
    if weight_scale is None:
        raise ValueError("weight_scale is required")

    from ...kernels.quant_matmul import quant_matmul_dispatch

    def f(a, q, s, *b):
        out = quant_matmul_dispatch(a, q, s, weight_dtype, group_size)
        return out + b[0] if b else out

    args = (x, weight, weight_scale) + ((bias,) if bias is not None else ())
    return _apply_op(f, *args, _name="weight_only_linear")


def llm_int8_linear(x, weight, bias=None, weight_scale=None, threshold=6.0):
    """LLM.int8() decomposition (reference: `llm_int8_linear`).

    Activation columns whose absmax exceeds `threshold` (the outliers) run
    in x.dtype against dequantized weight columns; the rest is dynamically
    per-row quantized and dispatched as a TRUE int8×int8 MXU dot
    (`preferred_element_type=int32`), then rescaled by
    `x_scale ⊗ weight_scale`. Outlier selection is a static-shape mask
    (two full-size matmuls), not a gather — data-dependent shapes do not
    trace under jit (SURVEY.md "XLA semantics"); XLA still saves the
    int8 operand bandwidth on the main path, which is where decode time
    goes.
    """
    if weight_scale is None:
        raise ValueError("weight_scale is required")
    if len(weight_scale.shape) == 2 and int(weight_scale.shape[0]) == 1:
        weight_scale = weight_scale.reshape([-1])
    if len(weight_scale.shape) != 1:
        raise ValueError("llm.int8 takes per-channel scales only "
                         "(grouped scales would dequantize every group "
                         "after the first with the wrong factor)")

    def f(a, q, s, *b):
        col_absmax = jnp.max(jnp.abs(a.astype(jnp.float32)),
                             axis=tuple(range(a.ndim - 1)))
        outlier = col_absmax > threshold  # [k]
        a_main = jnp.where(outlier, 0.0, a.astype(jnp.float32))
        # dynamic symmetric per-row activation quant
        row_scale = jnp.max(jnp.abs(a_main), axis=-1, keepdims=True) / 127.0
        row_scale = jnp.maximum(row_scale, jnp.finfo(jnp.float32).tiny)
        aq = jnp.clip(jnp.rint(a_main / row_scale), -127, 127).astype(jnp.int8)
        main = jax.lax.dot_general(
            aq, q, (((aq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        sw = s.astype(jnp.float32)
        main = main * row_scale * sw  # [.., n]
        a_out = jnp.where(outlier, a.astype(jnp.float32), 0.0)
        w_deq = q.astype(jnp.float32) * sw[None, :]
        out = (main + jnp.matmul(a_out, w_deq)).astype(a.dtype)
        return out + b[0] if b else out

    args = (x, weight, weight_scale) + ((bias,) if bias is not None else ())
    return _apply_op(f, *args, _name="llm_int8_linear")


class WeightOnlyLinear(Layer):
    """Inference Linear over quantized weight storage.

    Drop-in replacement produced by `quantize_for_inference` for
    `nn.Linear` / `ColumnParallelLinear` / `RowParallelLinear` (reference
    analogue: PaddleNLP's WeightOnlyLinear over the
    `weight_only_linear` op). The tp shard semantics of the source layer
    are replayed: the int8 weight buffer inherits the source weight's
    sharding spec (the [k, n] layout is unchanged; int4 packs along k,
    which only halves the k extent), the per-channel scale shards with the
    out dim, and the source's input/output `shard_tensor` calls are
    reproduced so GSPMD places the same collectives around the quantized
    matmul.
    """

    def __init__(self, in_features, out_features, algo="weight_only_int8",
                 group_size=-1, name=None):
        super().__init__()
        _check_algo(algo)
        if algo == "llm.int8" and group_size != -1:
            # llm_int8_linear's int8×int8 main path rescales by one
            # per-channel factor; grouped scales have no home there
            # (upstream's llm_int8_linear has no group_size either)
            raise ValueError("algo='llm.int8' supports per-channel scales "
                             "only (group_size=-1)")
        self._in_features = in_features
        self._out_features = out_features
        self._algo = algo
        self._weight_dtype = "int4" if algo == "weight_only_int4" else "int8"
        self._group_size = group_size
        self._pre_shard = None   # e.g. (None, None, "tp") for row-parallel
        self._post_shard = None  # source layer's output shard_tensor spec
        self.bias = None
        k = in_features // 2 if self._weight_dtype == "int4" else in_features
        groups = 1 if group_size == -1 else in_features // group_size
        sshape = (out_features,) if group_size == -1 else (groups,
                                                          out_features)
        self.register_buffer("quant_weight",
                             Tensor(np.zeros((k, out_features), np.int8)))
        self.register_buffer("weight_scale",
                             Tensor(np.zeros(sshape, np.float32)))

    @classmethod
    def from_source(cls, layer, algo="weight_only_int8", group_size=-1):
        """Quantize an existing linear-family layer into a new instance."""
        w = layer.weight
        k, n = int(w.shape[0]), int(w.shape[1])
        obj = cls(k, n, algo=algo, group_size=group_size)
        qw, scale = weight_quantize(w, algo=algo if algo != "llm.int8"
                                    else "weight_only_int8",
                                    group_size=group_size)
        obj.quant_weight = qw
        obj.weight_scale = scale
        # __init__'s `self.bias = None` left a plain instance-dict entry;
        # drop it or it would shadow the Parameter that Layer.__setattr__
        # routes into _parameters (attribute lookup hits __dict__ first)
        obj.__dict__.pop("bias", None)
        obj.bias = layer.bias
        obj.training = False
        # replay the source's sharding contract
        spec = getattr(w, "sharding_spec", None)
        if spec is not None:
            obj.quant_weight.sharding_spec = tuple(spec)
            out_spec = spec[-1] if len(spec) == 2 else None
            obj.weight_scale.sharding_spec = (
                (out_spec,) if scale.ndim == 1 else (None, out_spec))
        cname = type(layer).__name__
        if cname == "ColumnParallelLinear":
            obj._post_shard = ((None, None, None) if layer.gather_output
                               else (None, None, "tp"))
        elif cname == "RowParallelLinear":
            if layer.input_is_parallel:
                obj._pre_shard = (None, None, "tp")
            obj._post_shard = (None, None, None)
        return obj

    def forward(self, x):
        if self._pre_shard is not None:  # row-parallel input stays sharded
            from ...distributed.sharding_utils import shard_tensor
            x = shard_tensor(x, *self._pre_shard)
        if self._algo == "llm.int8":
            out = llm_int8_linear(x, self.quant_weight, self.bias,
                                  self.weight_scale)
        else:
            out = weight_only_linear(x, self.quant_weight, self.bias,
                                     self.weight_scale, self._weight_dtype,
                                     group_size=self._group_size)
        if self._post_shard is not None:
            from ...distributed.sharding_utils import shard_tensor
            out = shard_tensor(out, *self._post_shard)
        return out

    def extra_repr(self):
        return (f"in_features={self._in_features}, "
                f"out_features={self._out_features}, algo={self._algo}")


def _walk_linear_family(model, replace):
    """Shared in-place walker over linear-family sublayers.

    `replace(name, full_name, child)` returns the replacement layer or
    None to keep the child. Used by `quantize_for_inference` here and by
    `paddle.quantization`'s QAT/PTQ swap — one predicate, one traversal.
    """
    targets = ("Linear", "ColumnParallelLinear", "RowParallelLinear")

    def _walk(parent, prefix):
        for name, child in list(parent._sub_layers.items()):
            full = f"{prefix}.{name}" if prefix else name
            if (type(child).__name__ in targets
                    and getattr(child, "weight", None) is not None
                    and len(child.weight.shape) == 2):
                rep = replace(name, full, child)
                if rep is not None:
                    setattr(parent, name, rep)
            else:
                _walk(child, full)

    _walk(model, "")
    return model


def quantize_for_inference(model, algo="weight_only_int8", group_size=-1,
                           exclude=()):
    """Swap every linear-family sublayer for a `WeightOnlyLinear` holding
    quantized storage (in place; returns the model).

    `exclude` lists sublayer names (attribute or dotted-qualified) to
    keep in float (e.g. `("lm_head",)` — logits are the layer most
    sensitive to weight noise). Reference analogue: PaddleNLP's
    weight-only conversion over `fused_multi_transformer`; here the
    serving engine picks the buffers up through `buffers_pytree()` with
    no engine changes.
    """
    _check_algo(algo)

    def replace(name, full, child):
        if full in exclude or name in exclude:
            return None
        return WeightOnlyLinear.from_source(child, algo, group_size)

    return _walk_linear_family(model, replace)
