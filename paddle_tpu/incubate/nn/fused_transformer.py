"""FusedMultiTransformer — the serving engine surface.

Reference parity: paddle/fluid/operators/fused/fused_multi_transformer_op
(+ python/paddle/incubate/nn/layer/fused_transformer.py — SURVEY.md §2.1
"Fused transformer ops"): a whole decoder stack in one op with KV cache,
pre/post-norm, rotary; plus FusedMultiHeadAttention / FusedFeedForward.

TPU-native design: each layer step is a fused XLA program (jit traces the
whole stack); the decode path writes KV into a preallocated dense cache via
dynamic_update_slice (paged Pallas cache: paddle_tpu.kernels.paged_kv). All
weights follow the reference's list-per-layer layout so PaddleNLP-style
loaders map 1:1.
"""
from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer_base import Layer
from ...tensor import Tensor, _apply_op, as_array


def fused_multi_head_attention(x, qkv_weight, linear_weight, pre_layer_norm=False,
                               pre_ln_scale=None, pre_ln_bias=None,
                               ln_scale=None, ln_bias=None, pre_ln_epsilon=1e-05,
                               qkv_bias=None, linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-05,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, name=None):
    """Functional fused MHA (reference: F.fused_multi_head_attention)."""
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, [as_array(x).shape[-1]], pre_ln_scale, pre_ln_bias,
                         pre_ln_epsilon)
    b, s, d = x.shape
    # qkv_weight: [3, num_heads, head_dim, d]
    nh = qkv_weight.shape[1]
    hd = qkv_weight.shape[2]

    def qkv_fn(a, w, *bias):
        out = jnp.einsum("bsd,thkd->bsthk", a, w)
        if bias:
            out = out + bias[0]
        return out

    args = [qkv_bias] if qkv_bias is not None else []
    qkv = _apply_op(qkv_fn, x, qkv_weight, *args, _name="qkv")
    from ...ops.manipulation import unbind

    q, k, v = unbind(qkv, axis=2)
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0, training=training)
    from ...ops.manipulation import reshape

    out = reshape(out, [b, s, nh * hd])
    out = F.linear(out, linear_weight, linear_bias)
    if dropout_rate:
        out = F.dropout(out, dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, [out.shape[-1]], ln_scale, ln_bias, ln_epsilon)
    return out


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu", ln1_epsilon=1e-5,
                      ln2_epsilon=1e-5, pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1, add_residual=True,
                      name=None):
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, [x.shape[-1]], ln1_scale, ln1_bias, ln1_epsilon)
    out = F.linear(x, linear1_weight, linear1_bias)
    out = getattr(F, activation)(out)
    out = F.dropout(out, dropout1_rate, training=training, mode=mode)
    out = F.linear(out, linear2_weight, linear2_bias)
    out = F.dropout(out, dropout2_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, [out.shape[-1]], ln2_scale, ln2_bias,
                           ln2_epsilon)
    return out


class FusedMultiHeadAttention(Layer):
    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, kdim=None, vdim=None,
                 normalize_before=False, need_weights=False, qkv_weight_attr=None,
                 qkv_bias_attr=None, linear_weight_attr=None,
                 linear_bias_attr=None, pre_ln_scale_attr=None,
                 pre_ln_bias_attr=None, ln_scale_attr=None, ln_bias_attr=None,
                 epsilon=1e-5, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.dropout_rate = dropout_rate
        self.attn_dropout_rate = attn_dropout_rate
        self._epsilon = epsilon
        self.qkv_weight = self.create_parameter(
            [3, num_heads, self.head_dim, embed_dim], attr=qkv_weight_attr)
        self.qkv_bias = self.create_parameter(
            [3, num_heads, self.head_dim], attr=qkv_bias_attr, is_bias=True)
        self.linear_weight = self.create_parameter(
            [embed_dim, embed_dim], attr=linear_weight_attr)
        self.linear_bias = self.create_parameter(
            [embed_dim], attr=linear_bias_attr, is_bias=True)
        self.pre_ln_scale = self.create_parameter(
            [embed_dim], attr=pre_ln_scale_attr,
            default_initializer=I.Constant(1.0))
        self.pre_ln_bias = self.create_parameter(
            [embed_dim], attr=pre_ln_bias_attr, is_bias=True)
        self.ln_scale = self.create_parameter(
            [embed_dim], attr=ln_scale_attr,
            default_initializer=I.Constant(1.0))
        self.ln_bias = self.create_parameter(
            [embed_dim], attr=ln_bias_attr, is_bias=True)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        return fused_multi_head_attention(
            query, self.qkv_weight, self.linear_weight,
            pre_layer_norm=self.normalize_before,
            pre_ln_scale=self.pre_ln_scale, pre_ln_bias=self.pre_ln_bias,
            ln_scale=self.ln_scale, ln_bias=self.ln_bias,
            qkv_bias=self.qkv_bias, linear_bias=self.linear_bias,
            attn_mask=attn_mask, dropout_rate=self.dropout_rate,
            attn_dropout_rate=self.attn_dropout_rate,
            training=self.training,
        )


class FusedFeedForward(Layer):
    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1,
                 epsilon=1e-05, activation="relu", act_dropout_rate=None,
                 normalize_before=False, linear1_weight_attr=None,
                 linear1_bias_attr=None, linear2_weight_attr=None,
                 linear2_bias_attr=None, ln1_scale_attr=None,
                 ln1_bias_attr=None, ln2_scale_attr=None, ln2_bias_attr=None,
                 nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.activation = activation
        self.dropout_rate = dropout_rate
        self.act_dropout_rate = act_dropout_rate if act_dropout_rate is not None \
            else dropout_rate
        self._epsilon = epsilon
        self.linear1_weight = self.create_parameter(
            [d_model, dim_feedforward], attr=linear1_weight_attr)
        self.linear1_bias = self.create_parameter(
            [dim_feedforward], attr=linear1_bias_attr, is_bias=True)
        self.linear2_weight = self.create_parameter(
            [dim_feedforward, d_model], attr=linear2_weight_attr)
        self.linear2_bias = self.create_parameter(
            [d_model], attr=linear2_bias_attr, is_bias=True)
        self.ln1_scale = self.create_parameter(
            [d_model], attr=ln1_scale_attr, default_initializer=I.Constant(1.0))
        self.ln1_bias = self.create_parameter([d_model], attr=ln1_bias_attr,
                                              is_bias=True)
        self.ln2_scale = self.create_parameter(
            [d_model], attr=ln2_scale_attr, default_initializer=I.Constant(1.0))
        self.ln2_bias = self.create_parameter([d_model], attr=ln2_bias_attr,
                                              is_bias=True)

    def forward(self, src, cache=None):
        return fused_feedforward(
            src, self.linear1_weight, self.linear2_weight, self.linear1_bias,
            self.linear2_bias, self.ln1_scale, self.ln1_bias, self.ln2_scale,
            self.ln2_bias, self.act_dropout_rate, self.dropout_rate,
            self.activation, self._epsilon, self._epsilon,
            self.normalize_before, training=self.training,
        )


class FusedTransformerEncoderLayer(Layer):
    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        self.fused_attn = FusedMultiHeadAttention(
            d_model, nhead,
            dropout_rate=dropout_rate,
            attn_dropout_rate=attn_dropout_rate if attn_dropout_rate is not None
            else dropout_rate,
            normalize_before=normalize_before,
        )
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate=dropout_rate,
            activation=activation,
            act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before,
        )

    def forward(self, src, src_mask=None, cache=None):
        out = self.fused_attn(src, attn_mask=src_mask)
        return self.ffn(out)


class FusedMultiTransformer(Layer):
    """The whole decoder stack as one fused module with KV cache — the
    serving engine (reference: fused_multi_transformer_op; config-5 model,
    BASELINE.json #5).

    Weights are per-layer lists, same structure as the reference op inputs
    (ln_scales, qkv_weights[3,nh,hd,d], out_proj, ffn1/ffn2, ffn_ln). Only
    pre-norm (normalize_before=True) is supported, matching the reference's
    serving configuration. `forward(x, cache_kvs=..., time_step=...)`
    implements incremental decode into dense preallocated caches.
    """

    def __init__(self, embed_dim, num_heads, dim_feedforward, dropout_rate=0.0,
                 activation="gelu", normalize_before=True, ln_scale_attrs=None,
                 ln_bias_attrs=None, qkv_weight_attrs=None, qkv_bias_attrs=None,
                 linear_weight_attrs=None, linear_bias_attrs=None,
                 ffn_ln_scale_attrs=None, ffn_ln_bias_attrs=None,
                 ffn1_weight_attrs=None, ffn1_bias_attrs=None,
                 ffn2_weight_attrs=None, ffn2_bias_attrs=None, epsilon=1e-5,
                 num_layers=-1, nranks=1, trans_qkvw=True, ring_id=-1,
                 name=None):
        super().__init__()
        assert normalize_before, "FusedMultiTransformer is pre-norm only"
        if num_layers < 0:
            num_layers = len(qkv_weight_attrs) if isinstance(
                qkv_weight_attrs, (list, tuple)) else 1
        self.num_layers = num_layers
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.dim_feedforward = dim_feedforward
        self._epsilon = epsilon
        self.activation = activation
        self.dropout_rate = dropout_rate

        def attr_i(attrs, i):
            return attrs[i] if isinstance(attrs, (list, tuple)) else attrs

        from ...nn.container import ParameterList

        self.ln_scales, self.ln_biases = ParameterList(), ParameterList()
        self.qkv_weights, self.qkv_biases = ParameterList(), ParameterList()
        self.linear_weights, self.linear_biases = ParameterList(), ParameterList()
        self.ffn_ln_scales, self.ffn_ln_biases = ParameterList(), ParameterList()
        self.ffn1_weights, self.ffn1_biases = ParameterList(), ParameterList()
        self.ffn2_weights, self.ffn2_biases = ParameterList(), ParameterList()
        for i in range(num_layers):
            self.ln_scales.append(self.create_parameter(
                [embed_dim], attr=attr_i(ln_scale_attrs, i),
                default_initializer=I.Constant(1.0)))
            self.ln_biases.append(self.create_parameter(
                [embed_dim], attr=attr_i(ln_bias_attrs, i), is_bias=True))
            self.qkv_weights.append(self.create_parameter(
                [3, num_heads, self.head_dim, embed_dim],
                attr=attr_i(qkv_weight_attrs, i)))
            self.qkv_biases.append(self.create_parameter(
                [3, num_heads, self.head_dim], attr=attr_i(qkv_bias_attrs, i),
                is_bias=True))
            self.linear_weights.append(self.create_parameter(
                [embed_dim, embed_dim], attr=attr_i(linear_weight_attrs, i)))
            self.linear_biases.append(self.create_parameter(
                [embed_dim], attr=attr_i(linear_bias_attrs, i), is_bias=True))
            self.ffn_ln_scales.append(self.create_parameter(
                [embed_dim], attr=attr_i(ffn_ln_scale_attrs, i),
                default_initializer=I.Constant(1.0)))
            self.ffn_ln_biases.append(self.create_parameter(
                [embed_dim], attr=attr_i(ffn_ln_bias_attrs, i), is_bias=True))
            self.ffn1_weights.append(self.create_parameter(
                [embed_dim, dim_feedforward], attr=attr_i(ffn1_weight_attrs, i)))
            self.ffn1_biases.append(self.create_parameter(
                [dim_feedforward], attr=attr_i(ffn1_bias_attrs, i),
                is_bias=True))
            self.ffn2_weights.append(self.create_parameter(
                [dim_feedforward, embed_dim], attr=attr_i(ffn2_weight_attrs, i)))
            self.ffn2_biases.append(self.create_parameter(
                [embed_dim], attr=attr_i(ffn2_bias_attrs, i), is_bias=True))

    def gen_cache(self, batch_size, max_length):
        """Preallocate dense KV caches: [2, b, nh, max_len, hd] per layer."""
        caches = []
        for _ in range(self.num_layers):
            caches.append(Tensor(jnp.zeros(
                (2, batch_size, self.num_heads, max_length, self.head_dim),
                dtype=jnp.float32)))
        return caches

    def _layer(self, i, x, attn_mask, cache_kv, time_step):
        residual = x
        out = F.layer_norm(x, [self.embed_dim], self.ln_scales[i],
                           self.ln_biases[i], self._epsilon)
        b, s = out.shape[0], out.shape[1]

        def qkv_fn(a, w, bias):
            return jnp.einsum("bsd,thkd->btshk", a, w) + bias[:, None, None]

        qkv = _apply_op(qkv_fn, out, self.qkv_weights[i], self.qkv_biases[i],
                        _name="qkv")
        from ...ops.manipulation import unbind

        q, k, v = unbind(qkv, axis=1)  # [b, s, nh, hd]
        if cache_kv is not None:
            # decode: write new k/v at time_step, attend over cache
            def upd(c, kk, vv):
                kk = jnp.swapaxes(kk, 1, 2)  # b nh s hd
                vv = jnp.swapaxes(vv, 1, 2)
                c = jax.lax.dynamic_update_slice_in_dim(
                    c, jnp.stack([kk, vv], axis=0), int(time_step), axis=3)
                return c

            new_cache = _apply_op(upd, cache_kv, k, v, _name="kv_update")
            kc = new_cache[0]  # b nh max hd
            vc = new_cache[1]

            def attend(qq, kk, vv):
                qq = jnp.swapaxes(qq, 1, 2)  # b nh s hd
                logits = jnp.einsum("bhqd,bhkd->bhqk", qq, kk) / math.sqrt(
                    self.head_dim)
                klen = kk.shape[2]
                mask = jnp.arange(klen)[None, None, None, :] <= (
                    int(time_step) + jnp.arange(qq.shape[2])[None, None, :, None]
                )
                logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
                p = jax.nn.softmax(logits, axis=-1)
                o = jnp.einsum("bhqk,bhkd->bhqd", p, vv)
                return jnp.swapaxes(o, 1, 2)

            attn_out = _apply_op(attend, q, kc, vc, _name="cached_attn")
        else:
            new_cache = None
            attn_out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=attn_mask is None,
                training=self.training)
        from ...ops.manipulation import reshape

        attn_out = reshape(attn_out, [b, s, self.embed_dim])
        attn_out = F.linear(attn_out, self.linear_weights[i],
                            self.linear_biases[i])
        x = residual + attn_out
        residual = x
        out = F.layer_norm(x, [self.embed_dim], self.ffn_ln_scales[i],
                           self.ffn_ln_biases[i], self._epsilon)
        out = F.linear(out, self.ffn1_weights[i], self.ffn1_biases[i])
        out = getattr(F, self.activation)(out)
        out = F.linear(out, self.ffn2_weights[i], self.ffn2_biases[i])
        x = residual + out
        return x, new_cache

    def forward(self, src, attn_mask=None, caches=None, pre_caches=None,
                rotary_embs=None, rotary_emb_dims=0, seq_lens=None,
                time_step=None):
        x = src
        new_caches = []
        for i in range(self.num_layers):
            cache_i = caches[i] if caches is not None else None
            x, new_cache = self._layer(i, x, attn_mask, cache_i,
                                       time_step if time_step is not None else 0)
            if new_cache is not None:
                new_caches.append(new_cache)
        if caches is not None:
            return x, new_caches
        return x


class FusedBiasDropoutResidualLayerNorm(Layer):
    """paddle.incubate.nn.FusedBiasDropoutResidualLayerNorm parity:
    LayerNorm(residual + dropout(x + bias)) as one fused expression."""

    def __init__(self, embed_dim, dropout_rate=0.5, weight_attr=None,
                 bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.dropout_rate = dropout_rate
        self.epsilon = epsilon
        self.linear_bias = self.create_parameter(
            [embed_dim], is_bias=True,
            default_initializer=I.Constant(0.0))
        self.ln_scale = self.create_parameter(
            [embed_dim], default_initializer=I.Constant(1.0))
        self.ln_bias = self.create_parameter(
            [embed_dim], is_bias=True, default_initializer=I.Constant(0.0))

    def forward(self, x, residual):
        from .functional import fused_bias_dropout_residual_layer_norm

        return fused_bias_dropout_residual_layer_norm(
            x, residual, self.linear_bias, self.ln_scale, self.ln_bias,
            self.dropout_rate, self.epsilon, training=self.training)
