"""Attention functionals.

Reference parity: the flash-attn glue (paddle/phi/kernels/gpu/flash_attn_*,
SURVEY.md §2.1 "Phi fusion kernels") and
`paddle.nn.functional.scaled_dot_product_attention`. On TPU the fused path is
a Pallas flash-attention kernel (paddle_tpu.kernels.flash_attention), taken
where its `use_flash` says so and FLAGS_use_pallas_kernels allows; otherwise
one fused XLA softmax(QK^T)V.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import config as _config
from ...tensor import Tensor, _apply_op, as_array


def _sdpa_reference(q, k, v, mask=None, dropout_p=0.0, causal=False, scale=None,
                    key=None):
    """q/k/v: [batch, seq, heads, head_dim] (paddle layout)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # -> [b, h, s, d]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((ql, kl), dtype=bool), k=kl - ql)
        logits = jnp.where(cmask, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """paddle layout: [batch, seq, num_heads, head_dim]."""
    rng_key = None
    if dropout_p > 0.0 and training:
        from ...framework import random as _random

        rng_key = _random.next_key()

    eff_dropout = dropout_p if training else 0.0
    if attn_mask is None and _config.get_flag("FLAGS_use_pallas_kernels",
                                              True):
        from ...kernels import flash_attention as fa

        qa = as_array(query)
        if fa.use_flash(qa.shape[1], as_array(key).shape[1], qa.shape[3],
                        eff_dropout, qa.dtype):
            def f(q, k, v):
                if eff_dropout > 0.0:
                    # in-kernel threefry dropout; a fresh per-step
                    # int32 seed derived from the framework RNG
                    seed = jax.random.randint(
                        rng_key, (), 0, np.iinfo(np.int32).max,
                        dtype=jnp.int32)
                    return fa.flash_attention_bshd(
                        q, k, v, causal=is_causal,
                        dropout=eff_dropout, dropout_seed=seed)
                return fa.flash_attention_bshd(q, k, v, causal=is_causal)

            return _apply_op(f, query, key, value,
                             _name="flash_attention")

    if attn_mask is not None:

        def f(q, k, v, m):
            return _sdpa_reference(q, k, v, mask=m, dropout_p=eff_dropout,
                                   causal=is_causal, key=rng_key)

        return _apply_op(f, query, key, value, attn_mask, _name="sdpa")

    def f(q, k, v):
        return _sdpa_reference(q, k, v, dropout_p=eff_dropout,
                               causal=is_causal, key=rng_key)

    return _apply_op(f, query, key, value, _name="sdpa")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(query, key, value, dropout_p=dropout,
                                       is_causal=causal, training=training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """paddle.nn.functional.flash_attention.flash_attn_unpadded parity:
    varlen attention over packed [total_tokens, heads, head_dim] tensors
    via the segment-masked Pallas kernel."""
    from ...kernels import flash_attention as fa

    eff_dropout = dropout if training else 0.0
    rng_key = None
    if eff_dropout > 0.0:
        from ...framework import random as _random

        rng_key = _random.next_key()

    d = as_array(query).shape[-1]
    if d % 128 == 0:
        def f(q, k, v, cq, ck):
            seed = None
            if eff_dropout > 0.0:
                seed = jax.random.randint(rng_key, (), 0,
                                          np.iinfo(np.int32).max,
                                          dtype=jnp.int32)
            out, _ = fa.flash_attn_unpadded(
                q, k, v, cq, ck, max_seqlen_q, max_seqlen_k, scale=scale,
                dropout=eff_dropout, causal=causal, dropout_seed=seed)
            return out

        out = _apply_op(f, query, key, value, cu_seqlens_q, cu_seqlens_k,
                        _name="flash_attn_unpadded")
        return out, None

    # head_dim not MXU-tile aligned (e.g. 64): XLA segment-masked dense
    # fallback — same packed CONTRACT as the fused kernel, whose own
    # checks don't run on this path
    if dropout and training:
        raise NotImplementedError(
            "flash_attn_unpadded: dropout unsupported")
    if causal:
        import numpy as _np

        cq_ = as_array(cu_seqlens_q)
        ck_ = as_array(cu_seqlens_k)
        try:
            if cq_.shape != ck_.shape or bool(
                    _np.any(_np.asarray(cq_) != _np.asarray(ck_))):
                raise ValueError(
                    "flash_attn_unpadded(causal=True) needs cu_seqlens_q "
                    "== cu_seqlens_k (per-sequence causal alignment)")
        except jax.errors.TracerArrayConversionError:
            pass

    def f_ref(q, k, v, cq, ck):
        import math as _math

        total_q = q.shape[0]
        total_k = k.shape[0]
        seg_q = jnp.searchsorted(cq[1:], jnp.arange(total_q),
                                 side="right")
        seg_k = jnp.searchsorted(ck[1:], jnp.arange(total_k),
                                 side="right")
        s_ = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32)
        s_ = s_ * (scale if scale is not None else 1.0 / _math.sqrt(
            q.shape[-1]))
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            mask = mask & (jnp.arange(total_q)[:, None]
                           >= jnp.arange(total_k)[None, :])
        s_ = jnp.where(mask[None], s_, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s_, axis=-1)
        p = jnp.where(mask[None], p, 0.0).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = _apply_op(f_ref, query, key, value, cu_seqlens_q, cu_seqlens_k,
                    _name="flash_attn_unpadded_ref")
    return out, None
