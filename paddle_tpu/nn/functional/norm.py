"""Normalization functionals (python/paddle/nn/functional/norm.py parity):
batch_norm, layer_norm, instance_norm, group_norm, local_response_norm,
normalize, rms_norm (TPU-native addition, Pallas-backed when available)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...tensor import Tensor, _apply_op, as_array


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def f(a):
        n = jnp.power(jnp.sum(jnp.power(jnp.abs(a), p), axis=axis, keepdims=True),
                      1.0 / p)
        return a / jnp.maximum(n, epsilon)

    return _apply_op(f, x, _name="normalize")


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05, data_format="NCHW",
               use_global_stats=None, name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    use_batch_stats = training and not use_global_stats

    a = as_array(x)
    ch_axis = a.ndim - 1 if channel_last else (1 if a.ndim > 1 else 0)
    reduce_axes = tuple(i for i in range(a.ndim) if i != ch_axis)
    bshape = [1] * a.ndim
    bshape[ch_axis] = -1

    if use_batch_stats:
        # update running stats (stateful; eager + functionalized under jit via
        # buffer rebinding). The batch mean/var are intentionally recomputed
        # INSIDE the vjp'd op below: the gradient must flow through them.
        # Under jit both computations live in one program and XLA CSE merges
        # them; only eager debug mode pays the duplicate reduction.
        mean_new = jnp.mean(a, axis=reduce_axes)
        var_new = jnp.var(a, axis=reduce_axes)
        if running_mean is not None:
            running_mean._rebind(
                momentum * as_array(running_mean) + (1 - momentum) * mean_new
            )
        if running_var is not None:
            n = a.size // a.shape[ch_axis]
            unbiased = var_new * n / max(n - 1, 1)
            running_var._rebind(
                momentum * as_array(running_var) + (1 - momentum) * unbiased
            )

        def f(arr, *wb):
            m = jnp.mean(arr, axis=reduce_axes, keepdims=True)
            v = jnp.var(arr, axis=reduce_axes, keepdims=True)
            out = (arr - m) * jax.lax.rsqrt(v + epsilon)
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(bshape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(bshape)
            return out

        args = [t for t in (weight, bias) if t is not None]
        return _apply_op(f, x, *args, _name="batch_norm")

    def f(arr, m, v, *wb):
        out = (arr - m.reshape(bshape)) * jax.lax.rsqrt(v.reshape(bshape) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [t for t in (weight, bias) if t is not None]
    return _apply_op(f, x, running_mean, running_var, *args, _name="batch_norm")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None):
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = [int(normalized_shape)]
    nd = len(tuple(normalized_shape))

    def f(a, *wb):
        axes = tuple(range(a.ndim - nd, a.ndim))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [t for t in (weight, bias) if t is not None]
    return _apply_op(f, x, *args, _name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm — the reference ships this as a Phi fusion kernel
    (paddle/phi/kernels/fusion rms_norm — SURVEY.md §2.1). Pallas fused
    kernel when shapes allow (FLAGS_use_pallas_kernels), fused XLA
    expression otherwise."""
    from ...framework import config as _config

    if weight is not None and _config.get_flag("FLAGS_use_pallas_kernels",
                                               True):
        from ...kernels import rms_norm as _krms

        a = as_array(x)
        if _krms.supports(int(np.prod(a.shape[:-1])), a.shape[-1],
                          itemsize=jnp.dtype(a.dtype).itemsize):
            def fk(a_, w_):
                return _krms.rms_norm(a_, w_, epsilon)

            return _apply_op(fk, x, weight, _name="rms_norm")

    def f(a, *w):
        var = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=-1, keepdims=True)
        out = a.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)
        if w:
            out = out * w[0].astype(jnp.float32)
        # output dtype follows x, matching the Pallas kernel's contract
        return out.astype(a.dtype)

    args = [weight] if weight is not None else []
    return _apply_op(f, x, *args, _name="rms_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    def f(a, *wb):
        ch_axis = a.ndim - 1 if channel_last else 1
        axes = tuple(i for i in range(2, a.ndim)) if not channel_last else tuple(
            i for i in range(1, a.ndim - 1))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + eps)
        bshape = [1] * a.ndim
        bshape[ch_axis] = -1
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [t for t in (weight, bias) if t is not None]
    return _apply_op(f, x, *args, _name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")

    def f(a, *wb):
        if channel_last:
            a_t = jnp.moveaxis(a, -1, 1)
        else:
            a_t = a
        n, c = a_t.shape[0], a_t.shape[1]
        spatial = a_t.shape[2:]
        g = a_t.reshape((n, num_groups, c // num_groups) + spatial)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) * jax.lax.rsqrt(v + epsilon)).reshape(a_t.shape)
        bshape = [1, -1] + [1] * len(spatial)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [t for t in (weight, bias) if t is not None]
    return _apply_op(f, x, *args, _name="group_norm")


def local_response_norm(x, size, alpha=0.0001, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def f(a):
        sq = jnp.square(a)
        ch_axis = 1 if data_format.startswith("NC") else a.ndim - 1
        c = a.shape[ch_axis]
        half = size // 2
        moved = jnp.moveaxis(sq, ch_axis, 0)
        padded = jnp.pad(moved, [(half, size - 1 - half)] + [(0, 0)] * (a.ndim - 1))
        acc = jnp.zeros_like(moved)
        for i in range(size):
            acc = acc + jax.lax.dynamic_slice_in_dim(padded, i, c, axis=0)
        acc = jnp.moveaxis(acc, 0, ch_axis)
        return a / jnp.power(k + alpha * acc / size, beta)

    return _apply_op(f, x, _name="local_response_norm")
