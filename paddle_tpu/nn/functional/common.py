"""Common functionals (python/paddle/nn/functional/common.py + input.py
parity): linear, dropout, embedding, interpolate, cosine_similarity,
pixel_shuffle, unfold, label_smooth."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import random as _random
from ...tensor import Tensor, _apply_op, as_array


def linear(x, weight, bias=None, name=None):
    # paddle weight layout: [in_features, out_features]
    if bias is not None:
        return _apply_op(
            lambda a, w, b: jnp.matmul(a, w) + b, x, weight, bias,
            _name="linear")
    return _apply_op(jnp.matmul, x, weight, _name="linear")


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return _apply_op(lambda a: a * (1.0 - p), x, _name="dropout_infer")
        from ...ops import math as _math

        return _math._identity(x)
    key = _random.next_key()

    def f(a):
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            for i in range(len(shape)):
                if i not in [ax % len(shape) for ax in axes]:
                    shape[i] = 1
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        keep = jnp.broadcast_to(keep, a.shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), jnp.zeros_like(a))
        return jnp.where(keep, a, jnp.zeros_like(a))

    return _apply_op(f, x, _name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def _alpha_dropout_impl(x, p, training, mask_shape, name):
    """SELU-preserving dropout core: dropped positions go to alpha' with
    an affine correction keeping zero mean / unit variance. `mask_shape`
    maps the input shape to the bernoulli mask shape (full shape for
    per-element, [N, C, 1...] for per-feature-map)."""
    if not training or p == 0.0:
        from ...ops import math as _math

        return _math._identity(x)
    key = _random.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(a):
        keep = jax.random.bernoulli(key, 1.0 - p, mask_shape(a.shape))
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return a_coef * jnp.where(keep, a, alpha_p) + b_coef

    return _apply_op(f, x, _name=name)


def alpha_dropout(x, p=0.5, training=True, name=None):
    return _alpha_dropout_impl(x, p, training, lambda s: s,
                               "alpha_dropout")


def feature_alpha_dropout(x, p=0.5, training=True, name=None):
    """Alpha dropout that drops whole feature maps: the keep/drop decision
    is shared across every spatial position of a [N, C, ...] channel
    (reference: paddle.nn.FeatureAlphaDropout), preserving SELU
    self-normalizing statistics like `alpha_dropout`."""
    return _alpha_dropout_impl(
        x, p, training, lambda s: s[:2] + (1,) * (len(s) - 2),
        "feature_alpha_dropout")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    def f(idx_unused, w):
        # indices are non-diff; close over them as static values via the
        # first arg (int tensor -> float0 grad, skipped by the tape)
        out = jnp.take(w, idx_unused.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (idx_unused == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros_like(out), out)
        return out

    return _apply_op(f, x, weight, _name="embedding")


def one_hot(x, num_classes, name=None):
    from ...ops.creation import one_hot as _oh

    return _oh(x, num_classes)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    def f(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)

    return _apply_op(f, x1, x2, _name="cosine_similarity")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    def f(a, b):
        d = a - b + epsilon
        return jnp.power(jnp.sum(jnp.power(jnp.abs(d), p), axis=-1,
                                 keepdims=keepdim), 1.0 / p)

    return _apply_op(f, x, y, _name="pairwise_distance")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(l, *pd):
        k = l.shape[-1]
        if pd:
            return (1 - epsilon) * l + epsilon * pd[0]
        return (1 - epsilon) * l + epsilon / k

    args = [prior_dist] if prior_dist is not None else []
    return _apply_op(f, label, *args, _name="label_smooth")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    a = as_array(x)
    channel_last = data_format in ("NHWC", "NWC", "NDHWC", "NLC")
    spatial_ndim = a.ndim - 2
    if channel_last:
        in_spatial = a.shape[1:-1]
    else:
        in_spatial = a.shape[2:]
    if size is not None:
        if isinstance(size, Tensor):
            size = size.tolist()
        out_spatial = tuple(int(s) for s in (size if isinstance(size, (list, tuple))
                                             else [size]))
    else:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * spatial_ndim
        out_spatial = tuple(
            int(np.floor(s * f)) for s, f in zip(in_spatial, scale_factor)
        )

    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def f(arr):
        if channel_last:
            out_shape = (arr.shape[0],) + out_spatial + (arr.shape[-1],)
            sp_axes = tuple(range(1, arr.ndim - 1))
        else:
            out_shape = arr.shape[:2] + out_spatial
            sp_axes = tuple(range(2, arr.ndim))
        if jmode == "nearest":
            idxs = []
            for ax, (i_s, o_s) in enumerate(zip(in_spatial, out_spatial)):
                idx = jnp.floor(jnp.arange(o_s) * (i_s / o_s)).astype(jnp.int32)
                idxs.append(idx)
            out = arr
            for ax, idx in zip(sp_axes, idxs):
                out = jnp.take(out, idx, axis=ax)
            return out
        return jax.image.resize(arr, out_shape, method=jmode)

    return _apply_op(f, x, _name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = int(upscale_factor)

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            out = a.reshape(n, c // (r * r), r, r, h, w)
            out = out.transpose(0, 1, 4, 2, 5, 3)
            return out.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = a.shape
        out = a.reshape(n, h, w, r, r, c // (r * r))
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h * r, w * r, c // (r * r))

    return _apply_op(f, x, _name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = int(downscale_factor)

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            out = a.reshape(n, c, h // r, r, w // r, r)
            out = out.transpose(0, 1, 3, 5, 2, 4)
            return out.reshape(n, c * r * r, h // r, w // r)
        raise NotImplementedError

    return _apply_op(f, x, _name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            out = a.reshape(n, groups, c // groups, h, w)
            out = out.transpose(0, 2, 1, 3, 4)
            return out.reshape(n, c, h, w)
        n, h, w, c = a.shape
        out = a.reshape(n, h, w, groups, c // groups)
        out = out.transpose(0, 1, 2, 4, 3)
        return out.reshape(n, h, w, c)

    return _apply_op(f, x, _name="channel_shuffle")


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    if isinstance(paddings, int):
        pt = pb = pl = pr = paddings
    elif len(paddings) == 2:
        pt = pb = paddings[0]
        pl = pr = paddings[1]
    else:
        pt, pl, pb, pr = paddings

    def f(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, [(0, 0), (0, 0), (pt, pb), (pl, pr)])
        hp, wp = a.shape[2], a.shape[3]
        oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
        ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
        patches = []
        for i in range(kh):
            for j in range(kw):
                seg = a[:, :, i * dh: i * dh + sh * (oh - 1) + 1: sh,
                        j * dw: j * dw + sw * (ow - 1) + 1: sw]
                patches.append(seg)
        out = jnp.stack(patches, axis=2)  # n, c, kh*kw, oh, ow
        return out.reshape(n, c * kh * kw, oh * ow)

    return _apply_op(f, x, _name="unfold")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """Inverse of unfold: sum sliding-window patches `[N, C*kh*kw, L]`
    back into images `[N, C, H, W]` (overlaps accumulate). Reference
    paddle.nn.functional.fold (SURVEY.md §2.2 nn functional tail); built
    as strided scatter-adds — the exact transpose of unfold's strided
    slices, so fold(unfold(x)) equals x times the window multiplicity."""
    oh_out, ow_out = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    if isinstance(paddings, int):
        pt = pb = pl = pr = paddings
    elif len(paddings) == 2:
        pt = pb = paddings[0]
        pl = pr = paddings[1]
    else:
        pt, pl, pb, pr = paddings

    def f(a):
        n, ckk, length = a.shape
        c = ckk // (kh * kw)
        hp, wp = oh_out + pt + pb, ow_out + pl + pr
        oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
        ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
        if oh * ow != length:
            raise ValueError(
                f"fold: input holds {length} blocks but output_sizes/"
                f"kernel/stride/padding/dilation imply {oh}x{ow}={oh * ow}")
        patches = a.reshape(n, c, kh * kw, oh, ow)
        out = jnp.zeros((n, c, hp, wp), a.dtype)
        idx = 0
        for i in range(kh):
            for j in range(kw):
                out = out.at[:, :, i * dh: i * dh + sh * (oh - 1) + 1: sh,
                             j * dw: j * dw + sw * (ow - 1) + 1: sw].add(
                    patches[:, :, idx])
                idx += 1
        return out[:, :, pt:pt + oh_out, pl:pl + ow_out]

    return _apply_op(f, x, _name="fold")


def bilinear(x1, x2, weight, bias=None, name=None):
    def f(a, b, w, *bb):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bb:
            out = out + bb[0]
        return out

    args = [bias] if bias is not None else []
    return _apply_op(f, x1, x2, weight, *args, _name="bilinear")


def zeropad2d(x, padding, data_format="NCHW", name=None):
    from ...ops.manipulation import pad as _pad

    return _pad(x, padding, mode="constant", value=0.0, data_format=data_format)
