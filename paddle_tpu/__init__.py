"""paddle_tpu: a TPU-native deep-learning framework with the PaddlePaddle
API surface (usage: ``import paddle_tpu as paddle``).

Built per SURVEY.md: tensors over jax.Array, tape autograd for eager,
jax.jit for the performance path, one jax.sharding.Mesh for the Fleet
distributed stack, Pallas for fused kernels.
"""
from __future__ import annotations

__version__ = "0.1.0"

import jax as _jax

# paddle dtype semantics: integer tensors are int64 by default. jax's
# x64-disabled mode silently demotes them to int32, so enable x64 and keep
# the FLOAT default at float32 ourselves (Tensor/as_array cast f64 -> default
# dtype unless the user explicitly asks for float64).
_jax.config.update("jax_enable_x64", True)

# persistent compile cache: JAX_COMPILATION_CACHE_DIR when set, else one
# fixed directory in the checkout — before anything compiles
from .framework import compile_cache as _compile_cache

_compile_cache.configure()

# --- framework core ---
from .framework import config as _config
from .framework import device as _device_mod
from .framework import dtype as _dtype_mod
from .framework import random as _random_mod
from .framework.config import (
    get_default_dtype,
    get_flags,
    set_default_dtype,
    set_flags,
)
from .framework.device import (
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_xpu,
    is_compiled_with_rocm,
    is_compiled_with_custom_device,
    get_cudnn_version,
    is_compiled_with_distribute,
    is_compiled_with_tpu,
    set_device,
)
from .framework.dtype import (  # noqa: F401
    DType,
    bfloat16,
    bool_ as bool,  # noqa: A001  (paddle exports paddle.bool)
    complex64,
    complex128,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from .framework.random import get_rng_state, seed, set_rng_state

# --- tensor + autograd ---
from .tensor import Parameter, Tensor, to_tensor
from .autograd.tape import (
    enable_grad,
    grad,
    is_grad_enabled,
    no_grad,
    set_grad_enabled,
)

# --- ops: re-export everything at top level (paddle.* op surface) ---
from . import ops as _ops
from .ops.activation import *  # noqa: F401,F403
from .ops.creation import (  # noqa: F401
    arange,
    assign,
    clone,
    complex,  # noqa: A001
    diag,
    diag_embed,
    diagflat,
    empty,
    empty_like,
    eye,
    full,
    full_like,
    linspace,
    logspace,
    meshgrid,
    one_hot,
    ones,
    ones_like,
    polar,
    tril,
    tril_indices,
    triu,
    triu_indices,
    vander,
    zeros,
    zeros_like,
)
from .ops.math import *  # noqa: F401,F403
from .ops.reduction import *  # noqa: F401,F403
from .ops.manipulation import *  # noqa: F401,F403
from .ops.logic import *  # noqa: F401,F403
from .ops.search import *  # noqa: F401,F403
from .ops.linalg import (  # noqa: F401
    bincount,
    bmm,
    cdist,
    corrcoef,
    cov,
    cross,
    dist,
    dot,
    einsum,
    histogram,
    histogram_bin_edges,
    histogramdd,
    lu,
    lu_unpack,
    matmul,
    matrix_transpose,
    mm,
    multi_dot,
    mv,
    norm,
    pdist,
    tensordot,
    vecdot,
)
from .ops.inplace import *  # noqa: F401,F403 — the paddle `op_` family
from .ops.random_ops import (  # noqa: F401
    bernoulli,
    binomial,
    geometric_,
    multinomial,
    normal,
    poisson,
    rand,
    randint,
    randint_like,
    randn,
    randperm,
    standard_gamma,
    standard_normal,
    uniform,
)

def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """paddle.set_printoptions parity (numpy-backed printing)."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not bool(sci_mode)
    _np.set_printoptions(**kw)


class LazyGuard:
    """paddle.LazyGuard API parity. The reference defers parameter
    materialization until first forward (a host-memory optimization for
    giant CPU-side inits); here parameters are jax arrays initialized
    directly on the accelerator, so eager init is already cheap and the
    guard is a documented no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# --- subsystems ---
from . import autograd  # noqa: F401
from . import amp  # noqa: F401
from . import device  # noqa: F401
from . import distributed  # noqa: F401
from . import framework  # noqa: F401
from . import hapi  # noqa: F401
from .hapi import callbacks  # noqa: F401 — paddle.callbacks namespace
from . import incubate  # noqa: F401
from . import audio  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import inference  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import linalg  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import observability  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import quantization  # noqa: F401
from . import signal  # noqa: F401
from . import sparse  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import utils  # noqa: F401
from . import vision  # noqa: F401

from .framework.io import load, save  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401


def is_tensor(x):
    return isinstance(x, Tensor)


def numel(x, name=None):
    return to_tensor(x.size, dtype="int64")


def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state):
    set_rng_state(state)


def in_dynamic_mode():
    from .jit import api as _jit_api

    return not _jit_api.in_to_static_trace()


def disable_static(place=None):
    pass


def enable_static():
    raise NotImplementedError(
        "paddle_tpu runs eager + jit (to_static); legacy static graph mode is "
        "covered by paddle_tpu.static's Program/Executor shim over jax.jit"
    )


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary

    return _summary(net, input_size, dtypes, input)


def flops(net, input_size, custom_ops=None, print_detail=False):
    """paddle.flops parity: forward-pass FLOPs of `net` at `input_size`.

    TPU-native counting: instead of the reference's per-layer-type hook
    table (python/paddle/hapi/dynamic_flops.py), the forward is lowered
    through XLA and the COMPILED program's cost analysis is read — every
    op (fused or not) is counted by the compiler itself, so custom layers
    need no registration (custom_ops is accepted for API compatibility).
    """
    import jax as _j
    import jax.numpy as _jnp

    from .autograd import tape as _tape
    from .jit.api import _LayerScope
    from .tensor import Tensor as _T

    shapes = input_size
    if isinstance(shapes, (list, tuple)) and shapes and \
            not isinstance(shapes[0], (list, tuple)):
        shapes = [shapes]
    xs = [_jnp.zeros(tuple(int(d) for d in s), _jnp.float32)
          for s in shapes]
    params = net.parameters_pytree()
    buffers = net.buffers_pytree()

    def fwd(p, b, *arrs):
        with _tape.no_grad(), _LayerScope(net, p, b):
            out = net(*[_T(a) for a in arrs])
        # every output leaf is returned: XLA dead-code-eliminates ops that
        # feed no output, which would undercount multi-head models
        # (GoogLeNet/InceptionV3 aux heads)
        return tuple(x._data if hasattr(x, "_data") else x
                     for x in _j.tree_util.tree_leaves(out))

    compiled = _j.jit(fwd).lower(params, buffers, *xs).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0] if cost else {}
    total = int(cost.get("flops", 0) or 0)
    if print_detail:
        print(f"Total FLOPs: {total:,}  "
              f"(XLA cost analysis; bytes accessed: "
              f"{int(cost.get('bytes accessed', 0) or 0):,})")
    return total


def device_count():
    return _device_mod.device_count()


def version():
    return __version__


def finfo(dtype):
    """paddle.finfo parity: float type limits (min/max/eps/bits/dtype)."""
    import numpy as _np

    nd = _dtype_mod.to_np_dtype(dtype)
    try:
        info = _np.finfo(nd)
    except ValueError:  # bfloat16 etc. — numpy defers to ml_dtypes
        import ml_dtypes

        info = ml_dtypes.finfo(nd)

    class _FInfo:
        min = float(info.min)
        max = float(info.max)
        eps = float(info.eps)
        tiny = float(getattr(info, "tiny", getattr(info, "smallest_normal",
                                                   0.0)))
        smallest_normal = float(getattr(info, "smallest_normal",
                                        getattr(info, "tiny", 0.0)))
        resolution = float(getattr(info, "resolution", 0.0))
        bits = int(info.bits)

    _FInfo.dtype = str(_dtype_mod.from_np_dtype(nd).name)
    return _FInfo()


def iinfo(dtype):
    """paddle.iinfo parity: integer type limits."""
    import numpy as _np

    info = _np.iinfo(_dtype_mod.to_np_dtype(dtype))

    class _IInfo:
        min = int(info.min)
        max = int(info.max)
        bits = int(info.bits)

    _IInfo.dtype = str(_dtype_mod.from_np_dtype(
        _dtype_mod.to_np_dtype(dtype)).name)
    return _IInfo()
