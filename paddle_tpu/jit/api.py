"""@to_static: the dygraph-to-compiled bridge.

Reference parity: python/paddle/jit (ProgramTranslator, @to_static,
jit.save/load — SURVEY.md §2.2 "JIT / dy2static"). TPU-native design
(SURVEY.md §7 phase 4): instead of AST-rewriting Python into a ProgramDesc,
the Layer/function is *functionalized* — parameters and buffers are swapped
for jit tracers, the unmodified Python forward runs once under jax tracing,
and XLA compiles the whole step. Python control flow unrolls at trace time
(like the reference's static unrolling); data-dependent control flow uses
lax.cond/scan, the same contract as the reference's cond/while_loop ops.

Key properties:
- program cache ≡ jax.jit's (shape, dtype)-keyed executable cache
  (the reference's InterpreterCore cache — SURVEY.md §3.3);
- RNG: each call draws a fresh seed from the eager KeyStream and threads it
  in as an argument, so dropout differs per step without recompilation while
  staying reproducible from paddle.seed (SURVEY.md §7 hard part #4);
- mutable state (BN running stats): buffers are traced as inputs and their
  post-forward values returned as outputs, then rebound — eager and jit
  stay semantically identical (hard part #1);
- training: `train_step()` fuses forward+loss+backward+optimizer update into
  one jitted program with donated params/opt-state (SURVEY.md §3.1
  "TPU lesson").
"""
from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as _random
from ..nn.layer_base import Layer
from ..observability import compilewatch as _cw
from ..observability.tracing import scope as _scope
from ..tensor import Tensor, as_array

_tls = threading.local()
# every build of a program made here is marked `jit.build` on the phases'
# timeline, flag or no flag
_cw.ensure_listener()


def in_to_static_trace() -> bool:
    return getattr(_tls, "tracing", False)


# ---------------------------------------------------------------------------
# (args, kwargs) <-> (array leaves, hashable structure)
# ---------------------------------------------------------------------------


def _encode(obj, leaves):
    if isinstance(obj, Tensor):
        leaves.append(obj._data)
        return ("__leaf__", len(leaves) - 1)
    if isinstance(obj, (jax.Array, np.ndarray)):
        leaves.append(jnp.asarray(obj))
        return ("__leaf__", len(leaves) - 1)
    if isinstance(obj, list):
        return ("__list__", tuple(_encode(o, leaves) for o in obj))
    if isinstance(obj, tuple):
        return ("__tuple__", tuple(_encode(o, leaves) for o in obj))
    if isinstance(obj, dict):
        return (
            "__dict__",
            tuple(sorted((k, _encode(v, leaves)) for k, v in obj.items())),
        )
    return ("__const__", obj)


def _decode(node, leaves, wrap):
    tag, payload = node
    if tag == "__leaf__":
        arr = leaves[payload]
        return Tensor(arr) if wrap else arr
    if tag == "__list__":
        return [_decode(o, leaves, wrap) for o in payload]
    if tag == "__tuple__":
        return tuple(_decode(o, leaves, wrap) for o in payload)
    if tag == "__dict__":
        return {k: _decode(v, leaves, wrap) for k, v in payload}
    return payload


def flatten_call(args, kwargs):
    leaves: list = []
    structure = _encode((tuple(args), dict(kwargs)), leaves)
    return leaves, structure


def flatten_call_tensors(args, kwargs):
    """Like flatten_call but leaves keep their Tensor identity (the
    run_program tape path needs them differentiable)."""
    leaves: list = []
    structure = _encode((tuple(args), dict(kwargs)), leaves)
    # re-walk: _encode stored obj._data for Tensors; recover the Tensors
    tensor_leaves: list = []

    def walk(obj):
        if isinstance(obj, Tensor):
            tensor_leaves.append(obj)
        elif isinstance(obj, (jax.Array, np.ndarray)):
            tensor_leaves.append(jnp.asarray(obj))
        elif isinstance(obj, (list, tuple)):
            for o in obj:
                walk(o)
        elif isinstance(obj, dict):
            for k in sorted(obj):
                walk(obj[k])

    walk((tuple(args), dict(kwargs)))
    return tensor_leaves, structure


def unflatten_call(leaves, structure, wrap=True):
    args, kwargs = _decode(structure, leaves, wrap)
    return args, kwargs


def flatten_out(out):
    leaves: list = []
    structure = _encode(out, leaves)
    return leaves, structure


def unflatten_out(leaves, structure, wrap=True):
    return _decode(structure, leaves, wrap)


# ---------------------------------------------------------------------------
# StaticFunction (forward jit)
# ---------------------------------------------------------------------------


class _LayerScope:
    """Swap a layer's param/buffer arrays for traced ones, restoring after."""

    def __init__(self, layer: Optional[Layer], params, buffers):
        self.layer = layer
        self.params = params
        self.buffers = buffers

    def __enter__(self):
        if self.layer is not None:
            self.saved_p = {n: p._data for n, p in self.layer.named_parameters()}
            self.saved_b = {n: b._data for n, b in self.layer.named_buffers()}
            self.layer.load_pytree(self.params)
            self.layer.load_pytree(self.buffers)
        return self

    def new_buffers(self):
        return self.layer.buffers_pytree() if self.layer is not None else {}

    def __exit__(self, *exc):
        if self.layer is not None:
            self.layer.load_pytree(self.saved_p)
            self.layer.load_pytree(self.saved_b)
        return False


_TO_STATIC_ENABLED = True  # paddle.jit.enable_to_static toggle


class StaticFunction:
    """Compiled forward over a Layer or plain function."""

    def __init__(self, fn, layer: Optional[Layer] = None, input_spec=None):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        # compilewatch attribution name: which @to_static program is
        # compiling (README.md "Memory & compile observability")
        owner = f"{type(layer).__name__}." if layer is not None else ""
        self._cw_name = \
            f"to_static.{owner}{getattr(fn, '__name__', 'fn')}"
        # out-tree PER input structure: alternating call signatures hit
        # the jit cache without retracing, so one global field would go
        # stale and decode with the wrong tree
        self._out_structures: Dict[Any, Any] = {}
        self._compiled = None
        self._lock = threading.Lock()

    def _build(self):
        def pure_fn(params, buffers, seed, arg_leaves, structure):
            stream = _random.KeyStream(jax.random.wrap_key_data(seed))
            _tls.tracing = True
            try:
                with _random.with_key_stream(stream), _LayerScope(
                    self._layer, params, buffers
                ) as scope:
                    args, kwargs = unflatten_call(arg_leaves, structure)
                    out = self._fn(*args, **kwargs)
                    new_buffers = scope.new_buffers()
            finally:
                _tls.tracing = False
            out_leaves, out_struct = flatten_out(out)
            self._out_structures[structure] = out_struct
            return out_leaves, new_buffers

        self._compiled = jax.jit(pure_fn, static_argnames=("structure",))

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED:
            # paddle.jit.enable_to_static(False): plain eager execution.
            # _fn is already bound when it came from a Layer (dy2static
            # rebinds via MethodType), so no layer injection here
            return self._fn(*args, **kwargs)
        with self._lock:
            if self._compiled is None:
                self._build()
        layer = self._layer
        params = layer.parameters_pytree() if layer is not None else {}
        buffers = layer.buffers_pytree() if layer is not None else {}
        seed = jax.random.key_data(_random.next_key())
        leaves, structure = flatten_call(args, kwargs)

        from ..autograd import tape as _tape

        param_tensors = []
        if layer is not None and _tape.grad_enabled() \
                and not in_to_static_trace():
            param_tensors = [p for _, p in layer.named_parameters()
                             if not p.stop_gradient]
        if param_tensors:
            # run_program_op parity (reference:
            # paddle/fluid/operators/run_program_op — SURVEY.md §2.1 "JIT
            # runtime"): the WHOLE jitted program is recorded as one op on
            # the eager tape, so loss.backward() after a @to_static
            # forward fills param .grad exactly like the dygraph path —
            # AND input tensors stay differentiable (leaves keep their
            # Tensor identity, so grads flow to upstream eager layers).
            from ..tensor import Tensor, _apply_op

            leaves, structure = flatten_call_tensors(args, kwargs)

            names = [n for n, p in layer.named_parameters()
                     if not p.stop_gradient]
            frozen = {n: p._data for n, p in layer.named_parameters()
                      if p.stop_gradient}
            n_out_holder = {}

            def prog_fn(*arrs):
                p = dict(frozen)
                p.update(dict(zip(names, arrs[:len(names)])))
                arg_leaves = list(arrs[len(names):])
                out_leaves, new_buffers = self._compiled(
                    p, buffers, seed, arg_leaves, structure)
                n_out_holder["n"] = len(out_leaves)
                buf_names = sorted(new_buffers)
                n_out_holder["buf_names"] = buf_names
                outs = tuple(out_leaves) + tuple(
                    new_buffers[b] for b in buf_names)
                # single-output ops take a LEAF cotangent in backward();
                # a 1-tuple would break the vjp structure
                return outs[0] if len(outs) == 1 else outs

            # compile attribution: any backend compile triggered by the
            # program dispatch below bills to this StaticFunction (the
            # structure is the static half of the jit cache key, the
            # leaf shapes the dynamic half)
            with _cw.call(self._cw_name,
                          _cw.signature(leaves, tag=("st", structure))
                          if _cw.enabled() else None):
                results = _apply_op(prog_fn, *param_tensors, *leaves,
                                    _name="run_program")
            if not isinstance(results, tuple):
                results = (results,)
            n_out = n_out_holder["n"]
            out_ts = results[:n_out]
            buf_ts = results[n_out:]
            if buf_ts:
                layer.load_pytree({b: t._data for b, t in zip(
                    n_out_holder["buf_names"], buf_ts)})
            return unflatten_out(list(out_ts),
                                 self._out_structures[structure],
                                 wrap=False)

        with _cw.call(self._cw_name,
                      _cw.signature(leaves, tag=("st", structure))
                      if _cw.enabled() else None):
            out_leaves, new_buffers = self._compiled(
                params, buffers, seed, leaves, structure
            )
        if layer is not None and new_buffers:
            layer.load_pytree(new_buffers)
        return unflatten_out(out_leaves, self._out_structures[structure])

    @property
    def code(self):
        return "<jax-traced program (StableHLO under jit)>"


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """@paddle.jit.to_static parity."""

    def decorate(fn):
        from .dy2static import convert_to_static

        if isinstance(fn, Layer):
            static = StaticFunction(convert_to_static(fn.forward),
                                    layer=fn, input_spec=input_spec)
            fn.forward = static
            return fn
        layer = getattr(fn, "__self__", None)
        if isinstance(layer, Layer):
            return StaticFunction(convert_to_static(fn), layer=layer,
                                  input_spec=input_spec)
        static = StaticFunction(convert_to_static(fn), layer=None,
                                input_spec=input_spec)
        functools.update_wrapper(static, fn)
        return static

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._paddle_not_to_static = True
    return fn


# ---------------------------------------------------------------------------
# train_step: fused fwd+bwd+update
# ---------------------------------------------------------------------------


def _grad_buckets(tree, cap_bytes):
    """Reverse-order, same-dtype, size-capped name buckets over a grad
    pytree — the jitted mirror of distributed.parallel._bucket_grads, so
    eager and compiled training coalesce at the same granularity."""
    buckets, cur, cur_bytes, cur_dtype = [], [], 0, None
    for n in reversed(list(tree)):
        a = tree[n]
        nbytes = int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
        if cur and (a.dtype != cur_dtype or cur_bytes + nbytes > cap_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(n)
        cur_bytes += nbytes
        cur_dtype = a.dtype
    if cur:
        buckets.append(cur)
    return buckets


def train_step(model: Layer, criterion: Callable, optimizer, donate=True,
               model_call: Optional[Callable] = None, sharding_stage=0,
               mesh=None, gradient_merge_steps: int = 1,
               gradient_merge_avg: bool = True):
    """Build a compiled train step: step(inputs, *labels) -> loss.

    `model_call(model, inputs)` defaults to `model(inputs)`;
    `criterion(output, *labels)` computes the scalar loss. Params and
    optimizer state are donated: XLA rewrites weights in place in HBM.

    sharding_stage (reference group_sharded_stage{2,3}, SURVEY.md §2.3):
      0/1 — params+grads replicated over the ZeRO axis (opt-state layout
            is the caller's concern: trainer.shard_opt_state);
      2   — grads constrained to the zero-extended spec inside the step
            (XLA lowers the dp grad reduction to reduce_scatter, and the
            weight update math runs shard-local);
      3   — params are STORED zero-sharded; the forward constrains them
            back to their compute spec (all-gather on use), and updated
            params are constrained to the stored layout again.

    gradient_merge_steps (reference GradientMergeOptimizer /
    strategy.gradient_merge k_steps, SURVEY.md §2.2 meta-optimizers): when
    k > 1, each call accumulates grads into a persistent f32 buffer and
    only every k-th call applies the (avg'd when gradient_merge_avg)
    merged grad — k successive calls on batch B match one step on batch
    k*B. The branch is a jit-compiled lax.cond, so the step stays ONE
    XLA program regardless of k.
    """
    opt_state_holder = {"state": None}
    call = model_call or (lambda m, x: m(x))
    k_merge = max(int(gradient_merge_steps), 1)

    grad_shardings = {}
    stored_shardings = {}
    compute_shardings = {}
    if mesh is not None:
        from ..distributed.fleet.meta_parallel.sharding.sharding_optimizer \
            import stage_shardings
        from ..distributed.sharding_utils import clean_spec, get_param_spec

        # single source of ZeRO-stage layout semantics (grads
        # zero-extended at S2+, params stored zero-sharded at S3 with
        # gather-on-use, pinned to the stored layout between steps)
        compute_shardings, grad_shardings, stored_shardings = \
            stage_shardings(
                {n: (tuple(p.shape),
                     tuple(clean_spec(get_param_spec(p), mesh)))
                 for n, p in model.named_parameters()},
                mesh, sharding_stage)

    def _constrain(tree, shardings):
        if not shardings:
            return tree
        return {n: jax.lax.with_sharding_constraint(a, shardings[n])
                if n in shardings else a for n, a in tree.items()}

    def _bucket_tree(grads):
        """Train-overlap bucket tree (FLAGS_train_overlap): coalesce the
        grad pytree into ~FLAGS_grad_bucket_mb granules in reverse
        parameter order — the order backward produces them. At stage >= 2
        each bucket member keeps its own zero-extended spec (that layout
        IS the reduce_scatter lowering), annotated bucket-by-bucket; below
        stage 2 each bucket is concat'd into one flat buffer,
        with_sharding_constraint-annotated, and split back, handing XLA's
        latency-hiding scheduler one value per bucket to overlap with
        backward compute instead of hundreds of per-param leaves. Concat/
        split and the constraints are identity math: losses stay
        bit-identical to the unbucketed step."""
        from ..framework import config as _config

        if mesh is None or not _config.get_flag("FLAGS_train_overlap",
                                                True):
            return grads
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        cap = max(int(_config.get_flag("FLAGS_grad_bucket_mb", 25)),
                  0) << 20
        out = dict(grads)
        if sharding_stage >= 2:
            for bucket in _grad_buckets(out, cap):
                for n in bucket:
                    if n in grad_shardings:
                        out[n] = jax.lax.with_sharding_constraint(
                            out[n], grad_shardings[n])
            return out
        rep = NamedSharding(mesh, P())
        for bucket in _grad_buckets(out, cap):
            if len(bucket) == 1:
                out[bucket[0]] = jax.lax.with_sharding_constraint(
                    out[bucket[0]], rep)
                continue
            flat = jnp.concatenate([out[n].reshape(-1) for n in bucket])
            flat = jax.lax.with_sharding_constraint(flat, rep)
            off = 0
            for n in bucket:
                size = int(np.prod(grads[n].shape, dtype=np.int64))
                out[n] = flat[off:off + size].reshape(grads[n].shape)
                off += size
        return out

    def pure_step(params, buffers, opt_state, lr, seed, arg_leaves, structure):
        stream = _random.KeyStream(jax.random.wrap_key_data(seed))
        (loss, new_buffers), grads = _loss_and_grads(
            params, buffers, stream, arg_leaves, structure)
        grads = _bucket_tree(grads)
        if sharding_stage >= 2:
            grads = _constrain(grads, grad_shardings)
        with _scope("optimizer"):
            new_params, new_opt_state = \
                optimizer.apply_gradients_functional(
                    params, grads, opt_state, lr)
        if stored_shardings:
            new_params = _constrain(new_params, stored_shardings)
        return loss, new_params, new_buffers, new_opt_state

    def _loss_and_grads(params, buffers, stream, arg_leaves, structure):
        """Shared fwd+bwd closure of both pure steps."""

        def compute_loss(p):
            from ..autograd import tape as _tape

            if sharding_stage >= 3:
                # gather-on-use: stored shards -> full compute layout. The
                # vjp of this constraint lands the cotangents back on the
                # stored (zero-sharded) layout — grads reduce_scatter for
                # free.
                p = _constrain(p, compute_shardings)
            _tls.tracing = True
            try:
                # the eager tape is bypassed — jax.value_and_grad
                # differentiates the traced jax ops directly
                with _tape.no_grad(), _random.with_key_stream(
                    stream
                ), _LayerScope(model, p, buffers) as scope:
                    args, kwargs = unflatten_call(arg_leaves, structure)
                    out = call(model, args[0])
                    loss_t = criterion(out, *args[1:], **kwargs)
                    new_buffers = scope.new_buffers()
            finally:
                _tls.tracing = False
            return as_array(loss_t), new_buffers

        return jax.value_and_grad(compute_loss, has_aux=True)(params)

    def pure_step_merge(params, buffers, opt_state, accum, count, lr, seed,
                        arg_leaves, structure):
        """gradient_merge variant: accumulate, apply every k_merge-th call."""
        stream = _random.KeyStream(jax.random.wrap_key_data(seed))
        (loss, new_buffers), grads = _loss_and_grads(
            params, buffers, stream, arg_leaves, structure)
        grads = _bucket_tree(grads)
        accum = {n: accum[n] + grads[n].astype(accum[n].dtype)
                 for n in accum}
        if sharding_stage >= 2:
            # keep the carried accumulator in the zero-sharded grad layout
            # (reduce-scattered once per micro-call, shard-local between)
            accum = _constrain(accum, grad_shardings)
        count = count + 1

        def apply(params, opt_state, accum):
            scale = jnp.float32(1.0 / k_merge if gradient_merge_avg else 1.0)
            merged = {n: (a * scale).astype(params[n].dtype)
                      for n, a in accum.items()}
            if sharding_stage >= 2:
                merged = _constrain(merged, grad_shardings)
            with _scope("optimizer"):
                new_params, new_opt = optimizer.apply_gradients_functional(
                    params, merged, opt_state, lr)
            if stored_shardings:
                new_params = _constrain(new_params, stored_shardings)
            zeros = {n: jnp.zeros_like(a) for n, a in accum.items()}
            return new_params, new_opt, zeros, jnp.zeros_like(count)

        def skip(params, opt_state, accum):
            return params, opt_state, accum, count

        new_params, new_opt, new_accum, new_count = jax.lax.cond(
            count >= k_merge, apply, skip, params, opt_state, accum)
        return loss, new_params, new_buffers, new_opt, new_accum, new_count

    if k_merge > 1:
        jitted = jax.jit(
            pure_step_merge,
            static_argnames=("structure",),
            donate_argnums=(0, 2, 3, 4) if donate else (),
        )
    else:
        jitted = jax.jit(
            pure_step,
            static_argnames=("structure",),
            donate_argnums=(0, 2) if donate else (),
        )
    # compilewatch: attribute the (rare, expensive) train-step compiles;
    # a post-warmup recompile here means the input pipeline is shape-
    # churning (bucket/pad the batch, not the jit cache)
    jitted = _cw.watch_jit("jit.train_step", jitted)
    merge_holder = {"accum": None, "count": None}

    def step(*args, **kwargs):
        params = model.parameters_pytree()
        buffers = model.buffers_pytree()
        if opt_state_holder["state"] is None:
            opt_state_holder["state"] = optimizer.init_state_pytree(params)
        lr = jnp.asarray(optimizer.get_lr(), dtype=jnp.float32)
        seed = jax.random.key_data(_random.next_key())
        leaves, structure = flatten_call(args, kwargs)
        ost = opt_state_holder["state"]
        if k_merge > 1:
            if merge_holder["accum"] is None:
                # accumulators live in the grad layout (zero-sharded at
                # stage>=2, else the param's own sharding) — a replicated
                # f32 copy of every param would defeat ZeRO's memory story
                def _accum_zeros(n, p):
                    z = jnp.zeros(p.shape, jnp.float32)
                    s = grad_shardings.get(n) if grad_shardings else \
                        getattr(p, "sharding", None)
                    # one-time accumulator init (first step only), not
                    # a per-step staging transfer
                    return jax.device_put(z, s) if s is not None else z  # tpu-lint: disable=sync-transfer-in-step-loop

                merge_holder["accum"] = {
                    n: _accum_zeros(n, p) for n, p in params.items()}
                merge_holder["count"] = jnp.zeros((), jnp.int32)
            (loss, new_params, new_buffers, new_opt, merge_holder["accum"],
             merge_holder["count"]) = jitted(
                params, buffers, opt_state_holder["state"],
                merge_holder["accum"], merge_holder["count"], lr, seed,
                leaves, structure)
        else:
            loss, new_params, new_buffers, new_opt = jitted(
                params, buffers, opt_state_holder["state"], lr, seed, leaves,
                structure,
            )
        opt_state_holder["state"] = new_opt
        # step-time ledger roofline (one dict lookup + flag read when
        # off/registered): AOT-lower the step on ShapeDtypeStructs —
        # shape/dtype only, safe after donation consumed the real
        # buffers — and read the compiled program's cost_analysis
        # FLOPs/bytes. Once per process, only under FLAGS_stepledger.
        from ..observability import stepledger as _sl

        if _sl.enabled() and not _sl.has_cost("train.step"):
            if k_merge > 1:
                _sl.register_from_lowered(
                    "train.step", jitted,
                    (params, buffers, ost, merge_holder["accum"],
                     merge_holder["count"], lr, seed, leaves, structure))
            else:
                _sl.register_from_lowered(
                    "train.step", jitted,
                    (params, buffers, ost, lr, seed, leaves, structure))
        model.load_pytree(new_params)
        model.load_pytree(new_buffers)
        optimizer._step_count += 1
        return Tensor(loss)

    step._opt_state_holder = opt_state_holder
    step._pure_step = pure_step
    step._sharding_stage = sharding_stage
    step._grad_shardings = grad_shardings
    step._stored_shardings = stored_shardings
    return step
