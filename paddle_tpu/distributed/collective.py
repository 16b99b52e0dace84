"""Collective API (reference: python/paddle/distributed/communication —
SURVEY.md §2.2 "Collective py API", §5 mapping table):

    c_allreduce_sum -> lax.psum        c_allgather  -> lax.all_gather
    c_reducescatter -> lax.psum_scatter send/recv    -> lax.ppermute
    alltoall        -> lax.all_to_all   broadcast    -> convert + psum trick

Eager semantics: each call runs a small shard_map'd program over the global
mesh axis named by `group` ("dp"/"tp"/...; None = all axes). Tensors passed
in are treated as *per-rank shards stacked on axis 0* when they carry a
leading mesh dimension, matching the reference's one-process-per-rank view;
in the common single-process case (world=1) every collective is an identity
— the real use is inside jit where these lower to ICI collectives.
"""
from __future__ import annotations

import time as _time
from typing import Optional

import jax

import jax.numpy as jnp
import numpy as np

from .. import faults as _faults
from ..framework import jax_compat as _jc
from ..tensor import Tensor, as_array
from . import mesh as _mesh


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


# --- telemetry (README.md "Observability"): per-collective call counts
# and bytes moved. Eager calls count executions; the jit-path helpers
# (psum/all_gather_jit/...) count TRACE-time emissions — one per compile,
# not per device launch (XLA owns the executed schedule). Child cells
# cache per op name; HandleCache re-resolves after a registry
# swap/reset, so the steady-state cost is one dict hit + float adds.
_coll_cache = None


def _make_coll_handles(reg):
    return {
        "calls": reg.counter(
            "collective_calls_total",
            "Collective API invocations (jit-path helpers count "
            "trace-time emissions).", labels=("op",)),
        "bytes": reg.counter(
            "collective_bytes_total",
            "Input bytes handed to each collective.", labels=("op",)),
        "timeouts": reg.counter(
            "collective_timeouts_total",
            "Eager collectives that exceeded "
            "FLAGS_collective_timeout_s and were converted from an "
            "indefinite stall into a CollectiveTimeout raise (the "
            "elastic controller restarts the pod on the resulting "
            "nonzero exit).", labels=("op",)),
        "children": {},
    }


def _count_collective(op: str, array=None, arrays=None,
                      instant=True) -> float:
    """One call-count increment per API invocation; bytes summed over
    `array` or every entry of `arrays` (returned so span call sites
    don't recompute them). With span tracing enabled, drops a
    `collective.<op>` instant on the timeline, and with the fleet layer
    on (FLAGS_telemetry_dir) a zero-duration sequence record — EXCEPT
    when the caller wraps execution in a real-duration `_coll_exec`
    (instant=False), which would double both."""
    global _coll_cache
    from ..observability import metrics as _om

    if _coll_cache is None:
        _coll_cache = _om.HandleCache(_make_coll_handles)
    h = _coll_cache.get()
    cell = h["children"].get(op)
    if cell is None:
        cell = (h["calls"].labels(op), h["bytes"].labels(op))
        h["children"][op] = cell
    cell[0].inc()
    nbytes = 0.0
    for a in (arrays if arrays is not None
              else (array,) if array is not None else ()):
        try:  # works for concrete arrays AND tracers (shape/dtype known)
            nbytes += float(np.prod(a.shape)) * a.dtype.itemsize
        except Exception:
            pass
    if nbytes:
        cell[1].inc(nbytes)
    if instant:
        from ..observability import fleet as _fleet
        from ..observability import tracing as _tracing

        if _tracing.enabled():
            _tracing.instant(f"collective.{op}", bytes=nbytes)
        if _fleet.enabled():
            # instantaneous/jit-trace-time calls still advance the per-op
            # sequence counter: every rank compiles/invokes in the same
            # program order, so these align fleet-wide too
            _fleet.record_collective(op, _time.time(), 0.0, nbytes)
    return nbytes


class _CollExec:
    """Wraps ONE eagerly-executing collective with the enabled channels:
    a real-duration tracing span and/or a fleet sequence record carrying
    (enter-time, duration). Allocated only when at least one channel is
    on — `_coll_exec` returns the shared no-op singleton otherwise, so
    the disabled path allocates nothing."""

    __slots__ = ("_op", "_nbytes", "_span", "_fleet", "_w0", "_t0")

    def __init__(self, op, nbytes, span, fleet_on):
        self._op = op
        self._nbytes = nbytes
        self._span = span
        self._fleet = fleet_on
        self._w0 = 0.0
        self._t0 = 0.0

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        if self._fleet:
            self._w0 = _time.time()        # wall: cross-rank alignment
            self._t0 = _time.perf_counter()  # monotonic: duration
        return self

    def __exit__(self, *exc):
        if self._fleet:
            from ..observability import fleet as _fleet

            _fleet.record_collective(
                self._op, self._w0, _time.perf_counter() - self._t0,
                self._nbytes)
        if self._span is not None:
            return self._span.__exit__(*exc)
        return False


def _coll_exec(op: str, nbytes: float = 0.0):
    """Execution context for an eagerly-executing collective: tracing
    span (real duration) + fleet sequence record (the jit-path helpers
    only emit at trace time — an instant/zero-duration record suffices
    there). No-op singleton when both channels are off."""
    from ..observability import fleet as _fleet
    from ..observability import tracing as _tracing

    fleet_on = _fleet.enabled()
    span = _tracing.span(f"collective.{op}", bytes=nbytes) \
        if _tracing.enabled() else None
    if span is None and not fleet_on:
        return _tracing.NOOP_SPAN
    return _CollExec(op, nbytes, span, fleet_on)


class CollectiveTimeout(RuntimeError):
    """An eager collective exceeded FLAGS_collective_timeout_s. Raised
    asynchronously into the stalled thread by the watchdog so a fleet
    deadlock (e.g. one rank never entering a barrier) becomes a nonzero
    exit the elastic controller can restart, instead of hanging the pod
    until the job is killed."""


def _watchdog_fire(op, timeout_s, tid):
    """Timer callback (watchdog thread): telemetry first — the flight
    recorder keeps the evidence even if the raise lands nowhere — then
    the async raise into the stalled thread."""
    import ctypes

    from ..observability import flight_recorder as _flight
    from ..observability import metrics as _om

    global _coll_cache
    try:
        if _coll_cache is None:
            _coll_cache = _om.HandleCache(_make_coll_handles)
        _coll_cache.get()["timeouts"].labels(op).inc()
        _flight.record_event("collective.timeout", op=op,
                             timeout_s=timeout_s)
    except Exception:  # noqa: BLE001 — the raise must still go out
        pass
    ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(tid), ctypes.py_object(CollectiveTimeout))


def _watchdog_arm(op: str):
    """One flag read when FLAGS_collective_timeout_s is 0 (the default);
    otherwise a daemon Timer that fires _watchdog_fire at the deadline.
    Callers cancel it in a finally."""
    from ..framework import config as _config

    timeout_s = float(_config.get_flag("FLAGS_collective_timeout_s",
                                       0.0) or 0.0)
    if timeout_s <= 0:
        return None
    import threading

    timer = threading.Timer(timeout_s, _watchdog_fire,
                            args=(op, timeout_s, threading.get_ident()))
    timer.daemon = True
    timer.start()
    return timer


def _axes_for_group(group):
    m = _mesh.get_mesh(optional=True)
    if m is None:
        return None
    if group is None:
        return tuple(m.axis_names)
    if isinstance(group, str):
        return (group,) if group in m.axis_names else None
    return None


def _world(axes):
    if axes is None:
        return 1
    m = _mesh.get_mesh()
    return int(np.prod([m.shape[a] for a in axes]))


def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place all_reduce (eager identity at world=1; psum under jit)."""
    nbytes = _count_collective("all_reduce", as_array(tensor),
                               instant=False)
    wd = _watchdog_arm("all_reduce")
    try:
        if _faults.enabled():
            _faults.maybe_stall_collective("all_reduce")
            _faults.maybe_fail_collective("all_reduce")
        with _coll_exec("all_reduce", nbytes):
            return _all_reduce_impl(tensor, op, group)
    finally:
        if wd is not None:
            wd.cancel()


def _all_reduce_impl(tensor, op, group):
    axes = _axes_for_group(group)
    if _world(axes) == 1:
        if not _jc.tracing():
            return tensor
    a = as_array(tensor)
    if _jc.tracing():
        # inside a jit/shard_map trace: emit the collective directly
        reducer = {"sum": jax.lax.psum, "max": jax.lax.pmax,
                   "min": jax.lax.pmin, "avg": jax.lax.pmean}[op]
        tensor._rebind(reducer(a, axes))
        return tensor
    # eager multi-device: run a tiny shard_map program
    from jax.sharding import PartitionSpec as P

    m = _mesh.get_mesh()
    reducer = {"sum": jax.lax.psum, "max": jax.lax.pmax,
               "min": jax.lax.pmin, "avg": jax.lax.pmean}[op]
    fn = jax.shard_map(lambda x: reducer(x, axes), mesh=m,
                       in_specs=P(), out_specs=P())
    tensor._rebind(fn(a))
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    _count_collective("all_gather", as_array(tensor))
    axes = _axes_for_group(group)
    if _world(axes) == 1:
        tensor_list.append(Tensor(as_array(tensor)))
        return tensor_list
    raise NotImplementedError(
        "eager multi-rank all_gather: use the jit path (sharding constraints)"
    )


def broadcast(tensor, src=0, group=None, sync_op=True):
    _count_collective("broadcast", as_array(tensor))
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # counts as "reduce", not "all_reduce": one API call, one increment
    nbytes = _count_collective("reduce", as_array(tensor),
                               instant=False)
    wd = _watchdog_arm("reduce")
    try:
        if _faults.enabled():
            _faults.maybe_stall_collective("reduce")
            _faults.maybe_fail_collective("reduce")
        with _coll_exec("reduce", nbytes):
            return _all_reduce_impl(tensor, op, group)
    finally:
        if wd is not None:
            wd.cancel()


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    _count_collective("scatter", as_array(tensor))
    if tensor_list:
        tensor._rebind(as_array(tensor_list[src]))
    return tensor


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    _count_collective("reduce_scatter", as_array(tensor))
    axes = _axes_for_group(group)
    if _world(axes) == 1:
        tensor._rebind(as_array(tensor_list[0]))
        return tensor
    raise NotImplementedError("eager multi-rank reduce_scatter: jit path only")


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """paddle.distributed.gather parity (single-process eager: only the
    dst rank's list receives tensors; multi-rank gathers live on the jit
    path via all_gather)."""
    from .env import get_rank

    _count_collective("gather", as_array(tensor))
    if _jc.tracing():
        raise RuntimeError(
            "distributed.gather mutates a host list and cannot run under "
            "jit tracing; use all_gather inside compiled code")
    if gather_list is not None and get_rank() == dst:
        gather_list.append(Tensor(as_array(tensor)))
    return tensor


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """paddle.distributed.alltoall_single parity (single-process eager:
    identity copy; multi-rank all_to_all lives on the jit path)."""
    _count_collective("alltoall_single", as_array(in_tensor))
    if _jc.tracing():
        raise RuntimeError(
            "distributed.alltoall_single mutates a host tensor and cannot "
            "run under jit tracing; use all_to_all inside compiled code")
    # set_value validates the shape and preserves out_tensor's dtype
    # (paddle keeps the out tensor's dtype)
    out_tensor.set_value(as_array(in_tensor))
    return out_tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    _count_collective("alltoall",
                      arrays=[as_array(t) for t in in_tensor_list])
    if out_tensor_list is None:
        out_tensor_list = []
    out_tensor_list.extend(Tensor(as_array(t)) for t in in_tensor_list)
    return out_tensor_list


def send(tensor, dst=0, group=None, sync_op=True):
    # counted even though it raises: attempted eager p2p is exactly the
    # misuse an operator wants visible on a dashboard
    _count_collective("send", as_array(tensor))
    raise NotImplementedError(
        "point-to-point eager send: multi-host eager is jit-path-only "
        "(SURVEY.md §7 hard part #5); PP uses ppermute inside the compiled "
        "schedule"
    )


def recv(tensor, src=0, group=None, sync_op=True):
    _count_collective("recv", as_array(tensor))
    raise NotImplementedError("see send()")


def barrier(group=None):
    _count_collective("barrier", instant=False)
    wd = _watchdog_arm("barrier")
    try:
        if _faults.enabled():
            _faults.maybe_stall_collective("barrier")
            _faults.maybe_fail_collective("barrier")
        with _coll_exec("barrier"):
            (jax.device_put(0) + 0).block_until_ready()
    finally:
        if wd is not None:
            wd.cancel()


def new_group(ranks=None, backend=None, timeout=None):
    return None


def get_group(id=0):
    return None


def wait(tensor, group=None, use_calc_stream=True):
    as_array(tensor).block_until_ready()


# jit-path collectives (used inside shard_map'd/pjit'd programs)
def psum(x, axis_name):
    _count_collective("psum", x)
    return jax.lax.psum(x, axis_name)


def all_gather_jit(x, axis_name, axis=0, tiled=True):
    _count_collective("all_gather_jit", x)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum_scatter(x, axis_name, scatter_dimension=0, tiled=True):
    _count_collective("psum_scatter", x)
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def ppermute(x, axis_name, perm):
    _count_collective("ppermute", x)
    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all_jit(x, axis_name, split_axis, concat_axis, tiled=True):
    _count_collective("all_to_all_jit", x)
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis,
                              tiled=tiled)


class P2POp:
    """One pending point-to-point op (paddle.distributed.P2POp)."""

    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def isend(tensor, dst=0, group=None):
    """Async send handle API. Same single-controller contract as send():
    eager host-side p2p does not exist in this build — p2p is expressed
    inside jitted programs as lax.ppermute (SURVEY.md §5 mapping,
    send_v2/recv_v2 -> ppermute); calling it eagerly raises with that
    guidance."""
    return send(tensor, dst=dst, group=group)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src=src, group=group)


def batch_isend_irecv(p2p_op_list):
    """paddle.distributed.batch_isend_irecv API shape.

    Executes each op in order and returns completed task handles. With the
    built-in send/recv this raises their documented NotImplementedError
    (eager p2p is jit-only in the single-controller design — use
    lax.ppermute inside shard_map); custom callables (tests, user shims)
    run to completion."""
    class _Done:
        def wait(self):
            return None

        def is_completed(self):
            return True

    tasks = []
    for op in p2p_op_list:
        op.op(op.tensor, op.peer, group=op.group)
        tasks.append(_Done())
    return tasks


# ---------------------------------------------------------------------------
# object collectives + misc (python/paddle/distributed/communication)
# ---------------------------------------------------------------------------


def _obj_to_tensor(obj):
    import pickle

    data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    return Tensor(jnp.asarray(data)), len(data)


def _tensor_to_obj(t, length):
    import pickle

    return pickle.loads(np.asarray(as_array(t))[:int(length)].tobytes())


def all_gather_object(object_list, obj, group=None):
    """paddle.distributed.all_gather_object parity under the
    single-controller stance: every process holds the same Python
    objects, so the gather of one object is [obj]. Eager multi-rank
    object exchange has no host p2p channel here (same contract as the
    tensor collectives: multi-rank = jit path, MIGRATING.md delta #6)."""
    if _world(_axes_for_group(group)) > 1:
        raise NotImplementedError(
            "eager multi-rank all_gather_object has no host channel in "
            "the single-controller design; Python-side state is already "
            "identical on every process")
    object_list.append(obj)


def broadcast_object_list(object_list, src=0, group=None):
    """paddle.distributed.broadcast_object_list parity: in the
    single-controller design src's list IS every process's list already,
    so this is a (semantics-preserving) no-op for any world size."""
    return


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """paddle.distributed.scatter_object_list parity (single-controller:
    world 1 receives src's first object; the reference's per-rank
    scattering needs a host channel the eager path doesn't have)."""
    world = max(_world(_axes_for_group(group)), 1)
    if world > 1:
        raise NotImplementedError(
            "eager multi-rank scatter_object_list has no host channel in "
            "the single-controller design")
    src_list = in_object_list or []
    out_object_list.extend(src_list[:1] or [None])


def destroy_process_group(group=None):
    """paddle.distributed.destroy_process_group parity: drop the mesh/env
    bindings (the XLA runtime itself has no persistent communicators)."""
    from . import mesh as _mesh_mod

    if group is None:
        _mesh_mod.set_mesh(None)


def get_backend(group=None):
    """paddle.distributed.get_backend parity: the comm backend name —
    'xla' (collectives lower to XLA over ICI/DCN; there is no NCCL)."""
    return "xla"


def is_available():
    """paddle.distributed.is_available parity."""
    return True


def gloo_barrier():
    """paddle.distributed.gloo_barrier parity: host-side barrier."""
    barrier()
