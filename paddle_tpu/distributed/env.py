"""Process environment (reference: env-var contract of
paddle.distributed.launch — PADDLE_TRAINER_ID etc., SURVEY.md §3.5).

On TPU, multi-host process identity comes from jax.distributed /
jax.process_index(); the PADDLE_* env vars are honored when present so
launch-style scripts keep working.
"""
from __future__ import annotations

import os

import jax

_initialized = False


def init_parallel_env():
    """paddle.distributed.init_parallel_env parity.

    Single-host: no-op (one process sees all local devices).
    Multi-host: jax.distributed.initialize from env
    (MASTER_ADDR/PADDLE_MASTER or coordinator discovery).
    """
    global _initialized
    if _initialized:
        return
    n_procs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if n_procs > 1 and not _distributed_client_up():
        # NOTE: nothing before this point may touch the XLA backend —
        # jax.distributed.initialize() must run before the first
        # jax.devices()/process_count()/computation in the process
        coordinator = os.environ.get("PADDLE_MASTER") or os.environ.get(
            "MASTER_ADDR")
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        _gather_endpoints(rank, n_procs)
        if coordinator:
            port = os.environ.get("MASTER_PORT", "8476")
            addr = coordinator if ":" in coordinator else f"{coordinator}:{port}"
            jax.distributed.initialize(
                coordinator_address=addr, num_processes=n_procs,
                process_id=rank,
            )
    _initialized = True


def _distributed_client_up() -> bool:
    """Whether jax.distributed is already initialized, WITHOUT touching the
    XLA backend (jax.process_count() would initialize it and make a later
    jax.distributed.initialize impossible)."""
    return jax.distributed.is_initialized()


def _gather_endpoints(rank: int, world: int, timeout: float = None) -> None:
    """Publish this rank's real endpoint to the launch master's TCPStore
    and rebuild PADDLE_TRAINER_ENDPOINTS from every rank's registration —
    the launcher can only synthesize placeholder entries for peer nodes
    (launch/context.py endpoints()); the store holds the truth."""
    store_ep = os.environ.get("PADDLE_STORE_ENDPOINT")
    my_ep = os.environ.get("PADDLE_CURRENT_ENDPOINT")
    job = os.environ.get("PADDLE_JOB_ID", "default")
    if not store_ep or not my_ep:
        return
    if timeout is None:
        timeout = float(os.environ.get("PADDLE_STORE_TIMEOUT", "30"))
    try:
        from .store import TCPStore

        host, port = store_ep.rsplit(":", 1)
        store = TCPStore(host, int(port), world_size=world, timeout=timeout)
        store.set(f"{job}/ep/{rank}", my_ep)
        eps = [store.wait(f"{job}/ep/{r}", timeout=timeout).decode()
               for r in range(world)]
        os.environ["PADDLE_TRAINER_ENDPOINTS"] = ",".join(eps)
    except Exception:
        # best-effort: single-node jobs and tests without a store master
        # keep the synthesized list
        pass


def get_rank():
    env = os.environ.get("PADDLE_TRAINER_ID")
    if env is not None:
        return int(env)
    return jax.process_index()


def get_world_size():
    env = os.environ.get("PADDLE_TRAINERS_NUM")
    if env is not None:
        return int(env)
    # data-parallel world size = number of mesh 'dp' slots if a mesh is live,
    # else process count (1 on single host even with many chips: collectives
    # under jit span local devices transparently)
    from . import mesh as _mesh

    m = _mesh.get_mesh(optional=True)
    if m is not None and "dp" in m.axis_names:
        return int(m.shape["dp"])
    return jax.process_count()


def is_initialized():
    return _initialized
