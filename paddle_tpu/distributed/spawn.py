"""paddle.distributed.spawn parity (SURVEY.md §2.2 "Launch"): run `func`
in nprocs subprocesses with the PADDLE_* env contract set per rank."""
from __future__ import annotations

import multiprocessing as mp
import os

from ..framework import jax_compat as _jc
from .launch.context import check_one_process_per_host, free_port


def _worker(func, rank, nprocs, master, args):
    os.environ.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "MASTER_ADDR": master.split(":")[0],
        "MASTER_PORT": master.split(":")[1],
        "PADDLE_LOCAL_RANK": str(rank),
    })
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    chips = _jc.tpu_chips_on_host()
    if nprocs <= 0:
        # reference semantics: one process per visible device. A host's
        # TPU chips all belong to ONE process, so there the count is 1
        # (and the parent must not start the backend to ask); CPU
        # devices can be counted
        if chips:
            nprocs = 1
        else:
            import jax

            nprocs = jax.local_device_count()
    check_one_process_per_host(nprocs, chips, os.environ)
    master = options.get("master") or f"127.0.0.1:{free_port()}"
    ctx = mp.get_context(options.get("start_method", "spawn"))
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, master, args),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if not join:
        return procs
    failed = []
    for rank, p in enumerate(procs):
        p.join()
        if p.exitcode != 0:
            failed.append((rank, p.exitcode))
    if failed:
        raise RuntimeError(f"spawn workers failed: {failed}")
    return procs
