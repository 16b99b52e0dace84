"""Launch context: CLI args + env -> a resolved job description.

Reference parity: python/paddle/distributed/launch/context (SURVEY.md §3.5):
`Context` parses --nnodes/--nproc_per_node/--master/--log_dir and the
PADDLE_* env, producing the per-rank env contract. TPU-native notes: a
chip belongs to one process and libtpu hands a process every chip of its
host, so the unit is ONE process PER HOST driving all local chips through
one `Mesh`; nproc_per_node defaults to 1, and on a host with TPU chips a
larger value is refused (`check_one_process_per_host`) unless the workers
are held to the CPU. Multi-proc-per-node remains for CPU workers.
"""
from __future__ import annotations

import argparse
import os
import socket
from dataclasses import dataclass, field
from typing import List, Optional


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@dataclass
class JobContext:
    script: str = ""
    script_args: List[str] = field(default_factory=list)
    nnodes: int = 1
    node_rank: int = 0
    nproc_per_node: int = 1
    master: Optional[str] = None
    log_dir: str = "log"
    job_id: str = "default"
    max_restarts: int = 0  # >0 enables elastic restart-from-failure
    # fleet telemetry root: each rank writes <dir>/rank_<i>/ shards
    # (observability/fleet.py); the controller merges them at job end
    telemetry_dir: Optional[str] = None
    # live telemetry plane base port: rank i serves /metrics,/healthz,
    # /readyz,/statusz on base+i (observability/httpd.py); 0 = off
    telemetry_port: int = 0
    envs: dict = field(default_factory=dict)

    def __post_init__(self):
        # resolve the master exactly once — every rank_env() call must see
        # the same MASTER_PORT or ranks can never rendezvous
        if self.master is None:
            self.master = f"127.0.0.1:{free_port()}"

    @property
    def world_size(self) -> int:
        return self.nnodes * self.nproc_per_node

    def rank_of(self, local_rank: int) -> int:
        return self.node_rank * self.nproc_per_node + local_rank

    def local_host(self) -> str:
        """This node's address as peers can reach it. Single-node jobs (and
        loopback masters) stay on the master host; multi-node jobs resolve
        the pod's own IP — the master's address is NOT where non-master
        ranks live (reference launcher records each pod's own IP)."""
        host = self.master.split(":")[0]
        if self.nnodes == 1 or host in ("127.0.0.1", "localhost"):
            return host
        # The outbound-route trick, not gethostbyname(gethostname()): on
        # Debian-style /etc/hosts the latter returns 127.0.1.1, which would
        # publish an unreachable loopback address to peers.
        try:
            import socket
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                s.connect((host, 1))  # no packet sent; just picks a route
                return s.getsockname()[0]
            finally:
                s.close()
        except OSError:
            return host

    def store_port(self) -> int:
        """Rendezvous TCPStore port: master_port + world_size by convention
        (ports master_port..master_port+world-1 are the rank endpoints)."""
        return int(self.master.split(":")[1]) + self.world_size

    def endpoints(self) -> List[str]:
        """Endpoint registry. This node's ranks are authoritative (built
        from local_host()); peer nodes' entries are placeholders on the
        master host — workers re-gather the real list through the TCPStore
        at rendezvous (env.init_parallel_env)."""
        host, port = self.master.split(":")
        lh = self.local_host()
        return [
            f"{lh if r // self.nproc_per_node == self.node_rank else host}"
            f":{int(port) + r}"
            for r in range(self.world_size)
        ]


def parse_args(argv=None) -> JobContext:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="multi-process / multi-node training launcher")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", "1")))
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER"))
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("--max_restarts", type=int,
                   default=int(os.environ.get("PADDLE_ELASTIC_MAX_RESTARTS",
                                              "0")))
    p.add_argument("--telemetry_dir", type=str,
                   default=os.environ.get("FLAGS_telemetry_dir") or None,
                   help="fleet telemetry root: every rank exports "
                        "rank_<i>/ shards here and the launcher merges "
                        "them into fleet.prom / fleet_trace.json / "
                        "fleet_report.txt at job end "
                        "(tools/fleet_report.py re-runs the analysis)")
    p.add_argument("--telemetry_port", type=int,
                   default=int(os.environ.get("FLAGS_telemetry_port")
                               or 0),
                   help="live telemetry plane base port: worker rank i "
                        "serves /metrics /healthz /readyz /statusz on "
                        "base+rank (observability/httpd.py; heartbeats "
                        "advertise the address for tools/"
                        "fleet_report.py --scrape). 0 = off")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    a = p.parse_args(argv)
    if a.nnodes > 1 and not a.master:
        raise SystemExit("--master host:port is required when --nnodes > 1")
    return JobContext(
        script=a.script, script_args=a.script_args, nnodes=a.nnodes,
        node_rank=a.node_rank, nproc_per_node=a.nproc_per_node,
        master=a.master, log_dir=a.log_dir,
        job_id=a.job_id, max_restarts=a.max_restarts,
        telemetry_dir=a.telemetry_dir,
        telemetry_port=a.telemetry_port)


def rank_env(ctx: JobContext, local_rank: int) -> dict:
    """The PADDLE_* env contract (reference §3.5) for one worker."""
    eps = ctx.endpoints()
    rank = ctx.rank_of(local_rank)
    master = ctx.master
    env = dict(os.environ)
    env.update(ctx.envs)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(ctx.world_size),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(eps),
        "PADDLE_CURRENT_ENDPOINT": eps[rank],
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_MASTER": master,
        "MASTER_ADDR": master.split(":")[0],
        "MASTER_PORT": master.split(":")[1],
        "PADDLE_JOB_ID": ctx.job_id,
    })
    # the controller blanks this in ctx.envs when its store failed to bind,
    # so workers skip the gather instead of stalling in connect retries
    env.setdefault("PADDLE_STORE_ENDPOINT",
                   f"{master.split(':')[0]}:{ctx.store_port()}")
    if ctx.telemetry_dir:
        # activates the rank-sharded fleet exporter in every worker
        # (observability/fleet.py reads the flag at first telemetry hit)
        env["FLAGS_telemetry_dir"] = ctx.telemetry_dir
    if ctx.telemetry_port:
        # one live HTTP plane per rank at base+rank — distinct ports
        # even with multiple workers on one host (observability/httpd)
        env["FLAGS_telemetry_port"] = str(ctx.telemetry_port + rank)
    return env


def check_one_process_per_host(nproc: int, tpu_chips: int, env) -> None:
    """Refuse N > 1 workers that would fight over the host's chips.

    libtpu gives each process ALL local chips and a chip serves one
    process, so N > 1 TPU workers on one host fail or hang at backend
    start-up. Workers held to the CPU (`env` has JAX_PLATFORMS without
    "tpu") do not touch the chips and may be as many as asked."""
    if nproc <= 1 or tpu_chips == 0:
        return
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return
    raise SystemExit(
        f"[launch] {nproc} worker processes on a host with {tpu_chips} "
        f"TPU chip(s): every worker would claim all of them. Run ONE "
        f"process per host and drive the {tpu_chips} chips through one "
        f"mesh (paddle_tpu.distributed.mesh.init_mesh, e.g. "
        f"tp={tpu_chips}); or set JAX_PLATFORMS=cpu for CPU workers.")
