"""Subprocess entry point for one CPU serving replica.

``python -m paddle_tpu.inference.replica_worker --fleet-dir D`` builds
a tiny LLaMA ServingEngine, warms every prefill bucket the traffic
shape can hit, starts the telemetry httpd on an ephemeral port, mounts
the ReplicaServer generate bridge, and publishes its endpoint through
a fleet heartbeat under ``--fleet-dir`` — after which the parent
discovers it with ``inference.auto_replicas(D)`` (the ``--replicas
auto`` path). One process per replica is the point: the router's
gates (tools/router_smoke.py) exercise N processes with N GILs, which
threads in one interpreter cannot show.

CPU BY DESIGN: the worker and ``spawn_replicas`` hold their processes to
the CPU (``JAX_PLATFORMS`` defaults to ``cpu``). A TPU chip belongs to one
process and a process that starts jax takes every local chip, so N worker
processes on one TPU host would fight for them. Replicas on chips are N
engines on N devices inside ONE process (``LocalReplica``; ROADMAP R6).

The worker prints exactly one ``READY {json}`` line on stdout when it
is routable, then heartbeats until its parent disappears or it is
terminated. ``--chaos`` arms a FLAGS_chaos schedule *after* warmup so
the injected fault lands in served traffic, not in compilation.

``spawn_replicas`` is the parent-side helper both callers share.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence


def _parse(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--name", default="replica")
    ap.add_argument("--fleet-dir", required=True,
                    help="FLAGS_telemetry_dir root; the heartbeat "
                         "endpoint published here is the discovery "
                         "contract")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--decode-burst", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="prompt length the warmup compiles for (the "
                         "caller must send prompts of this length to "
                         "stay recompile-free)")
    ap.add_argument("--scheduler", default=None,
                    help="SchedulerPolicy name (fifo | slo); default "
                         "follows FLAGS_scheduler_policy")
    ap.add_argument("--prefix-cache", type=int, default=None,
                    help="enable the prefix cache (0/1 engine kwarg "
                         "prefix_cache); the kv-fabric smoke turns it "
                         "on to exercise spill/promote under served "
                         "traffic)")
    ap.add_argument("--kv-host-cache-mb", type=int, default=None,
                    help="host-RAM spill tier budget in MB "
                         "(FLAGS_kv_host_cache_mb; requires "
                         "--prefix-cache)")
    ap.add_argument("--kv-disk-cache-dir", default=None,
                    help="disk spill tier directory "
                         "(FLAGS_kv_disk_cache_dir)")
    ap.add_argument("--kv-quant", default=None,
                    help="KV cache quantization (e.g. int8) so the "
                         "handoff parity smoke covers quantized "
                         "pages+scales on the wire")
    ap.add_argument("--vocab", type=int, default=97)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", default="",
                    help="FLAGS_chaos schedule armed AFTER warmup, "
                         "e.g. 'decode.oom@p=1.0:n=2'")
    ap.add_argument("--flag", action="append", default=[],
                    metavar="FLAGS_name=value",
                    help="extra FLAGS_* overrides applied before the "
                         "engine is built (repeatable), e.g. "
                         "--flag FLAGS_timeseries_interval_s=0.2 "
                         "--flag FLAGS_anomaly=1 — how doctor_smoke "
                         "arms history sampling + anomaly detection "
                         "in its workers")
    ap.add_argument("--recovery-backoff", type=float, default=None,
                    help="FLAGS_serving_recovery_backoff_s override "
                         "(widen the drain window the smoke observes)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="FLAGS_trace_sample for this replica (the "
                         "stitch smoke sets 1.0 so every routed "
                         "request's X-PT-Trace context lands spans in "
                         "this worker's trace.json shard)")
    ap.add_argument("--slo-ttft-ms", type=float, default=60000.0,
                    help="FLAGS_slo_ttft_p95_ms for this replica. The "
                         "default is deliberately loose: a tiny CPU "
                         "model's first requests pay XLA compile, and "
                         "with the burn window clamped to short "
                         "history a production threshold would leave "
                         "the replica permanently 'burning' — which "
                         "would make the router shed the whole smoke")
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _parse(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import config as _cfg
    from paddle_tpu.inference import ReplicaServer, ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import fleet as _fleet
    from paddle_tpu.observability import httpd as _httpd

    flags = {"FLAGS_telemetry_dir": args.fleet_dir,
             # OOM forensics dumps default to cwd; a chaos-armed
             # worker must drop them with its other artifacts, not
             # into whatever directory the parent launched from
             "FLAGS_memwatch_dump_dir": args.fleet_dir,
             "FLAGS_slo_ttft_p95_ms": float(args.slo_ttft_ms)}
    if args.recovery_backoff is not None:
        flags["FLAGS_serving_recovery_backoff_s"] = \
            float(args.recovery_backoff)
    if args.trace_sample is not None:
        flags["FLAGS_trace_sample"] = float(args.trace_sample)
    for pair in args.flag:
        name, sep, val = pair.partition("=")
        if not sep or not name.startswith("FLAGS_"):
            raise SystemExit(f"--flag expects FLAGS_name=value, "
                             f"got {pair!r}")
        flags[name] = val  # set_flags coerces via the flag's type
    _cfg.set_flags(flags)

    paddle.seed(args.seed)
    cfg = LlamaConfig.tiny(vocab=args.vocab, hidden=args.hidden,
                           layers=args.layers, heads=args.heads,
                           seq=args.max_seq_len)
    model = LlamaForCausalLM(cfg)
    extra = {}
    if args.prefix_cache is not None:
        extra["prefix_cache"] = args.prefix_cache
    if args.kv_host_cache_mb is not None:
        extra["kv_host_cache_mb"] = args.kv_host_cache_mb
    if args.kv_disk_cache_dir is not None:
        extra["kv_disk_cache_dir"] = args.kv_disk_cache_dir
    if args.kv_quant:
        extra["kv_cache_quant"] = args.kv_quant
    engine = ServingEngine(model, max_batch=args.max_batch,
                           max_seq_len=args.max_seq_len,
                           page_size=args.page_size,
                           decode_strategy="greedy_search",
                           decode_burst=args.decode_burst,
                           scheduler=args.scheduler, **extra)
    engine.warmup(prompt_len=args.prompt_len)
    # requests arrive one at a time over HTTP, so admission forms
    # prefill batches at every pow2 nb up to max_batch — compile each
    # bucket now or the first routed requests pay XLA inside the
    # throughput gate's timed region
    rng = np.random.RandomState(args.seed + 1)
    warm_nbs = sorted({1, 2, args.max_batch} & set(
        range(1, args.max_batch + 1)))
    # still warmup traffic: the request ledger (FLAGS_requestlog) must
    # not bill these synthetic requests to a tenant
    engine._warming = True
    try:
        for nb in warm_nbs:
            for _ in range(nb):
                engine.add_request(
                    rng.randint(0, args.vocab, (args.prompt_len,)),
                    max_new_tokens=4)
            engine.run()
    finally:
        engine._warming = False

    _httpd.start_server(port=0)
    server = ReplicaServer(engine).start()
    _fleet.heartbeat()
    _fleet.flush_now()
    if args.chaos:
        _cfg.set_flags({"FLAGS_chaos": args.chaos})
    print("READY " + json.dumps(
        {"name": args.name,
         "endpoint": _httpd.advertised_address()}), flush=True)

    try:
        while True:
            time.sleep(1.0)
            if os.getppid() == 1:   # orphaned — parent is gone
                break
            _fleet.heartbeat()
            _fleet.flush_now()
    except KeyboardInterrupt:
        pass
    server.stop()
    return 0


# ---------------------------------------------------------------------------
# parent-side spawner (shared by the tools/*_smoke.py drives)
# ---------------------------------------------------------------------------


class ReplicaProc:
    """A spawned worker: its Popen handle plus the READY payload."""

    def __init__(self, proc: subprocess.Popen, name: str):
        self.proc = proc
        self.name = name
        self.endpoint: Optional[str] = None
        self.ready = threading.Event()
        self.lines: List[str] = []

    def stop(self):
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10.0)


def _pump(rp: ReplicaProc):
    for raw in rp.proc.stdout:
        line = raw.decode("utf-8", "replace").rstrip()
        rp.lines.append(line)
        if line.startswith("READY "):
            try:
                rp.endpoint = json.loads(line[6:]).get("endpoint")
            except ValueError:
                rp.endpoint = None
            rp.ready.set()
    rp.ready.set()   # EOF: wake the waiter so it can report the death


def spawn_replicas(n: int, fleet_dir: str, *,
                   worker_args: Sequence[str] = (),
                   chaos: str = "", chaos_replicas: Sequence[int] = (),
                   chaos_by_replica: Optional[Dict[int, str]] = None,
                   recovery_backoff: Optional[float] = None,
                   timeout: float = 300.0,
                   log_dir: Optional[str] = None) -> List[ReplicaProc]:
    """Spawn ``n`` replica workers and block until every one prints
    READY (raises RuntimeError with the worker's log tail otherwise).
    ``chaos`` is armed only on the replica indices in
    ``chaos_replicas``; ``chaos_by_replica`` maps index -> schedule
    when different replicas need DIFFERENT faults (the doctor smoke
    storms decode.oom on one worker and drags rank.slow on another).
    Each worker gets a distinct PADDLE_TRAINER_ID so the fleet shards
    (and heartbeat endpoints) don't collide."""
    procs: List[ReplicaProc] = []
    log_dir = log_dir or fleet_dir
    os.makedirs(log_dir, exist_ok=True)
    for i in range(n):
        name = f"r{i}"
        cmd = [sys.executable, "-m",
               "paddle_tpu.inference.replica_worker",
               "--name", name, "--fleet-dir", fleet_dir,
               *worker_args]
        sched = (chaos_by_replica or {}).get(i) or \
            (chaos if chaos and i in set(chaos_replicas) else "")
        if sched:
            cmd += ["--chaos", sched]
            if recovery_backoff is not None:
                cmd += ["--recovery-backoff", str(recovery_backoff)]
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PADDLE_TRAINER_ID"] = str(i)
        stderr = open(os.path.join(log_dir, f"{name}.stderr.log"), "wb")
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=stderr, env=env)
        stderr.close()
        rp = ReplicaProc(proc, name)
        threading.Thread(target=_pump, args=(rp,), daemon=True).start()
        procs.append(rp)
    deadline = time.monotonic() + timeout
    for rp in procs:
        left = max(0.0, deadline - time.monotonic())
        if not rp.ready.wait(timeout=left) or rp.endpoint is None:
            for p in procs:
                p.stop()
            tail = "\n".join(rp.lines[-5:])
            raise RuntimeError(
                f"replica {rp.name} not READY after {timeout:.0f}s "
                f"(exit={rp.proc.poll()}); stdout tail:\n{tail}\n"
                f"stderr: {os.path.join(log_dir, rp.name)}.stderr.log")
    return procs


if __name__ == "__main__":
    sys.exit(main())
