"""Collective controller: pod build + watch loop + elastic restart.

Reference parity: python/paddle/distributed/launch/controllers (SURVEY.md
§3.5): `CollectiveController.build_pod` makes one Container per device,
redirects per-rank logs to `<log_dir>/workerlog.N`, and a watch loop polls
container status — teardown on failure, or (elastic, SURVEY.md §5 "Failure
detection") relaunch up to max_restarts with the restart-from-checkpoint
philosophy: the training script is expected to resume from its latest
checkpoint (distributed.checkpoint.CheckpointManager).
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

from ...framework import jax_compat as _jc
from .context import JobContext, check_one_process_per_host, rank_env


@dataclass
class Container:
    local_rank: int
    cmd: List[str]
    env: dict
    log_path: str
    proc: Optional[subprocess.Popen] = None
    _interrupted: bool = False

    def start(self):
        os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
        logf = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            self.cmd, env=self.env, stdout=logf, stderr=subprocess.STDOUT)
        self._interrupted = False

    def poll(self) -> Optional[int]:
        return self.proc.poll() if self.proc else None

    def interrupt(self):
        """Send SIGINT without waiting — _teardown broadcasts this to
        the whole pod first so every rank's grace window overlaps
        instead of serializing (a pod of hung ranks would otherwise pay
        one full escalation each, back to back)."""
        if self.proc and self.proc.poll() is None:
            try:
                self.proc.send_signal(signal.SIGINT)
                self._interrupted = True
            except OSError:
                pass

    def terminate(self, grace: float = 5.0):
        """SIGINT -> SIGTERM -> SIGKILL escalation. SIGINT first is
        deliberate: Python's default SIGTERM disposition skips atexit,
        which would drop the fleet exporter's FINAL telemetry flush in
        every surviving rank — losing the last flush-interval of
        collectives/heartbeats, the most diagnostic window of a failure
        teardown. KeyboardInterrupt unwinds through atexit; a hung rank
        that ignores it meets SIGTERM/SIGKILL on the same grace. Sends
        no second SIGINT when interrupt() already delivered one (a rank
        unwinding its atexit flush must not be re-interrupted mid-write)."""
        if self.proc and self.proc.poll() is None:
            if not self._interrupted:
                try:
                    self.proc.send_signal(signal.SIGINT)
                    self._interrupted = True
                except OSError:
                    pass
            try:
                self.proc.wait(grace)
                return
            except subprocess.TimeoutExpired:
                pass
            self.proc.terminate()
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


class CollectiveController:
    def __init__(self, ctx: JobContext):
        self.ctx = ctx
        self.pod: List[Container] = []
        self.pod_restarts = 0
        self._store = None
        if ctx.node_rank == 0:
            # Rendezvous store for the job (reference: the launch master's
            # TCPStore). Port is the deterministic convention
            # master_port + world_size, so non-master pods can derive it
            # without extra coordination; workers use it to publish their
            # real endpoints (env.init_parallel_env gather).
            try:
                from ..store import TCPStore

                self._store = TCPStore(
                    "127.0.0.1", ctx.store_port(), is_master=True,
                    world_size=ctx.world_size)
            except Exception as e:  # port taken / native build issue:
                # launch still works; blank the endpoint so this pod's
                # workers skip the gather instead of stalling in connect
                # retries against a store that will never answer
                print(f"[launch] TCPStore master unavailable: {e}",
                      file=sys.stderr)
                ctx.envs["PADDLE_STORE_ENDPOINT"] = ""

    def build_pod(self):
        check_one_process_per_host(
            self.ctx.nproc_per_node, _jc.tpu_chips_on_host(),
            {**os.environ, **self.ctx.envs})
        for lr in range(self.ctx.nproc_per_node):
            rank = self.ctx.rank_of(lr)
            log = os.path.join(self.ctx.log_dir, f"workerlog.{rank}")
            cmd = [sys.executable, "-u", self.ctx.script,
                   *self.ctx.script_args]
            self.pod.append(Container(
                local_rank=lr, cmd=cmd, env=rank_env(self.ctx, lr),
                log_path=log))
        return self.pod

    def run(self, poll_interval: float = 0.5) -> int:
        """Start everything; watch; return the job's exit code."""
        if not self.pod:
            self.build_pod()
        for c in self.pod:
            c.start()
        try:
            return self._watch(poll_interval)
        except KeyboardInterrupt:
            self._teardown()
            self._aggregate_telemetry()
            return 130

    def _watch(self, poll_interval: float) -> int:
        while True:
            statuses = [c.poll() for c in self.pod]
            if all(s == 0 for s in statuses):
                self._aggregate_telemetry()
                return 0
            failed = next((s for s in statuses if s not in (None, 0)), None)
            if failed is not None:
                # collective jobs cannot be repaired one rank at a time —
                # surviving ranks are parked inside collectives with stale
                # rendezvous state. Restart the WHOLE pod (reference
                # semantics: relaunch from the latest checkpoint).
                if self.pod_restarts < self.ctx.max_restarts:
                    self.pod_restarts += 1
                    print(f"[launch] a rank exited {failed}; elastic pod "
                          f"restart {self.pod_restarts}/"
                          f"{self.ctx.max_restarts}", file=sys.stderr)
                    self._record_restart(failed)
                    self._teardown()
                    for c in self.pod:
                        c.start()
                else:
                    print(f"[launch] rank failed with exit code {failed}; "
                          f"tearing down pod "
                          f"(logs: {self.ctx.log_dir}/workerlog.*)",
                          file=sys.stderr)
                    self._teardown()
                    # failure is exactly when the merged view matters:
                    # the report names the dead rank / straggler
                    self._aggregate_telemetry()
                    return failed
            time.sleep(poll_interval)

    def _record_restart(self, exit_code):
        """Durable restart breadcrumb (telemetry_dir/pod_restarts.json):
        tools/chaos_drill.py asserts the elastic restart actually fired,
        and operators correlate it with the resumed step. Best-effort."""
        tdir = self.ctx.telemetry_dir
        if not tdir:
            return
        try:
            import json

            # a kill can land before any rank's flusher created the
            # telemetry dir — the breadcrumb must not depend on that
            os.makedirs(tdir, exist_ok=True)
            path = os.path.join(tdir, "pod_restarts.json")
            try:
                with open(path, "r", encoding="utf-8") as f:
                    events = json.load(f)
            except (OSError, ValueError):
                events = []
            events.append({"restart": self.pod_restarts,
                           "exit_code": exit_code, "t": time.time()})
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(events, f, indent=1)
            os.replace(tmp, path)
        except Exception as e:  # noqa: BLE001 — best-effort breadcrumb
            print(f"[launch] restart breadcrumb failed: {e}",
                  file=sys.stderr)

    def _teardown(self):
        # broadcast SIGINT first (overlapping grace windows), then the
        # serial wait/escalate pass
        for c in self.pod:
            c.interrupt()
        for c in self.pod:
            c.terminate()

    def _aggregate_telemetry(self):
        """Merge the rank telemetry shards at job end (success, final
        failure, or interrupt): fleet.prom + fleet_trace.json +
        fleet_report.txt land next to the shards, and dead-rank /
        straggler findings go to stderr. Best-effort — a telemetry
        failure must never change the job's exit code."""
        tdir = self.ctx.telemetry_dir
        if not tdir:
            return
        try:
            from ...observability import fleet as _fleet

            report = _fleet.aggregate(tdir)
            if not report["shards"]:
                print(f"[launch] fleet telemetry: no rank shards under "
                      f"{tdir}", file=sys.stderr)
                return
            text = _fleet.format_report(report)
            path = os.path.join(tdir, "fleet_report.txt")
            with open(path, "w") as f:
                f.write(text)
            art = report["artifacts"]
            print(f"[launch] fleet telemetry: merged "
                  f"{len(report['shards'])} shards -> {art['prom']}, "
                  f"{art['trace']}; report: {path}", file=sys.stderr)
            for r in report["missing"]:
                print(f"[launch] MISSING RANK: rank {r} wrote no "
                      f"telemetry shard", file=sys.stderr)
            for d in report["dead"]:
                if d.get("never_beat"):
                    print(f"[launch] DEAD RANK: rank {d['rank']} never "
                          f"beat (hung before its first step?)",
                          file=sys.stderr)
                else:
                    print(f"[launch] DEAD RANK: rank {d['rank']} "
                          f"stopped beating at step {d['step']} "
                          f"({d['age_s']:.1f} s behind the fleet)",
                          file=sys.stderr)
            for r in report["stragglers"][:3]:
                print(f"[launch] STRAGGLER: rank {r['last_rank']} was "
                      f"last into {r['op']} #{r['seq']} by "
                      f"{r['skew_s'] * 1e3:.1f} ms", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — best-effort reporting
            print(f"[launch] fleet telemetry aggregation failed: {e}",
                  file=sys.stderr)
