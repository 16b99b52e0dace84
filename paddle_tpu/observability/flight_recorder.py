"""Stall flight-recorder: an event ring + a watchdog thread.

A silent TPU hang (a wedged collective, a lost device, a deadlocked host
thread) looks identical to "still computing" from the outside. The
flight recorder turns it into an artifact:

- `FlightRecorder` — a bounded ring of recent instrumentation events
  (`record(kind, **fields)` is one deque append; serving/train steps and
  the dataloader push breadcrumbs here).
- `Watchdog` — a daemon thread armed by `start()` and fed by `beat()`
  from every completed serving/train step. If no beat lands within the
  deadline it dumps ALL Python thread stacks plus the trailing event
  ring to a file and increments `stalls_total` — exactly once per stall
  (it re-arms only after the next beat).

Steps signal liveness through `beat_all()`, which fans out to every
started watchdog — the engine/trainer don't need a handle to whichever
watchdog the operator armed.
"""
from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from collections import deque
from typing import List, Optional

from . import metrics as _metrics


class FlightRecorder:
    """Bounded ring of (timestamp, kind, fields) breadcrumbs."""

    def __init__(self, capacity: int = 1024):
        self._events = deque(maxlen=int(capacity))

    def record(self, kind: str, **fields):
        # one deque append; deque(maxlen=...) is thread-safe under the GIL
        self._events.append((time.time(), kind, fields))

    def tail(self, n: Optional[int] = None) -> List[tuple]:
        evs = list(self._events)
        return evs if n is None else evs[-int(n):]

    def clear(self):
        self._events.clear()

    def __len__(self):
        return len(self._events)


_default_recorder = FlightRecorder()


def default_recorder() -> FlightRecorder:
    return _default_recorder


def record_event(kind: str, **fields):
    """Record into the process-default ring (the instrumentation entry
    point — one deque append, safe on any hot path)."""
    _default_recorder.record(kind, **fields)


# every started watchdog; beat_all() fans out from step completions
_watchdogs: List["Watchdog"] = []
_watchdogs_lock = threading.Lock()


def beat_all():
    for w in _watchdogs:
        w.beat()


def any_stalled() -> bool:
    """True while any started watchdog is in the stalled state (missed
    deadline, no beat since) — the liveness half of /healthz
    (observability/httpd.py). Re-arms to False at the next beat."""
    return any(w._stalled for w in list(_watchdogs))


def format_thread_stacks() -> str:
    """All Python thread stacks as text (the /debug/stacks payload and
    the stall-dump section share this)."""
    return _format_thread_stacks()


def _format_thread_stacks() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    parts = []
    for tid, frame in sys._current_frames().items():
        parts.append(f"--- thread {names.get(tid, '?')} (ident {tid}) ---")
        parts.append("".join(traceback.format_stack(frame)))
    return "\n".join(parts)


class Watchdog:
    """Deadline monitor over step completions.

    wd = Watchdog(deadline=30.0, dump_dir="/tmp")
    wd.start()              # arms; serving/train steps call beat_all()
    ...
    wd.stop()

    On a missed deadline: one dump file (thread stacks + the last
    `tail_events` ring entries), `stalls_total` += 1, and the watchdog
    holds fire until a beat proves the process is alive again."""

    def __init__(self, deadline: float, dump_dir: str = ".",
                 recorder: Optional[FlightRecorder] = None,
                 registry: Optional[_metrics.Registry] = None,
                 name: str = "runtime", tail_events: int = 256,
                 poll_interval: Optional[float] = None):
        if deadline <= 0:
            raise ValueError("watchdog deadline must be > 0 seconds")
        self.deadline = float(deadline)
        self.dump_dir = dump_dir
        self.name = name
        self.tail_events = int(tail_events)
        self.recorder = recorder or default_recorder()
        reg = registry or _metrics.default_registry()
        self._stalls = reg.counter(
            "stalls_total",
            "Watchdog deadline misses (no serving/train step completed "
            "in time); each one produced a flight-recorder dump.")
        self._poll = poll_interval or min(self.deadline / 4.0, 1.0)
        self._last_beat = None
        self._stalled = False
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.dumps: List[str] = []  # paths written, newest last

    def beat(self):
        self._last_beat = time.monotonic()
        self._stalled = False

    def start(self):
        if self._thread is not None:
            return self
        self._stop_evt.clear()
        self._last_beat = time.monotonic()
        self._thread = threading.Thread(
            target=self._loop, name=f"watchdog-{self.name}", daemon=True)
        with _watchdogs_lock:
            _watchdogs.append(self)
        self._thread.start()
        return self

    def stop(self):
        self._stop_evt.set()
        with _watchdogs_lock:
            if self in _watchdogs:
                _watchdogs.remove(self)
        if self._thread is not None:
            self._thread.join(timeout=self._poll * 4 + 1.0)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _loop(self):
        while not self._stop_evt.wait(self._poll):
            if self._stalled or self._last_beat is None:
                continue
            age = time.monotonic() - self._last_beat
            if age > self.deadline:
                # mark BEFORE dumping: exactly one dump per stall even if
                # the dump itself is slow
                self._stalled = True
                try:
                    self.dump(age)
                except Exception:
                    pass
                self._stalls.inc()

    def dump(self, stall_age: Optional[float] = None) -> str:
        """Write the stall artifact; returns its path.

        The filename carries rank (when the launch env declares one) and
        pid: concurrent ranks of one job share a dump_dir, and without
        the disambiguation they would overwrite each other's dumps."""
        os.makedirs(self.dump_dir, exist_ok=True)
        rank, world = _metrics.rank_world()
        rank_known = world > 1 or "PADDLE_TRAINER_ID" in os.environ
        rank_tag = f"_r{rank}" if rank_known else ""
        path = os.path.join(
            self.dump_dir,
            f"stall_{self.name}{rank_tag}_{os.getpid()}_"
            f"{len(self.dumps)}.txt")
        lines = [
            f"paddle_tpu stall flight-recorder dump",
            f"name: {self.name}",
            f"rank: {rank}",
            f"world_size: {world}",
            f"pid: {os.getpid()}",
            f"time: {time.strftime('%Y-%m-%dT%H:%M:%S%z')}",
            f"deadline_s: {self.deadline}",
            f"stall_age_s: "
            f"{'' if stall_age is None else round(stall_age, 3)}",
            "",
            "== python thread stacks ==",
            _format_thread_stacks(),
            "",
            "== open spans (longest first) ==",
        ]
        # the span tracer knows WHERE each thread is stuck semantically
        # ("41 s inside serving.prefill"), not just which stack frame —
        # append every in-flight span with its elapsed time
        try:
            from . import tracing as _tracing

            opened = _tracing.open_spans()
            if opened:
                for thread_name, span_name, elapsed in opened:
                    lines.append(
                        f"{thread_name}: {span_name} "
                        f"({elapsed:.3f}s open)")
            else:
                lines.append("(none)")
        except Exception:  # noqa: BLE001 — a tracer failure must not
            lines.append("(unavailable)")  # take the stall dump down
        # a stalled step is often an OOM-retry loop: append the current
        # memory report (device watermarks + ranked live buffers) so the
        # dump answers "was it memory?" without a second incident
        lines += ["", "== memory report =="]
        try:
            from . import memwatch as _memwatch

            lines.append(_memwatch.report_text().rstrip())
        except Exception:  # noqa: BLE001 — memwatch failure must not
            lines.append("(unavailable)")  # take the stall dump down
        lines += [
            "",
            f"== last {self.tail_events} events "
            f"(of {len(self.recorder)} in ring) ==",
        ]
        for ts, kind, fields in self.recorder.tail(self.tail_events):
            lines.append(f"{ts:.6f} {kind} {fields}")
        lines += [
            "",
            "hint: a stall with threads parked inside a collective is "
            "often a rank-divergent collective (`if rank == 0: "
            "all_reduce(...)`) — statically detectable BEFORE the run: "
            "`python tools/tpu_lint.py --select "
            "rank-divergent-collective paddle_tpu/`",
        ]
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        self.dumps.append(path)
        return path
