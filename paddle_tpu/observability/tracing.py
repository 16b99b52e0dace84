"""Span tracing: per-request / per-step timelines with Chrome-trace
export (README.md "Observability", third channel).

The metrics registry answers "what are the aggregates" and the flight
recorder answers "what happened just before the hang" — neither answers
"*why* was THIS request's TTFT 900 ms" or "which phase of step N ate the
budget". Spans do: every instrumented hot path (serving request
lifecycle, train step phases, checkpoint saves,
collective calls, dataloader fetches) records bounded, monotonic-clock
intervals that export directly into the Chrome trace-event JSON format
Perfetto / chrome://tracing load natively, and that
`tools/trace_report.py` turns into TTFT breakdowns and a critical path.

Design (thread-safe, nothing allocated in the ring when off; the only
dependency is jax's profiler, for `phase`):

- `span(name, **attrs)` — context manager for synchronous phases;
  `begin(...)`/`end()` — explicit open spans for async phases that cross
  call boundaries (a request's queue wait). Timestamps come from
  `time.perf_counter()` (monotonic — wall-clock steps never produce
  negative durations).
- `Trace` — one logical timeline (one serving request, one train step).
  Spans buffer on the trace and commit into the tracer's bounded ring at
  `finish()`, subject to HEAD-BASED sampling: the keep/drop decision is
  taken when the trace starts (`FLAGS_trace_sample` = sampling
  probability, 0 = tracing off entirely). Escape hatch: when
  `FLAGS_trace_slow_ms` > 0, an UNsampled trace still buffers and is
  promoted to the ring if its total latency crosses the threshold — the
  slow tail is exactly what an operator needs and exactly what head
  sampling would lose; each promotion (and every sampled-slow trace)
  bumps `trace_slow_requests_total`.
- Track assignment: synchronous spans land on a per-thread track (with
  thread-name metadata); each own-track `Trace` (serving requests) gets
  its own `req/<trace_id>` track so overlapping requests don't corrupt
  each other's nesting in the viewer.
- Storage is a bounded ring (`deque(maxlen=...)`) of plain tuples — one
  append per committed span, safe on any hot path under the GIL.
- `FLAGS_trace_sample=0` fast path: `enabled()` is one flag read;
  `span()`/`start_trace()` return shared no-op singletons and allocate
  NOTHING (`Tracer.spans_created` counts every span/trace allocation so
  tests can pin the fast path, same discipline as
  `Registry.allocations`).
- `phase(name, **attrs)` / `mark(name, seconds, **attrs)` — the serving
  engine's own timeline (and the builds of every program). A phase IS a
  `jax.profiler.TraceAnnotation`, opened whatever the flags say: with no
  profiler session that is one small object and a flag test inside
  TraceMe, with one (`jax.profiler.start_trace`) the span lands in the
  `.xplane.pb` on the device trace's own clock, where an idle gap of the
  chip can be laid against it. Only when `enabled()` does it ALSO commit
  to the ring, as `emit` does. `SCOPES` are the `jax.named_scope` names
  the models give their parts, so that the device's operations can be
  summed the same way (`scope(name)`).

Correlation across the three channels: spans carry the same `rid` /
`trace_id` fields `flight_recorder.record_event` breadcrumbs carry, the
watchdog stall dump appends the currently-open spans per thread
(`open_spans()`), and slow traces surface in the metrics registry via
`trace_slow_requests_total`.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from jax import named_scope as scope  # noqa: F401 — `with scope("attn")`
from jax.profiler import TraceAnnotation

from . import metrics as _metrics

# what the models call their parts in `op_name` (HLO metadata only, the
# compiled programs do not change). Finer names nest under these:
# `attn/kv_write` (the cache write), `attn/latent` (scores, softmax and
# weighted sum over latent pages), `attn/flash` (the flash-attention kernels
# of a training step, forward and backward: `kernels/flash_attention.SCOPE`),
# `mlp/router`, `mlp/experts`, `mlp/shared` (an expert layer's parts),
# `head/sample` (the sampler).
SCOPES = ("embed", "attn", "mlp", "head", "optimizer")

# span record ring entry: (ph, name, t0, t1, tid, trace_id, attrs)
#   ph: "X" complete span | "i" instant event
#   t0/t1: time.perf_counter() seconds (t1 == t0 for instants)
#   tid: integer track id (thread track or per-trace request track)
#   trace_id: int or None (freestanding spans)
#   attrs: dict or None
_PH_SPAN = "X"
_PH_INSTANT = "i"

# request/trace tracks live far above thread tracks so the two ranges
# can never collide in the viewer
_TRACE_TID_BASE = 1 << 20

# trace ids carry a pid-derived salt in their high bits: ids minted by
# different processes of one fleet (router, N replicas) must never
# collide, because the cross-shard stitcher (tools/trace_report.py
# --stitch) joins rank shards on trace_id alone
_ID_SEQ_BITS = 20
_ID_SALT = (os.getpid() & 0xFFFF) << _ID_SEQ_BITS

_clock = time.perf_counter


def _flags():
    from ..framework import config as _config

    return _config


def sample_rate() -> float:
    try:
        return float(_flags().get_flag("FLAGS_trace_sample", 0.0))
    except (TypeError, ValueError):
        return 0.0


def slow_ms() -> float:
    try:
        return float(_flags().get_flag("FLAGS_trace_slow_ms", 0.0))
    except (TypeError, ValueError):
        return 0.0


def enabled() -> bool:
    """One flag read — the whole cost of tracing when it is off."""
    return sample_rate() > 0.0


# ---------------------------------------------------------------------------
# no-op singletons (the FLAGS_trace_sample=0 fast path allocates nothing)
# ---------------------------------------------------------------------------


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def end(self, **attrs):
        return None


NOOP_SPAN = _NoopSpan()


class _NoopTrace:
    __slots__ = ()
    trace_id = None
    sampled = False
    marks: dict = {}

    def span(self, name, **attrs):
        return NOOP_SPAN

    def begin(self, name, **attrs):
        return NOOP_SPAN

    def end(self, name, **attrs):
        return None

    def emit(self, name, t0, t1, **attrs):
        return None

    def instant(self, name, **attrs):
        return None

    def mark(self, key, value):
        return None

    def finish(self, **attrs):
        return None


NOOP_TRACE = _NoopTrace()


# ---------------------------------------------------------------------------
# cross-process trace context (inject / extract)
# ---------------------------------------------------------------------------
#
# A routed request's timeline spans processes: router.queue/route live
# in the router's ring, serving.queue/prefill/decode in the replica's,
# and a disaggregated request's decode in a THIRD engine. The compact
# context below is the shared identity: trace_id (pid-salted, so the
# stitcher can join shards on it), the parent span name, and the
# sampling verdict. The verdict is decided ONCE, where the request
# enters the fleet (the router): sampled-at-router stays sampled on
# every hop, and an unsampled request never leaves orphan fragments on
# some shards but not others.
#
# Wire format (the X-PT-Trace header): "<trace_id hex>-<0|1>-<parent>".
# Transport: Router/HttpReplica send it on POST /v1/generate; the
# telemetry httpd parks the raw header on the handler thread
# (set_pending) and the route handler adopts it with extract();
# KVHandoff carries it across the prefill->decode detach/attach
# boundary (inference/serving.py).

TRACE_HEADER = "X-PT-Trace"


class TraceContext:
    """The propagated identity of one distributed trace."""

    __slots__ = ("trace_id", "span", "sampled")

    def __init__(self, trace_id: int, span: Optional[str],
                 sampled: bool):
        self.trace_id = int(trace_id)
        self.span = span or None
        self.sampled = bool(sampled)

    def header(self) -> str:
        return (f"{self.trace_id:x}-{1 if self.sampled else 0}-"
                f"{self.span or ''}")

    def __repr__(self):
        return (f"TraceContext(trace_id={self.trace_id}, "
                f"span={self.span!r}, sampled={self.sampled})")


_tls = threading.local()


def inject(trace) -> Optional[str]:
    """The trace's context as a header value, or None for a no-op /
    finished-anonymous trace (callers skip the header entirely —
    downstream then samples on its own, exactly as before)."""
    trace_id = getattr(trace, "trace_id", None)
    if trace_id is None:
        return None
    return TraceContext(int(trace_id), getattr(trace, "name", None),
                        bool(getattr(trace, "sampled", False))).header()


def parse_context(header) -> Optional[TraceContext]:
    """Header value -> TraceContext, or None on anything malformed (a
    bad header degrades to an unlinked local trace, never an error)."""
    if not header or not isinstance(header, str):
        return None
    parts = header.split("-", 2)
    if len(parts) < 2:
        return None
    try:
        trace_id = int(parts[0], 16)
    except ValueError:
        return None
    return TraceContext(trace_id, parts[2] if len(parts) > 2 else None,
                        parts[1] == "1")


def set_pending(header: Optional[str]):
    """Park a raw inbound header on this thread (observability/httpd.py
    calls this before dispatching a route handler); the handler adopts
    it with extract(). One thread-local store — no parsing until a
    handler asks."""
    _tls.pending = header


def extract(header: Optional[str] = None) -> Optional[TraceContext]:
    """Adopt an inbound trace context as THIS thread's current context:
    parses `header` (or the pending header httpd parked here) and
    installs it, so every start_trace() on this thread joins the
    inherited timeline. Returns the context, or None (no/invalid
    header, or tracing off — one flag read, nothing allocated)."""
    if not enabled():
        return None
    if header is None:
        header = getattr(_tls, "pending", None)
    ctx = parse_context(header)
    _tls.ctx = ctx
    return ctx


def current_context() -> Optional[TraceContext]:
    return getattr(_tls, "ctx", None)


def set_current(ctx: Optional[TraceContext]) -> Optional[TraceContext]:
    """Install `ctx` as this thread's context; returns the previous one
    (in-process transports bracket a call with set_current/restore)."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


def clear_context():
    """Drop this thread's context AND pending header (httpd calls this
    after every handled request so a pooled handler thread never leaks
    one request's identity into the next)."""
    _tls.ctx = None
    _tls.pending = None


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class _OpenSpan:
    """An in-flight span: context manager AND explicit-`end()` handle.

    Registered with its tracer while open so the watchdog stall dump can
    report "hung 41 s inside serving.prefill" (`open_spans()`)."""

    __slots__ = ("_tracer", "_trace", "name", "t0", "attrs", "tid",
                 "_thread", "_done")

    def __init__(self, tracer, trace, name, tid, attrs):
        self._tracer = tracer
        self._trace = trace
        self.name = name
        self.t0 = _clock()
        self.attrs = attrs or None
        self.tid = tid
        self._thread = threading.current_thread().name
        self._done = False
        tracer._open[id(self)] = self

    def set(self, **attrs):
        """Attach attributes discovered mid-span."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def end(self, **attrs):
        if self._done:
            return
        self._done = True
        if attrs:
            self.set(**attrs)
        self._tracer._open.pop(id(self), None)
        rec = (_PH_SPAN, self.name, self.t0, _clock(), self.tid,
               self._trace.trace_id if self._trace is not None else None,
               self.attrs)
        if self._trace is not None:
            self._trace._spans.append(rec)
        else:
            self._tracer._ring.append(rec)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.set(error=repr(exc) if exc is not None
                     else exc_type.__name__)
        self.end()
        return False


class Trace:
    """One logical timeline (request / train step): spans buffer here and
    commit to the ring at `finish()` if the head-sampling decision said
    keep — or if the trace turned out slow (`FLAGS_trace_slow_ms`)."""

    __slots__ = ("_tracer", "trace_id", "sampled", "t0", "_spans",
                 "_tid", "marks", "name", "_finished")

    def __init__(self, tracer, trace_id, sampled, name, own_track, attrs):
        self._tracer = tracer
        self.trace_id = trace_id
        self.sampled = sampled
        self.name = name
        self.t0 = _clock()
        self._spans: List[tuple] = []
        self._tid = (_TRACE_TID_BASE + trace_id) if own_track \
            else tracer._thread_tid()
        self.marks: Dict[str, float] = {}
        self._finished = False
        if attrs:
            self._spans.append((_PH_INSTANT, name or "trace.start",
                                self.t0, self.t0, self._tid, trace_id,
                                dict(attrs)))

    def span(self, name, **attrs):
        """Synchronous child span (context manager)."""
        self._tracer.spans_created += 1
        return _OpenSpan(self._tracer, self, name, self._tid,
                         attrs or None)

    def begin(self, name, **attrs):
        """Open an async phase; close with the handle's `.end()` or
        `trace.end(name)` from another call frame."""
        return self.span(name, **attrs)

    def end(self, name, **attrs):
        """Close the most recent still-open span named `name` (async
        phases whose begin handle wasn't threaded through)."""
        for sp in reversed(list(self._tracer._open.values())):
            if sp._trace is self and sp.name == name:
                sp.end(**attrs)
                return
        return None

    def emit(self, name, t0, t1, **attrs):
        """Record a completed span with explicit endpoints (phases timed
        by the caller, e.g. one batched prefill shared by N requests)."""
        self._tracer.spans_created += 1
        self._spans.append((_PH_SPAN, name, t0, t1, self._tid,
                            self.trace_id, attrs or None))

    def instant(self, name, **attrs):
        """Zero-duration annotation (preempt / abort / first-token)."""
        self._tracer.spans_created += 1
        now = _clock()
        self._spans.append((_PH_INSTANT, name, now, now, self._tid,
                            self.trace_id, attrs or None))

    def mark(self, key, value):
        """Stash a timestamp/value on the trace (e.g. decode start)."""
        self.marks[key] = value

    def finish(self, **attrs):
        """Commit (or drop) the buffered timeline. Returns the total
        trace duration in seconds."""
        if self._finished:
            return None
        self._finished = True
        # close any span left open (error paths) so nothing leaks in
        # the watchdog's open-span registry
        for sp in list(self._tracer._open.values()):
            if sp._trace is self:
                sp.end(unclosed=True)
        now = _clock()
        total = now - self.t0
        threshold = slow_ms()
        slow = threshold > 0.0 and total * 1e3 >= threshold
        if slow:
            self._tracer._slow_counter().inc()
        if self.sampled or slow:
            if attrs or slow:
                a = dict(attrs) if attrs else {}
                if slow:
                    a["slow"] = True
                a["total_s"] = round(total, 6)
                self._spans.append((_PH_SPAN, self.name or "trace",
                                    self.t0, now, self._tid,
                                    self.trace_id, a))
            for rec in self._spans:
                self._tracer._ring.append(rec)
        self._spans = []
        return total


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------


class Tracer:
    """Bounded ring of committed spans + sampling + Chrome export."""

    def __init__(self, capacity: int = 16384,
                 registry: Optional[_metrics.Registry] = None):
        self._ring = deque(maxlen=int(capacity))
        self._open: Dict[int, _OpenSpan] = {}
        self._lock = threading.Lock()
        self._thread_tids: Dict[int, int] = {}
        self._thread_names: Dict[int, str] = {}
        self._next_trace_id = 0
        # deterministic head-sampling accumulator: take a trace whenever
        # the running sum of the sample rate crosses an integer — exact
        # at rate 1, rate-accurate (not RNG-flaky) below it
        self._sample_acc = 0.0
        # every Span/Trace object minted (the FLAGS_trace_sample=0
        # alloc-guard asserts this stays flat, like Registry.allocations)
        self.spans_created = 0
        self._registry = registry
        self._slow_cache: Optional[_metrics.HandleCache] = None

    # -- sampling ----------------------------------------------------------

    def sample(self) -> bool:
        rate = sample_rate()
        if rate <= 0.0:
            return False
        if rate >= 1.0:
            return True
        with self._lock:
            self._sample_acc += rate
            if self._sample_acc >= 1.0:
                self._sample_acc -= 1.0
                return True
        return False

    def _slow_counter(self):
        if self._registry is not None:
            return self._registry.counter(
                "trace_slow_requests_total",
                "Traces whose total latency crossed FLAGS_trace_slow_ms "
                "(committed to the trace ring even when head sampling "
                "dropped them).")
        if self._slow_cache is None:
            self._slow_cache = _metrics.HandleCache(
                lambda reg: reg.counter(
                    "trace_slow_requests_total",
                    "Traces whose total latency crossed "
                    "FLAGS_trace_slow_ms (committed to the trace ring "
                    "even when head sampling dropped them)."))
        return self._slow_cache.get()

    # -- track bookkeeping -------------------------------------------------

    def _thread_tid(self) -> int:
        ident = threading.get_ident()
        tid = self._thread_tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._thread_tids.get(ident)
                if tid is None:
                    tid = len(self._thread_tids) + 1
                    self._thread_tids[ident] = tid
                    self._thread_names[tid] = \
                        threading.current_thread().name
        return tid

    # -- recording ---------------------------------------------------------

    def start_trace(self, name: str = "trace", own_track: bool = False,
                    parent=None, **attrs):
        """Begin a logical timeline; head sampling decides retention NOW.
        Returns NOOP_TRACE (not None — callers never branch) when
        tracing is off, or when the trace is unsampled and the slow
        escape hatch is disabled (nothing could ever commit it).

        `parent` (a TraceContext, or the thread's extract()-installed
        context when omitted) makes this trace a HOP of a distributed
        one: it adopts the inherited trace_id and the inherited
        sampling verdict — decided once where the request entered the
        fleet — instead of minting/sampling its own."""
        if not enabled():
            return NOOP_TRACE
        ctx = parent if parent is not None else current_context()
        if ctx is not None:
            if not ctx.sampled and slow_ms() <= 0.0:
                return NOOP_TRACE
            if ctx.span:
                attrs.setdefault("parent", ctx.span)
            self.spans_created += 1
            return Trace(self, int(ctx.trace_id), bool(ctx.sampled),
                         name, own_track, attrs)
        sampled = self.sample()
        if not sampled and slow_ms() <= 0.0:
            return NOOP_TRACE
        with self._lock:
            trace_id = _ID_SALT | (self._next_trace_id
                                   & ((1 << _ID_SEQ_BITS) - 1))
            self._next_trace_id += 1
        self.spans_created += 1
        return Trace(self, trace_id, sampled, name, own_track, attrs)

    def span(self, name, **attrs):
        """Freestanding synchronous span on the calling thread's track
        (control-plane phases: checkpoint saves, collective calls). Committed whenever tracing is enabled — these
        are low-rate and always worth keeping."""
        if not enabled():
            return NOOP_SPAN
        self.spans_created += 1
        return _OpenSpan(self, None, name, self._thread_tid(),
                         attrs or None)

    def emit(self, name, t0, t1, **attrs):
        """Freestanding completed span with explicit endpoints."""
        if not enabled():
            return
        self.spans_created += 1
        self._ring.append((_PH_SPAN, name, t0, t1, self._thread_tid(),
                           None, attrs or None))

    def instant(self, name, **attrs):
        if not enabled():
            return
        self.spans_created += 1
        now = _clock()
        self._ring.append((_PH_INSTANT, name, now, now,
                           self._thread_tid(), None, attrs or None))

    # -- introspection -----------------------------------------------------

    def open_spans(self) -> List[Tuple[str, str, float]]:
        """(thread_name, span_name, elapsed_s) for every in-flight span,
        oldest first — the watchdog appends this to its stall dump."""
        now = _clock()
        out = [(sp._thread, sp.name, now - sp.t0)
               for sp in list(self._open.values())]
        out.sort(key=lambda r: -r[2])
        return out

    def __len__(self):
        return len(self._ring)

    def clear(self):
        self._ring.clear()
        self._open.clear()

    # -- export ------------------------------------------------------------

    def to_chrome_trace(self, pid: Optional[int] = None,
                        since_s: Optional[float] = None) -> List[dict]:
        """The ring as a Chrome trace-event ARRAY (the JSON Array Format
        both Perfetto and chrome://tracing load directly). Stable field
        set per event: name/cat/ph/ts/dur/pid/tid/args ("X"), instants
        drop dur and add s (scope).

        `pid` defaults to the OS pid; the fleet exporter passes the RANK
        instead, so merged multi-rank traces render one process lane per
        rank in the viewer (fleet.py).

        `since_s` keeps only spans that ENDED within the trailing
        window — the /debug/trace?secs=N on-demand capture
        (observability/httpd.py) downloads the last N seconds of the
        ring without draining it."""
        pid = os.getpid() if pid is None else int(pid)
        recs = list(self._ring)
        if since_s is not None:
            cutoff = _clock() - float(since_s)
            recs = [r for r in recs if r[3] >= cutoff]
        events: List[dict] = []
        seen_tids = set()
        for ph, name, t0, t1, tid, trace_id, attrs in recs:
            args = dict(attrs) if attrs else {}
            if trace_id is not None:
                args.setdefault("trace_id", trace_id)
            ev = {
                "name": name,
                "cat": name.split(".", 1)[0],
                "ph": ph,
                "ts": round(t0 * 1e6, 3),
                "pid": pid,
                "tid": int(tid),
                "args": args,
            }
            if ph == _PH_SPAN:
                ev["dur"] = round(max(t1 - t0, 0.0) * 1e6, 3)
            else:
                ev["s"] = "t"
            events.append(ev)
            seen_tids.add(int(tid))
        events.sort(key=lambda e: e["ts"])
        meta: List[dict] = []
        for tid in sorted(seen_tids):
            if tid >= _TRACE_TID_BASE:
                tname = f"req/{tid - _TRACE_TID_BASE}"
            else:
                tname = self._thread_names.get(tid, f"thread-{tid}")
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": tname}})
        return meta + events

    def write_trace(self, path: str, pid: Optional[int] = None) -> int:
        """Atomically write the Chrome trace JSON; returns the number of
        non-metadata events written."""
        events = self.to_chrome_trace(pid=pid)
        _metrics.atomic_write(path, json.dumps(events, indent=0))
        return sum(1 for e in events if e["ph"] != "M")


# ---------------------------------------------------------------------------
# process-global default tracer + module-level convenience API
# ---------------------------------------------------------------------------

_default = Tracer()


def default_tracer() -> Tracer:
    return _default


def set_default_tracer(tracer: Tracer) -> Tracer:
    """Swap the process default (tests); returns the previous one."""
    global _default
    prev = _default
    _default = tracer
    return prev


def start_trace(name: str = "trace", own_track: bool = False,
                parent=None, **attrs):
    return _default.start_trace(name, own_track=own_track,
                                parent=parent, **attrs)


def span(name, **attrs):
    return _default.span(name, **attrs)


def emit(name, t0, t1, **attrs):
    return _default.emit(name, t0, t1, **attrs)


def instant(name, **attrs):
    return _default.instant(name, **attrs)


class _Phase:
    """One host phase: a profiler annotation, and a ring span when
    tracing is enabled."""

    __slots__ = ("_annotation", "name", "attrs", "_t0")

    def __init__(self, name, attrs):
        self._annotation = TraceAnnotation(name, **attrs)
        self.name = name
        self.attrs = attrs
        self._t0 = None

    def __enter__(self):
        self._annotation.__enter__()
        if enabled():
            self._t0 = _clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        if self._t0 is not None:
            _default.emit(self.name, self._t0, _clock(), **self.attrs)
        return False


def phase(name, **attrs):
    """`with phase("serving.admit"): ...` — see the module docstring.
    Attribute values are numbers or short strings; give only those that
    something reads."""
    return _Phase(name, attrs)


def mark(name, seconds=None, **attrs):
    """A moment on the phases' timeline (a request got its slot), or
    something that ENDED now and took `seconds` (a compile that a
    listener reports afterwards): a zero-length annotation carrying
    `seconds`, and in the ring the interval itself."""
    if seconds is not None:
        attrs["seconds"] = seconds
    with TraceAnnotation(name, **attrs):
        pass
    if enabled():
        now = _clock()
        _default.emit(name, now - max(seconds or 0.0, 0.0), now, **attrs)


# -- counts a compiled program makes of its own work --------------------------
# A model `count`s a traced integer (token-expert pairs of an expert layer)
# while the engine, tracing its decode step, collects: the sums ride out of
# the program beside the tokens and the engine puts them on the phase that
# follows the step's one read. Outside a `device_counts()` nothing collects
# and `count` drops its value, so a program nobody asks compiles unchanged.

_collecting = threading.local()


class device_counts:
    """`with device_counts() as counts:` — `counts[name]` is the sum of
    every `count(name, value)` made while the body was traced."""

    def __enter__(self):
        self._outer = getattr(_collecting, "counts", None)
        _collecting.counts = counts = {}
        return counts

    def __exit__(self, exc_type, exc, tb):
        _collecting.counts = self._outer
        return False


def counting() -> bool:
    return getattr(_collecting, "counts", None) is not None


def count(name, value):
    counts = getattr(_collecting, "counts", None)
    if counts is not None:
        counts[name] = counts[name] + value if name in counts else value


def open_spans():
    return _default.open_spans()


def to_chrome_trace(pid: Optional[int] = None,
                    since_s: Optional[float] = None):
    return _default.to_chrome_trace(pid=pid, since_s=since_s)


def write_trace(path: str, pid: Optional[int] = None) -> int:
    return _default.write_trace(path, pid=pid)
