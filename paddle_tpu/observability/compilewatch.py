"""Compile observability: per-callable compile accounting + recompile-
storm detection (README.md "Memory & compile observability", fifth
telemetry channel).

XLA compiles are the silent tax of a jit runtime: a shape that misses
the executable cache stalls the caller for seconds, and a callable fed
unbucketed shapes recompiles forever — the pathology shape buckets
exist to prevent, yet nothing reported WHERE compiles were happening. This module wraps the repo's jit entry points
(`jit/api.py` StaticFunction + train_step, the serving prefill/decode/
burst programs) and reports:

- **Compile counts + time per callable**: a listener on jax's
  `/jax/core/compile/backend_compile_duration` monitoring event
  attributes every real backend compile to the wrapped callable that
  triggered it (`compilewatch_compiles_total{callable}` /
  `compilewatch_compile_seconds_total{callable}`), and puts a
  `compile.<name>` mark on the phases' timeline (`tracing.mark`: the
  profiler's clock, and the ring when tracing is on) — compiles land on
  the same timeline as the steps they stall. Flag or no flag, the same
  listener marks every build `jit.build` with its `kind`: `compile`, or
  `cache_load` when the persistent cache answered (a retrace that HITS
  the cache still costs the caller its tracing and loading time, and
  adds no file for a cache census to count).

- **Shape-signature tracking**: each wrapped call records an abstract
  signature (shape/dtype of array leaves + static values — the same
  keying jax's executable cache uses), so the storm report can CITE the
  offending argument shapes, not just count misses.

- **Recompile storms**: after a callable's warmup mark
  (`mark_warmup_done(prefix)` — the serving engine marks `serving.` at
  the end of `warmup()`), every further compile is a RECOMPILE
  (`compilewatch_recompiles_total{callable}`); more than
  `FLAGS_compilewatch_storm_shapes` distinct post-warmup signatures is
  a storm: `compilewatch_storms_total` bumps, a `compilewatch.storm`
  breadcrumb lands in the flight-recorder ring, and `storm_report()`
  names the callable and its shapes (churning shapes belong in a
  bucket, not the jit cache). `tools/ci.sh` gates the traced serving smoke on ZERO
  decode recompiles after warmup.

Zero-overhead contract: with `FLAGS_compilewatch` off, a wrapped call
is ONE flag read and a tail call — no signature walk, no allocations
(`CompileWatch.events` stays flat; pinned by
tests/test_compilewatch.py, the tracing alloc-guard discipline).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

from . import metrics as _metrics
from . import tracing as _tracing

_MAX_SIGS_PER_CALLABLE = 64  # bounded: a storm must not become a leak


def _flags():
    from ..framework import config as _config

    return _config


def enabled() -> bool:
    """One flag read — the whole per-call cost when compilewatch is
    off."""
    return bool(_flags().get_flag("FLAGS_compilewatch", False))


def storm_threshold() -> int:
    try:
        v = int(_flags().get_flag("FLAGS_compilewatch_storm_shapes", 4))
        return v if v > 0 else 4
    except (TypeError, ValueError):
        return 4


# ---------------------------------------------------------------------------
# shape signatures
# ---------------------------------------------------------------------------


def _sig_of(obj, out: List[str], budget: List[int]):
    """Append the abstract signature of one argument subtree. Arrays
    contribute dtype[shape] (the jit cache key's array part); plain
    values contribute their repr (static args retrace on change);
    containers recurse. `budget` caps the walk on pathological trees."""
    if budget[0] <= 0:
        return
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        budget[0] -= 1
        out.append(f"{dtype}[{','.join(str(int(s)) for s in shape)}]")
        return
    data = getattr(obj, "_data", None)  # paddle Tensor
    if data is not None and hasattr(data, "shape"):
        _sig_of(data, out, budget)
        return
    if isinstance(obj, dict):
        for k in sorted(obj, key=str):
            _sig_of(obj[k], out, budget)
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _sig_of(o, out, budget)
        return
    budget[0] -= 1
    try:
        out.append(repr(obj)[:48])
    except Exception:  # noqa: BLE001
        out.append("<?>")


def signature(args, kwargs=None, tag=None) -> tuple:
    """The hashable shape signature of a call. `tag` distinguishes
    sibling program variants sharing one callable name (e.g. the
    all-greedy decode specialization)."""
    out: List[str] = []
    budget = [4096]
    _sig_of(args, out, budget)
    if kwargs:
        _sig_of(kwargs, out, budget)
    return (tag,) + tuple(out) if tag is not None else tuple(out)


def format_sig(sig: tuple, limit: int = 6) -> str:
    """Compact human form of a signature — the storm report's shape
    citation (first `limit` array entries, count of the rest)."""
    arrays = [s for s in sig if isinstance(s, str) and "[" in s]
    shown = ", ".join(arrays[:limit])
    more = len(arrays) - limit
    return shown + (f", +{more} more" if more > 0 else "") \
        if arrays else "(no array args)"


# ---------------------------------------------------------------------------
# the watch
# ---------------------------------------------------------------------------


def _make_handles(reg):
    return {
        "compiles": reg.counter(
            "compilewatch_compiles_total",
            "XLA backend compiles attributed to each watched callable "
            "(populated when FLAGS_compilewatch is on).",
            labels=("callable",)),
        "compile_s": reg.counter(
            "compilewatch_compile_seconds_total",
            "Wall seconds spent inside XLA backend compilation, by "
            "watched callable.", labels=("callable",)),
        "recompiles": reg.counter(
            "compilewatch_recompiles_total",
            "Compiles AFTER the callable's warmup mark — in-traffic "
            "compiles the warmup was supposed to prepay.",
            labels=("callable",)),
        "storms": reg.counter(
            "compilewatch_storms_total",
            "Recompile storms detected: a callable compiled for more "
            "than FLAGS_compilewatch_storm_shapes distinct argument-"
            "shape signatures after warmup (see storm_report()).",
            labels=("callable",)),
    }


class _Record:
    __slots__ = ("name", "compiles", "recompiles", "compile_s",
                 "warmup_done", "sigs", "post_sigs", "storm")

    def __init__(self, name: str, warmup_done: bool):
        self.name = name
        self.compiles = 0
        self.recompiles = 0
        self.compile_s = 0.0
        self.warmup_done = warmup_done
        self.sigs: Dict[tuple, int] = {}       # sig -> calls seen
        self.post_sigs: Dict[tuple, int] = {}  # sig -> compiles after mark
        self.storm = False


class CompileWatch:
    """Per-callable compile accounting. One instance per process
    (`default_watch()`); tests inject fresh ones via
    `_reset_for_tests()`."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: Dict[str, _Record] = {}
        self._warm_prefixes: List[str] = []
        self._tls = threading.local()
        # every record/sig allocation — the off-path guard asserts this
        # stays flat (Registry.allocations discipline)
        self.events = 0
        self._handles: Optional[_metrics.HandleCache] = None

    # -- handles -----------------------------------------------------------

    def _h(self):
        if self._handles is None:
            self._handles = _metrics.HandleCache(_make_handles)
        return self._handles.get()

    def _record(self, name: str) -> _Record:
        rec = self._records.get(name)
        if rec is None:
            with self._lock:
                rec = self._records.get(name)
                if rec is None:
                    warm = any(name.startswith(p)
                               for p in self._warm_prefixes)
                    rec = self._records[name] = _Record(name, warm)
                    self.events += 1
        return rec

    # -- call attribution --------------------------------------------------

    def call(self, name: str, sig: Optional[tuple] = None):
        """Context manager naming the callable about to dispatch; any
        backend compile that fires inside is attributed to `name` (and,
        when `sig` is given, cited with these argument shapes)."""
        return _CallCtx(self, name, sig)

    def _current(self):
        return getattr(self._tls, "ctx", None)

    def observe_compile(self, dur_s: float):
        """One backend compile just finished (monitoring listener).
        Attributes it to the innermost active call context on this
        thread; unattributed compiles (jax internals outside any
        watched entry point) are ignored."""
        ctx = self._current()
        if ctx is None:
            return
        name, sig = ctx
        rec = self._record(name)
        rec.compiles += 1
        rec.compile_s += float(dur_s)
        self.events += 1
        h = self._h()
        h["compiles"].labels(name).inc()
        h["compile_s"].labels(name).inc(max(float(dur_s), 0.0))
        _tracing.mark(f"compile.{name}", seconds=float(dur_s),
                      sig=format_sig(sig) if sig else "")
        if rec.warmup_done:
            rec.recompiles += 1
            h["recompiles"].labels(name).inc()
            key = sig if sig is not None else ("<unsigned>",)
            if len(rec.post_sigs) < _MAX_SIGS_PER_CALLABLE or \
                    key in rec.post_sigs:
                rec.post_sigs[key] = rec.post_sigs.get(key, 0) + 1
            from . import flight_recorder as _flight

            _flight.record_event("compilewatch.recompile", callable=name,
                                 sig=format_sig(key),
                                 post_warmup_sigs=len(rec.post_sigs))
            if not rec.storm and \
                    len(rec.post_sigs) > storm_threshold():
                rec.storm = True
                h["storms"].labels(name).inc()
                _flight.record_event(
                    "compilewatch.storm", callable=name,
                    distinct_shapes=len(rec.post_sigs),
                    report=self.storm_report(name))

    # -- warmup ------------------------------------------------------------

    def mark_warmup_done(self, prefix: str = ""):
        """Declare warmup over for every callable whose name starts with
        `prefix` ("" = all): further compiles are in-traffic recompiles.
        Callables first seen AFTER the mark inherit it — a program that
        never compiled during warmup is exactly an in-traffic compile."""
        with self._lock:
            if prefix not in self._warm_prefixes:
                self._warm_prefixes.append(prefix)
            for rec in self._records.values():
                if rec.name.startswith(prefix):
                    rec.warmup_done = True

    # -- introspection -----------------------------------------------------

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {
                name: {
                    "compiles": rec.compiles,
                    "recompiles": rec.recompiles,
                    "compile_s": round(rec.compile_s, 6),
                    "warmup_done": rec.warmup_done,
                    "distinct_sigs": len(rec.sigs),
                    "post_warmup_sigs": [
                        {"sig": format_sig(s), "compiles": c}
                        for s, c in rec.post_sigs.items()],
                    "storm": rec.storm,
                }
                for name, rec in sorted(self._records.items())
            }

    def total_compiles(self) -> int:
        return sum(r.compiles for r in self._records.values())

    def recompiles(self, prefix: str = "") -> int:
        return sum(r.recompiles for r in self._records.values()
                   if r.name.startswith(prefix))

    def storms(self) -> List[str]:
        return sorted(n for n, r in self._records.items() if r.storm)

    def storm_report(self, name: Optional[str] = None) -> str:
        """The named recompile-storm report: which callable, how many
        distinct post-warmup shapes, and the offending signatures,
        with a pointer to shape buckets, which absorb shape churn."""
        names = [name] if name else (self.storms() or
                                     sorted(self._records))
        lines = []
        for n in names:
            rec = self._records.get(n)
            if rec is None or not rec.post_sigs:
                continue
            lines.append(
                f"RECOMPILE STORM: {n} compiled for "
                f"{len(rec.post_sigs)} distinct argument-shape "
                f"signature(s) AFTER warmup "
                f"(threshold {storm_threshold()}, "
                f"{rec.recompiles} recompiles, "
                f"{rec.compile_s:.3f}s compiling):")
            for sig, c in sorted(rec.post_sigs.items(),
                                 key=lambda kv: -kv[1])[:10]:
                lines.append(f"  {c}x  {format_sig(sig)}")
        if lines:
            lines.append(
                "hint: churning shapes belong in a shape bucket, not "
                "the jit cache — pad/bucket the offending dims (next "
                "power of two, serving's page-multiple prefill "
                "buckets) so one compiled "
                "program serves the whole family.")
        return "\n".join(lines) + ("\n" if lines else "")

    def _reset(self):
        with self._lock:
            self._records.clear()
            self._warm_prefixes.clear()
            self.events = 0
            self._handles = None


class _CallCtx:
    """Thread-local (name, sig) attribution frame; nests (innermost
    wins)."""

    __slots__ = ("_watch", "_name", "_sig", "_prev")

    def __init__(self, watch: CompileWatch, name: str,
                 sig: Optional[tuple]):
        self._watch = watch
        self._name = name
        self._sig = sig

    def __enter__(self):
        w = self._watch
        self._prev = getattr(w._tls, "ctx", None)
        w._tls.ctx = (self._name, self._sig)
        if self._sig is not None:
            rec = w._record(self._name)
            if self._sig not in rec.sigs and \
                    len(rec.sigs) < _MAX_SIGS_PER_CALLABLE:
                rec.sigs[self._sig] = 0
                w.events += 1
            if self._sig in rec.sigs:
                rec.sigs[self._sig] += 1
        return self

    def __exit__(self, *exc):
        self._watch._tls.ctx = self._prev
        return False


# ---------------------------------------------------------------------------
# the jax monitoring listener (registered once, where the jit entry
# points are made)
# ---------------------------------------------------------------------------

_listener_lock = threading.Lock()
_listener_on = False
_listener_tls = threading.local()
# jax 0.9.0 (pxla.py) times `compile_or_get_cached` as a whole under the
# first name, load or compile; inside it, compiler.py reports the second
# only when the persistent cache held the program
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_event_duration(event: str, duration_secs: float, **_kw):
    if event == _CACHE_LOAD_EVENT:
        _listener_tls.loaded = True
        return
    if event != _COMPILE_EVENT:
        return
    loaded = getattr(_listener_tls, "loaded", False)
    _listener_tls.loaded = False
    try:
        _tracing.mark("jit.build", seconds=float(duration_secs),
                      kind="cache_load" if loaded else "compile")
        if enabled():
            _watch.observe_compile(duration_secs)
    except Exception:  # noqa: BLE001 — telemetry must never take a
        pass           # compile (or the caller) down


def ensure_listener():
    """Register the compile-event listener (idempotent): `jit/api.py`
    at import and `watch_jit` for every serving program, flag or no
    flag, since `jit.build` is marked either way."""
    global _listener_on
    if _listener_on:
        return
    with _listener_lock:
        if _listener_on:
            return
        try:
            from jax._src import monitoring as _mon

            _mon.register_event_duration_secs_listener(_on_event_duration)
            _listener_on = True
        except Exception:  # noqa: BLE001 — no monitoring on this jax:
            _listener_on = True  # degrade to signature-only tracking


# ---------------------------------------------------------------------------
# module-level API
# ---------------------------------------------------------------------------

_watch = CompileWatch()


def default_watch() -> CompileWatch:
    return _watch


def call(name: str, sig: Optional[tuple] = None):
    """Attribution context for a dispatch region (a StaticFunction
    program call). No-op singleton when off."""
    if not enabled():
        return _NOOP_CTX
    ensure_listener()
    return _watch.call(name, sig)


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_CTX = _NoopCtx()


class _WatchedJit:
    """Callable proxy over a jitted function: every call records its
    shape signature and attributes any compile it triggers to `name`.
    The off path is one flag read + a tail call. Attribute access
    (`lower`, `eval_shape`, ...) delegates to the wrapped jit object —
    AOT users like tools/serving_rehearsal.py keep working."""

    __slots__ = ("__wrapped__", "_name", "_tag")

    def __init__(self, name, fn, tag):
        self.__wrapped__ = fn
        self._name = name
        self._tag = tag

    def __call__(self, *args, **kwargs):
        if not enabled():
            return self.__wrapped__(*args, **kwargs)
        with _watch.call(self._name,
                         signature(args, kwargs, tag=self._tag)):
            return self.__wrapped__(*args, **kwargs)

    def __getattr__(self, item):
        # only reached for attrs not on the proxy: jit surface passthrough
        return getattr(self.__wrapped__, item)

    def __repr__(self):
        return f"compilewatch[{self._name}]({self.__wrapped__!r})"


def watch_jit(name: str, fn, tag=None):
    """Wrap a jitted callable for per-callable compile attribution (see
    _WatchedJit)."""
    ensure_listener()
    return _WatchedJit(name, fn, tag)


def mark_warmup_done(prefix: str = ""):
    """No-op (one flag read) when off."""
    if enabled():
        _watch.mark_warmup_done(prefix)


def snapshot() -> Dict[str, dict]:
    return _watch.snapshot()


def total_compiles() -> int:
    return _watch.total_compiles()


def recompiles(prefix: str = "") -> int:
    return _watch.recompiles(prefix)


def storms() -> List[str]:
    return _watch.storms()


def storm_report(name: Optional[str] = None) -> str:
    return _watch.storm_report(name)


def events_created() -> int:
    return _watch.events


def _reset_for_tests():
    _watch._reset()
