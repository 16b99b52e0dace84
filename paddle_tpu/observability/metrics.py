"""Dependency-free runtime metrics registry (SURVEY.md §5 "Metrics /
logging": the reference exposes VisualDL scalars + benchmark flags; a
production serving/training stack needs process metrics it can scrape).

Design (prometheus-client shaped, zero deps):

- `Counter` / `Gauge` / `Histogram` value cells. Histograms default to
  fixed log-spaced latency buckets (100 µs … 60 s, a 1-2.5-5 ladder) so
  every latency series in the process is cross-comparable.
- Labeled families: `registry.counter(name, help, labels=("op",))`
  returns a family whose `.labels("all_reduce")` resolves (and caches) a
  child cell. Hot paths resolve children ONCE and then only touch plain
  float adds — the registry counts every family/child allocation in
  `registry.allocations` so tests can assert a loop allocates nothing.
- A process-global default registry (`default_registry()`), swappable
  and resettable for tests.
- Exporters: Prometheus text exposition (`to_prometheus()`) and JSONL
  snapshots (`write_jsonl()`), both pure functions of registry state.

Thread-safety: creation is locked; increments are plain float ops (GIL
atomic enough for monitoring — a torn read costs one scrape sample, not
correctness).
"""
from __future__ import annotations

import bisect
import json
import math
import os
import threading
import time
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

# fixed log-spaced latency ladder (seconds): 100us .. 60s in 1-2.5-5
# decades + the +Inf bucket implied at exposition time. ONE ladder for
# every latency histogram keeps dashboards cross-comparable.
LATENCY_BUCKETS: Tuple[float, ...] = (
    1e-4, 2.5e-4, 5e-4,
    1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2,
    1e-1, 2.5e-1, 5e-1,
    1.0, 2.5, 5.0, 10.0, 25.0, 60.0,
)


class Counter:
    """Monotonic float counter."""

    __slots__ = ("_value",)

    def __init__(self):
        self._value = 0.0

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError("counters only go up; use a Gauge")
        self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Settable value; optionally backed by a callback sampled at
    collection time (`set_function`)."""

    __slots__ = ("_value", "_fn")

    def __init__(self):
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None

    def set(self, value: float):
        self._value = float(value)

    def inc(self, amount: float = 1.0):
        self._value += amount

    def dec(self, amount: float = 1.0):
        self._value -= amount

    def set_function(self, fn: Callable[[], float]):
        self._fn = fn

    @property
    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return math.nan
        return self._value


class Histogram:
    """Fixed-bucket histogram: per-bucket counts + sum + count.

    Buckets are upper bounds (exclusive of +Inf, which is implied)."""

    __slots__ = ("buckets", "_counts", "_sum", "_count", "_ex")

    def __init__(self, buckets: Sequence[float] = LATENCY_BUCKETS):
        b = tuple(sorted(float(x) for x in buckets))
        if not b:
            raise ValueError("histogram needs at least one bucket bound")
        self.buckets = b
        self._counts = [0] * (len(b) + 1)  # trailing slot = +Inf
        self._sum = 0.0
        self._count = 0
        self._ex = None  # bucket idx -> (labels, value); lazy — None
        # until the first exemplared observe, so cells that never see
        # one (the common case) cost nothing extra

    def observe(self, value: float,
                exemplar: Optional[Dict[str, str]] = None):
        idx = bisect.bisect_left(self.buckets, value)
        self._counts[idx] += 1
        self._sum += value
        self._count += 1
        if exemplar:
            if self._ex is None:
                self._ex = {}
            self._ex[idx] = (dict(exemplar), float(value))

    def exemplars(self) -> Dict[int, tuple]:
        """Last (labels, observed value) per bucket index — what the
        exposition attaches as OpenMetrics `# {...} v` suffixes."""
        return dict(self._ex) if self._ex else {}

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def count(self) -> int:
        return self._count

    def state(self) -> Tuple[list, float, int]:
        """One consistent read for exporters: (per-bucket counts incl.
        the trailing +Inf slot, sum, count) with count DERIVED from the
        counts copy — a scrape concurrent with observe() can therefore
        never expose `_bucket{+Inf}` != `_count` (the torn read that
        makes strict exposition parsers reject a histogram). `observe`
        bumps the bucket slot before `_sum`/`_count`, so the copy is
        either fully pre- or post-increment per observation; `sum` may
        lag the counts by at most the in-flight observation — a float
        sample, not an invariant."""
        counts = list(self._counts)
        return counts, self._sum, sum(counts)

    def bucket_counts(self) -> Dict[float, int]:
        """CUMULATIVE counts keyed by upper bound (math.inf last) — the
        Prometheus exposition shape. Built from ONE `state()` copy so
        the cumulative series is monotone even mid-observe."""
        counts, _sum_, total = self.state()
        out = {}
        acc = 0
        for ub, c in zip(self.buckets, counts):
            acc += c
            out[ub] = acc
        out[math.inf] = total
        return out


_KINDS = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class _Family:
    """One named metric family: a fixed label schema and its children."""

    def __init__(self, registry, name: str, help_: str, kind: str,
                 labelnames: Tuple[str, ...], **kwargs):
        self.name = name
        self.help = help_
        self.kind = kind
        self.labelnames = labelnames
        self._registry = registry
        self._kwargs = kwargs
        self._children: Dict[Tuple[str, ...], object] = {}

    def labels(self, *values, **kv):
        if kv:
            if values:
                raise ValueError("pass label values positionally OR by "
                                 "keyword, not both")
            values = tuple(kv[n] for n in self.labelnames)
        key = tuple(str(v) for v in values)
        if len(key) != len(self.labelnames):
            raise ValueError(
                f"{self.name} takes labels {self.labelnames}, got {key}")
        child = self._children.get(key)
        if child is None:
            with self._registry._lock:
                child = self._children.get(key)
                if child is None:
                    child = _KINDS[self.kind](**self._kwargs)
                    self._children[key] = child
                    self._registry.allocations += 1
        return child

    def samples(self):
        # snapshot under the lock: a scrape must not race a hot path
        # minting its first child for a new label value
        with self._registry._lock:
            items = sorted(self._children.items())
        for key, child in items:
            yield dict(zip(self.labelnames, key)), child


class Registry:
    """Named metric families; create-or-get semantics so every subsystem
    can resolve its handles independently."""

    def __init__(self):
        from . import lockwatch as _lockwatch  # lazy: leaf module

        self._lock = _lockwatch.rlock("metrics.registry")
        self._families: Dict[str, _Family] = {}
        # counts every family AND child cell ever created — the
        # instrumentation-overhead tests assert a hot loop adds zero
        self.allocations = 0
        # bumped by reset(): library-internal handle caches key on
        # (id(registry), generation) to notice both swaps and resets
        self.generation = 0

    @property
    def lock(self):
        """The registry's creation RLock, exposed so a scrape can take
        the WHOLE exposition under it (observability/httpd.py /metrics):
        per-family locking already guarantees each family is internally
        consistent; holding the lock across families additionally pins
        cross-family consistency for the scrape's duration (an RLock, so
        same-thread family iteration inside stays reentrant)."""
        return self._lock

    def _get_or_create(self, name, help_, kind, labels, **kwargs):
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.labelnames != tuple(labels):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}{fam.labelnames}, not {kind}{labels}")
                if fam._kwargs != kwargs:
                    # e.g. a histogram re-registered with different
                    # buckets: silently returning the original would put
                    # observations in bounds the caller never asked for
                    raise ValueError(
                        f"metric {name!r} already registered with "
                        f"{fam._kwargs}, not {kwargs}")
                return fam if fam.labelnames else fam.labels()
            fam = _Family(self, name, help_, kind, tuple(labels), **kwargs)
            self._families[name] = fam
            self.allocations += 1
            return fam if fam.labelnames else fam.labels()

    def counter(self, name: str, help_: str = "",
                labels: Iterable[str] = ()):
        """Unlabeled: returns the Counter cell. Labeled: returns the
        family (resolve cells via .labels(...))."""
        return self._get_or_create(name, help_, "counter", tuple(labels))

    def gauge(self, name: str, help_: str = "", labels: Iterable[str] = ()):
        return self._get_or_create(name, help_, "gauge", tuple(labels))

    def histogram(self, name: str, help_: str = "",
                  labels: Iterable[str] = (),
                  buckets: Sequence[float] = LATENCY_BUCKETS):
        return self._get_or_create(name, help_, "histogram", tuple(labels),
                                   buckets=buckets)

    def families(self):
        with self._lock:
            return list(self._families.values())

    def get(self, name: str) -> Optional[_Family]:
        return self._families.get(name)

    def value(self, name: str, **labels) -> float:
        """Test/debug convenience: the current value of a counter/gauge
        (or a histogram's count) under the given labels."""
        fam = self._families[name]
        cell = fam.labels(**labels) if fam.labelnames else fam.labels()
        return cell.count if isinstance(cell, Histogram) else cell.value

    def reset(self):
        """Drop every family (tests). Handles resolved before a reset keep
        counting into detached cells — re-resolve after resetting."""
        with self._lock:
            self._families.clear()
            self.generation += 1


def rank_world() -> Tuple[int, int]:
    """This process's (rank, world_size) from the launch env contract
    (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM, distributed/launch).

    Env-only ON PURPOSE: telemetry must never be the thing that
    initializes the XLA backend (jax.process_index() would, and a later
    jax.distributed.initialize would then be impossible). Single-process
    jobs report (0, 1)."""
    try:
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0)
    except ValueError:
        rank = 0
    try:
        world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or 1)
    except ValueError:
        world = 1
    return rank, world


def fleet_labels() -> Dict[str, str]:
    """The constant labels stamped onto every exposition sample so shards
    from different ranks merge without collisions (fleet.py aggregator);
    single-rank exports carry rank="0"/world_size="1" and are therefore
    fleet-merge-ready too."""
    rank, world = rank_world()
    return {"rank": str(rank), "world_size": str(world)}


def registry_key(registry: Optional["Registry"] = None) -> tuple:
    """Cache key for library-internal metric handles: changes whenever
    the default registry is swapped OR reset, so lazy module-level
    caches (collective/dataloader/checkpoint) re-resolve instead of
    writing to a detached registry forever."""
    reg = registry or default_registry()
    return (id(reg), reg.generation)


class HandleCache:
    """Lazily-resolved metric handles for library-internal
    instrumentation: `get()` returns `factory(default_registry())`,
    re-invoking the factory whenever the default registry is swapped
    (set_default_registry) or reset — the ONE invalidation rule shared
    by the collective/dataloader/checkpoint caches. Steady-state cost:
    one registry_key() tuple compare."""

    __slots__ = ("_factory", "_key", "_handles")

    def __init__(self, factory):
        self._factory = factory
        self._key = None
        self._handles = None

    def get(self):
        key = registry_key()
        if self._key != key:
            self._handles = self._factory(default_registry())
            self._key = key
        return self._handles


# ---------------------------------------------------------------------------
# process-global default registry
# ---------------------------------------------------------------------------

_default = Registry()


def default_registry() -> Registry:
    return _default


def set_default_registry(registry: Registry) -> Registry:
    """Swap the process default (tests); returns the previous one."""
    global _default
    prev = _default
    _default = registry
    return prev


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _fmt_labels(labels: Dict[str, str], extra: str = "") -> str:
    parts = [f'{k}="{_escape(v)}"' for k, v in labels.items()]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _escape(v: str) -> str:
    return str(v).replace("\\", r"\\").replace('"', r"\"").replace(
        "\n", r"\n")


def _fmt_exemplar(ex) -> str:
    """OpenMetrics exemplar suffix for one bucket sample line:
    ` # {trace_id="..."} <observed value>`, or "" when the bucket never
    saw one. Strict text-0.0.4 parsers must strip this before reading
    the bucket count — fleet._parse_prom_samples does."""
    if not ex:
        return ""
    ex_labels, ex_value = ex
    inner = ",".join(f'{k}="{_escape(v)}"'
                     for k, v in ex_labels.items())
    return f" # {{{inner}}} {_fmt_float(ex_value)}"


def _fmt_float(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def to_prometheus(registry: Optional[Registry] = None,
                  const_labels: Optional[Dict[str, str]] = None,
                  family_filter: Optional[Callable[[str], bool]]
                  = None) -> str:
    """Prometheus text exposition format 0.0.4 of the whole registry.

    `const_labels` are stamped onto EVERY sample; the default is
    `fleet_labels()` (rank/world_size from the launch env) so any
    export — including a single-rank one — can be merged into a fleet
    exposition without sample collisions. Pass `{}` to suppress.

    `family_filter(name) -> bool` restricts the exposition to matching
    families (the memwatch channel's `memory.prom` shard carries only
    the memory/compile families)."""
    registry = registry or default_registry()
    if const_labels is None:
        const_labels = fleet_labels()
    lines = []
    for fam in registry.families():
        if family_filter is not None and not family_filter(fam.name):
            continue
        lines.append(f"# HELP {fam.name} {fam.help}")
        lines.append(f"# TYPE {fam.name} {fam.kind}")
        for labels, cell in fam.samples():
            if const_labels:
                labels = {**labels, **const_labels}
            if fam.kind == "histogram":
                # ONE state() copy per cell: _bucket/_sum/_count come
                # from the same snapshot, so a concurrent observe()
                # cannot tear the invariant _bucket{+Inf} == _count
                counts, hsum, total = cell.state()
                exs = cell.exemplars()
                acc = 0
                for i, (ub, c) in enumerate(zip(cell.buckets, counts)):
                    acc += c
                    le = _fmt_labels(labels, f'le="{_fmt_float(ub)}"')
                    lines.append(f"{fam.name}_bucket{le} {acc}"
                                 + _fmt_exemplar(exs.get(i)))
                le = _fmt_labels(labels, 'le="+Inf"')
                lines.append(f"{fam.name}_bucket{le} {total}"
                             + _fmt_exemplar(exs.get(len(counts) - 1)))
                ls = _fmt_labels(labels)
                lines.append(
                    f"{fam.name}_sum{ls} {_fmt_float(hsum)}")
                lines.append(f"{fam.name}_count{ls} {total}")
            else:
                lines.append(f"{fam.name}{_fmt_labels(labels)} "
                             f"{_fmt_float(cell.value)}")
    return "\n".join(lines) + "\n"


_atomic_seq = 0


def atomic_write(path: str, text: str, append: bool = False):
    """Write `text` via a temp file in the target directory + os.replace:
    a scraper reading mid-write sees either the old complete file or the
    new complete file, never a torn one. The temp name is unique per (pid, thread, call) so concurrent
    writers of the SAME path can't truncate each other's temp file — the
    last replace wins whole, never torn.

    Append mode folds the existing content into the temp file first, so
    a reader still only ever sees complete snapshots; that trades
    kernel-level O_APPEND merging for replace-atomicity, so it assumes
    ONE appender per path (the snapshot-history use case) — concurrent
    appenders should write distinct paths."""
    global _atomic_seq
    path = os.path.abspath(path)
    if append:
        try:
            with open(path) as f:
                text = f.read() + text
        except FileNotFoundError:
            pass
    _atomic_seq += 1
    tmp = (f"{path}.{os.getpid()}.{threading.get_ident()}."
           f"{_atomic_seq}.tmp")
    try:
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def write_prometheus(path: str, registry: Optional[Registry] = None):
    atomic_write(path, to_prometheus(registry))


def snapshot(registry: Optional[Registry] = None) -> list:
    """One dict per sample: {"name", "kind", "labels", value fields}."""
    registry = registry or default_registry()
    ts = time.time()
    rank, world = rank_world()
    out = []
    for fam in registry.families():
        for labels, cell in fam.samples():
            row = {"ts": round(ts, 3), "rank": rank, "world_size": world,
                   "name": fam.name, "kind": fam.kind, "labels": labels}
            if fam.kind == "histogram":
                # same single-copy discipline as to_prometheus
                counts, hsum, total = cell.state()
                row["count"] = total
                row["sum"] = hsum
                buckets = {}
                acc = 0
                for ub, c in zip(cell.buckets, counts):
                    acc += c
                    buckets[_fmt_float(ub)] = acc
                buckets["+Inf"] = total
                row["buckets"] = buckets
            else:
                row["value"] = cell.value
            out.append(row)
    return out


def write_jsonl(path_or_file, registry: Optional[Registry] = None,
                append: bool = True):
    """Append one JSON line per sample — periodic snapshots of the same
    registry form a scrape history a notebook can replay."""
    rows = snapshot(registry)
    if hasattr(path_or_file, "write"):
        for r in rows:
            path_or_file.write(json.dumps(r) + "\n")
        return
    atomic_write(path_or_file,
                 "".join(json.dumps(r) + "\n" for r in rows),
                 append=append)
