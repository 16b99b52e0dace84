"""Shape-bucketed measured-dispatch autotuner for the Pallas kernels.

The round-5 VERDICT showed every Pallas-vs-XLA crossover in this repo was
a hand-pinned constant (`FLAGS_flash_bwd_min_seq`-style) extrapolated from
a handful of on-chip rows. This module replaces guessing with measuring:
on first call per (op, shape-bucket, dtype, device-kind) it times every
registered candidate implementation — the XLA reference and the Pallas
variants across a small block-size grid — and caches the winner in a
persistent JSON table so later processes (and later driver windows) reuse
the measurement instead of re-deriving it.

Contract (ISSUE 2 acceptance criteria):
  * `FLAGS_autotune` ∈ {off, on, readonly}. `off` (default): call sites
    take the legacy flag-based dispatch, bit-identical to pre-autotune
    behavior. `on`: measure-and-cache on miss. `readonly`: cached winners
    are used but a miss NEVER times anything (serving hot paths must not
    absorb measurement jitter).
  * Explicit legacy flags (`FLAGS_flash_bwd_min_seq` etc.) beat cached
    winners — call sites check them before consulting the tuner.
  * The winner is the measured argmin, so a Pallas candidate that timed
    slower than the XLA candidate can never be selected (property-tested
    with the injectable fake timer in tests/test_autotune.py).
  * The timer is injectable (`set_timer`) and the cache dir overridable
    (`FLAGS_autotune_cache_dir`), so tests depend on neither wall clock
    nor $HOME.

Cache file: `~/.cache/paddle_tpu/autotune_<device_kind>.json`, entries
keyed by `op|kernel-version|bucket` (device kind is the filename). All
candidate timings are stored, not just the winner: when the concrete call
shape is not exactly the bucket shape (buckets round seq up to a power of
two) dispatch picks the fastest candidate *eligible* for the concrete
shape from the recorded table.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

SCHEMA_VERSION = 1

# bump when a kernel's code changes enough to invalidate old measurements
KERNEL_VERSIONS = {
    "flash_fwd": "fa-v2",
    "flash_train": "fa-v2",
    "flash_bwd": "fa-v2",
    "flash_bwd_dq": "fa-v2",
    "flash_bwd_dkv": "fa-v2",
    "paged_decode": "pa-v1",
    "rms_norm": "rn-v1",
    "quant_matmul": "qm-v1",
    "matmul": "mm-v1",
}

BLOCK_GRID = (128, 256, 512)


class Candidate(NamedTuple):
    name: str          # e.g. "xla", "flash:256x128", "split"
    kind: str          # "xla" | "pallas"
    fn: Callable       # pure function of the example args (jit-able)
    meta: dict         # blocks/strategy payload the call site executes


def _interpret() -> bool:
    from . import interpret

    return interpret()


def measurement_allowed() -> bool:
    """False when mode=on would time Pallas kernels under interpret mode
    with the real timer — CPU-emulation timings are meaningless and can
    stall a first call for minutes. A custom (test/smoke) timer lifts
    the restriction; readonly/off modes never measure anyway."""
    return _mode() != "on" or not _interpret() or has_custom_timer()


def _mode() -> str:
    from ..framework import config as _config

    m = str(_config.get_flag("FLAGS_autotune", "off")).lower()
    return m if m in ("off", "on", "readonly") else "off"


def mode() -> str:
    return _mode()


def enabled() -> bool:
    return _mode() != "off"


def device_kind() -> str:
    import jax

    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — no backend at all
        kind = "unknown"
    return "".join(c if c.isalnum() else "_" for c in str(kind).lower())


def bucket_pow2(n: int) -> int:
    """Round up to the next power of two (shape bucket edge)."""
    n = max(int(n), 1)
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------


def default_timer(fn, args, iters=8) -> float:
    """Device-time of one `fn(*args)` call in milliseconds.

    Iterations run INSIDE one jitted lax.scan (one dispatch, serialized
    by a tiny carry dependency) — the same machinery as
    tools/tpu_kernel_bench.timeit — so the per-dispatch host cost is
    paid once, not once per iteration.
    """
    import jax
    import jax.numpy as jnp

    a0, rest = args[0], tuple(args[1:])

    @jax.jit
    def many(a, *r):
        def body(carry, _):
            out = fn(carry, *r)
            # depend on EVERY output leaf so no candidate gets a partial
            # DCE advantage; scale runtime-tiny so the carry stays valid
            total = sum(jnp.sum(leaf).astype(jnp.float32)
                        for leaf in jax.tree_util.tree_leaves(out))
            dep = total * jnp.float32(1e-30)
            return carry + dep.astype(carry.dtype), None

        return jax.lax.scan(body, a, None, length=iters)[0]

    jax.block_until_ready(many(a0, *rest))  # compile + first-exec tax
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(many(a0, *rest))
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


_timer_lock = threading.Lock()
_timer: Callable = default_timer
_timer_is_default = True


def set_timer(timer: Optional[Callable]):
    """Install an injectable timer `timer(fn, args) -> ms` (None resets
    to the default device timer). Tests install a deterministic fake so
    nothing depends on wall clock."""
    global _timer, _timer_is_default
    with _timer_lock:
        if timer is None:
            _timer = default_timer
            _timer_is_default = True
        else:
            _timer = timer
            _timer_is_default = False


def has_custom_timer() -> bool:
    return not _timer_is_default


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------


class Autotuner:
    """One persistent measured-dispatch table per device kind."""

    def __init__(self, cache_dir: Optional[str] = None,
                 device: Optional[str] = None):
        self._lock = threading.Lock()
        self._mem: Dict[str, dict] = {}
        self._loaded = False
        self._cache_dir = cache_dir
        self._device = device
        # resolved choose_* results per concrete call signature: a
        # readonly/on cache hit must not rebuild ~10 candidate closures
        # per eager attention call (dropped with reset_tuner())
        self._choice_memo: Dict[tuple, object] = {}

    # -- persistence --------------------------------------------------------

    def cache_dir(self) -> str:
        if self._cache_dir:
            return self._cache_dir
        from ..framework import config as _config

        flag_dir = _config.get_flag("FLAGS_autotune_cache_dir", "")
        if flag_dir:
            return flag_dir
        return os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu")

    def cache_path(self) -> str:
        dev = self._device or device_kind()
        return os.path.join(self.cache_dir(), f"autotune_{dev}.json")

    def _load(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.cache_path()) as f:
                payload = json.load(f)
            if payload.get("schema_version") == SCHEMA_VERSION:
                self._mem.update(payload.get("entries", {}))
        except FileNotFoundError:
            pass
        except Exception:  # noqa: BLE001 — corrupt cache == empty cache
            pass

    def _save(self):
        path = self.cache_path()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            payload = {
                "schema_version": SCHEMA_VERSION,
                "device_kind": self._device or device_kind(),
                "entries": self._mem,
            }
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # atomic: a kill never corrupts
        except Exception:  # noqa: BLE001 — cache write failure is not fatal
            pass

    # -- lookup / measurement ----------------------------------------------

    @staticmethod
    def make_key(op: str, bucket: Sequence) -> str:
        ver = KERNEL_VERSIONS.get(op, "v0")
        parts = [f"{k}={v}" for k, v in bucket]
        return "|".join([op, ver] + parts)

    def snapshot(self) -> Dict[str, dict]:
        """Copy of the current entry table (tools emit it into their
        artifacts; mutation-safe vs the locked internals)."""
        with self._lock:
            self._load()
            return {k: dict(v) for k, v in self._mem.items()}

    def lookup(self, key: str) -> Optional[dict]:
        with self._lock:
            self._load()
            return self._mem.get(key)

    def measure(self, op: str, key: str,
                candidates: Sequence[Candidate],
                make_args: Callable[[], tuple]) -> Optional[dict]:
        """Time every candidate on bucket-shaped example inputs, persist
        and return the entry. Returns None when nothing could be timed.

        Every measurement pass is OBSERVABLE in production (a cache-miss
        re-timing under traffic is exactly the event an operator needs to
        see): a `autotune.measure` span carries each candidate timing and
        the winning decision as attributes, a flight-recorder
        `autotune.decision` breadcrumb lands in the event ring, and
        `autotune_decisions_total{op,winner}` counts it in the metrics
        registry — not just in the JSON cache file."""
        from ..observability import compilewatch as _cw
        from ..observability import tracing as _tracing

        timer = _timer
        # compile attribution: candidate timing compiles every variant —
        # compilewatch bills those to autotune.<op>, not to whatever
        # serving/train callable happened to trigger the measurement
        with _tracing.span("autotune.measure", op=op, key=key) as sp, \
                _cw.call(f"autotune.{op}"):
            args = make_args()
            timings: Dict[str, float] = {}
            for c in candidates:
                try:
                    timings[c.name] = float(timer(c.fn, args))
                except Exception:  # noqa: BLE001 — a failing candidate
                    pass           # just drops out of the table
            if not timings:
                sp.set(outcome="nothing_timed")
                return None
            # argmin with XLA-first tie-break: equal times must never
            # flip dispatch toward an unproven Pallas variant
            order = {"xla": 0, "pallas": 1}
            ranked = sorted(
                timings.items(),
                key=lambda kv: (kv[1],
                                order.get(
                                    next((c.kind for c in candidates
                                          if c.name == kv[0]), "pallas"),
                                    1)))
            entry = {
                "winner": ranked[0][0],
                "timings_ms": {k: round(v, 6)
                               for k, v in timings.items()},
                "op": op,
            }
            sp.set(winner=entry["winner"],
                   timings_ms=entry["timings_ms"])
        _record_decision(op, key, entry)
        with self._lock:
            self._load()
            self._mem[key] = entry
            self._save()
        return entry

    def pick(self, op: str, bucket: Sequence,
             candidates: Sequence[Candidate],
             make_args: Callable[[], tuple],
             eligible: Optional[Callable[[Candidate], bool]] = None,
             ) -> Optional[Candidate]:
        """Return the winning candidate for this bucket, or None when the
        caller must take its legacy dispatch path (mode off, readonly
        miss, or no timeable candidate).

        `eligible` filters which candidates the CONCRETE call shape can
        execute — buckets round shapes up, so the cached winner may be
        invalid for the live shape; then the fastest recorded eligible
        candidate wins instead.
        """
        m = _mode()
        if m == "off" or not candidates:
            return None
        key = self.make_key(op, bucket)
        entry = self.lookup(key)
        if entry is None:
            if m == "readonly":
                return None  # never time in readonly mode
            entry = self.measure(op, key, candidates, make_args)
            if entry is None:
                return None
        by_name = {c.name: c for c in candidates}
        ok = (lambda c: True) if eligible is None else eligible
        win = by_name.get(entry["winner"])
        if win is not None and ok(win):
            return win
        # winner not executable at the concrete shape: fastest eligible row
        for name, _t in sorted(entry.get("timings_ms", {}).items(),
                               key=lambda kv: kv[1]):
            c = by_name.get(name)
            if c is not None and ok(c):
                return c
        return None


# decision-observability handles (labeled counter); HandleCache
# re-resolves after a registry swap/reset — tests included
_decisions_cache = None


def _record_decision(op: str, key: str, entry: dict):
    """Surface a measurement decision in the metrics registry and the
    flight-recorder ring (cache-miss re-timings under traffic must be
    visible in production, not just in the JSON cache file). Never
    raises — observability must not take a tuning pass down."""
    global _decisions_cache
    try:
        from ..observability import flight_recorder as _flight
        from ..observability import metrics as _om

        if _decisions_cache is None:
            _decisions_cache = _om.HandleCache(lambda reg: reg.counter(
                "autotune_decisions_total",
                "Autotune measurement passes that picked a winner "
                "(cache-miss re-timings included), by op and winning "
                "candidate.", labels=("op", "winner")))
        _decisions_cache.get().labels(op, entry["winner"]).inc()
        _flight.record_event("autotune.decision", op=op, key=key,
                             winner=entry["winner"],
                             timings_ms=entry["timings_ms"])
    except Exception:  # noqa: BLE001
        pass


_default_tuner: Optional[Autotuner] = None
_default_lock = threading.Lock()


def get_tuner() -> Autotuner:
    global _default_tuner
    with _default_lock:
        if _default_tuner is None:
            _default_tuner = Autotuner()
        return _default_tuner


def reset_tuner():
    """Drop the process-default tuner (tests; also picks up a changed
    FLAGS_autotune_cache_dir)."""
    global _default_tuner
    with _default_lock:
        _default_tuner = None


# ---------------------------------------------------------------------------
# op-specific candidate builders (the call sites stay thin)
# ---------------------------------------------------------------------------


def _memo(key, build):
    """Per-process memo over a full choose_* call signature: candidate
    construction (closures, grad wrappers, supports() sweeps) happens at
    most once per concrete shape, not per call."""
    tuner = get_tuner()
    # mode and timer-presence are part of the key: a None memoized while
    # measurement was disallowed must not survive a timer install
    key = key + (_mode(), has_custom_timer())
    if key in tuner._choice_memo:
        return tuner._choice_memo[key]
    result = build()
    tuner._choice_memo[key] = result
    return result


def _example_qkv(bh, s_q, s_kv, d, dtype):
    import jax
    import jax.numpy as jnp

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (bh, s_q, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (bh, s_kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (bh, s_kv, d), jnp.float32).astype(dtype)
    return q, k, v


def _block_pairs(s_q, s_kv):
    from . import flash_attention as fa

    pairs = []
    for bq in BLOCK_GRID:
        for bk in BLOCK_GRID:
            if fa.supports(s_q, s_kv, 128, bq, bk):
                pairs.append((bq, bk))
    return pairs


def flash_fwd_bucket(bh, s_q, s_kv, d, dtype, causal):
    return (("bh", bucket_pow2(bh)), ("sq", bucket_pow2(s_q)),
            ("skv", bucket_pow2(s_kv)), ("d", int(d)),
            ("causal", int(bool(causal))), ("dt", str(dtype)))


def choose_flash_fwd(bh, s_q, s_kv, d, dtype, causal, scale,
                     training=False):
    """Measured dispatch for the flash forward (and, with
    `training=True`, the full fwd+bwd train step — what the SDPA training
    path actually pays). Returns the winning Candidate or None (legacy
    dispatch). Winner meta: {"impl": "xla"} or {"impl": "flash",
    "block_q": bq, "block_k": bk}."""
    return _memo(
        ("flash_fwd", bh, s_q, s_kv, d, str(dtype), bool(causal),
         float(scale), bool(training)),
        lambda: _choose_flash_fwd(bh, s_q, s_kv, d, dtype, causal, scale,
                                  training))


def _choose_flash_fwd(bh, s_q, s_kv, d, dtype, causal, scale, training):
    if not measurement_allowed():
        return None

    import jax
    import jax.numpy as jnp

    from . import flash_attention as fa

    bseq_q, bseq_kv = bucket_pow2(s_q), bucket_pow2(s_kv)
    bbh = bucket_pow2(bh)
    op = "flash_train" if training else "flash_fwd"

    def xla_fwd(q, k, v):
        return fa._xla_sdpa_bhsd(q, k, v, scale, causal)

    def grad_of(fwd):
        def loss(q, k, v):
            return jnp.sum(fwd(q, k, v).astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))

    cands: List[Candidate] = []
    timed = grad_of(xla_fwd) if training else xla_fwd
    cands.append(Candidate("xla", "xla", timed, {"impl": "xla"}))
    for bq, bk in _block_pairs(bseq_q, bseq_kv):
        def flash_fwd(q, k, v, _bq=bq, _bk=bk):
            return fa._flash_call(q, k, v, scale, causal, _bq, _bk)

        timed = grad_of(flash_fwd) if training else flash_fwd
        cands.append(Candidate(f"flash:{bq}x{bk}", "pallas", timed,
                               {"impl": "flash", "block_q": bq,
                                "block_k": bk}))

    def make_args():
        return _example_qkv(bbh, bseq_q, bseq_kv, d, dtype)

    def eligible(c):
        if c.meta["impl"] == "xla":
            return True
        return fa.supports(s_q, s_kv, d, c.meta["block_q"],
                           c.meta["block_k"])

    return get_tuner().pick(
        op, flash_fwd_bucket(bh, s_q, s_kv, d, dtype, causal),
        cands, make_args, eligible)


def _example_bwd_res(bh, s_q, s_kv, d, dtype, scale, causal):
    """Synthetic (res, g) for timing backward candidates: a real forward
    run at the bucket shape so lse/out are consistent with q/k/v (the
    backward's flop profile does not depend on the values, but p = exp(s
    - lse) must stay bounded or timings drown in inf/nan handling)."""
    import jax

    from . import flash_attention as fa

    q, k, v = _example_qkv(bh, s_q, s_kv, d, dtype)
    out, lse = fa._flash_fwd(q, k, v, scale, causal, 128, 128)
    g = jax.random.normal(jax.random.PRNGKey(3), q.shape,
                          jax.numpy.float32).astype(dtype)
    return q, k, v, out, lse, g


def choose_flash_bwd_blocks(which, bh, s_q, s_kv, d, dtype, scale, causal):
    """Tune ONE backward pass ('dq' or 'dkv') over the block grid.
    Returns (block_q, block_k) or None."""
    return _memo(
        ("flash_bwd_" + which, bh, s_q, s_kv, d, str(dtype),
         float(scale), bool(causal)),
        lambda: _choose_flash_bwd_blocks(which, bh, s_q, s_kv, d, dtype,
                                         scale, causal))


def _choose_flash_bwd_blocks(which, bh, s_q, s_kv, d, dtype, scale,
                             causal):
    if not measurement_allowed():
        return None

    from . import flash_attention as fa

    bbh, bsq, bskv = bucket_pow2(bh), bucket_pow2(s_q), bucket_pow2(s_kv)

    cands = []
    for bq, bk in _block_pairs(bsq, bskv):
        if which == "dq":
            def pass_fn(q, k, v, out, lse, g, _bq=bq, _bk=bk):
                return fa._flash_bwd_dq((q, k, v, out, lse), g, scale,
                                        causal, _bq, _bk)
        else:
            def pass_fn(q, k, v, out, lse, g, _bq=bq, _bk=bk):
                return fa._flash_bwd_dkv((q, k, v, out, lse), g, scale,
                                         causal, _bq, _bk)
        cands.append(Candidate(f"{which}:{bq}x{bk}", "pallas", pass_fn,
                               {"block_q": bq, "block_k": bk}))

    def make_args():
        return _example_bwd_res(bbh, bsq, bskv, d, dtype, scale, causal)

    def eligible(c):
        return fa.supports(s_q, s_kv, d, c.meta["block_q"],
                           c.meta["block_k"])

    win = get_tuner().pick(
        f"flash_bwd_{which}",
        flash_fwd_bucket(bh, s_q, s_kv, d, dtype, causal),
        cands, make_args, eligible)
    if win is None:
        return None
    return win.meta["block_q"], win.meta["block_k"]


def choose_flash_bwd(bh, s_q, s_kv, d, dtype, scale, causal,
                     block_q, block_k, allow_xla=True):
    """Measured dispatch for the flash backward. Candidates: the XLA
    recompute vjp, the legacy fused (shared-block) Pallas pair at the
    caller's blocks, and the split dq/dkv strategy at each pass's own
    tuned blocks. Winner meta: {"impl": "xla"} | {"impl": "fused"} |
    {"impl": "split", "dq": (bq, bk), "dkv": (bq, bk)}."""
    return _memo(
        ("flash_bwd", bh, s_q, s_kv, d, str(dtype), float(scale),
         bool(causal), block_q, block_k, bool(allow_xla)),
        lambda: _choose_flash_bwd(bh, s_q, s_kv, d, dtype, scale, causal,
                                  block_q, block_k, allow_xla))


def _choose_flash_bwd(bh, s_q, s_kv, d, dtype, scale, causal, block_q,
                      block_k, allow_xla):
    if not measurement_allowed():
        return None

    from . import flash_attention as fa

    bbh, bsq, bskv = bucket_pow2(bh), bucket_pow2(s_q), bucket_pow2(s_kv)

    # tune the independent per-pass block choices first (their winners
    # parameterize the split candidate below); bucket-shape blocks are
    # re-validated against the concrete shape by the caller's `eligible`
    dq_blocks = choose_flash_bwd_blocks("dq", bh, s_q, s_kv, d, dtype,
                                        scale, causal)
    dkv_blocks = choose_flash_bwd_blocks("dkv", bh, s_q, s_kv, d, dtype,
                                         scale, causal)

    cands: List[Candidate] = []
    if allow_xla:
        def xla_bwd(q, k, v, out, lse, g):
            return fa._xla_ref_bwd((q, k, v, out, lse), g, scale, causal)

        cands.append(Candidate("xla", "xla", xla_bwd, {"impl": "xla"}))

    if fa.supports(bsq, bskv, d, block_q, block_k):
        def fused_bwd(q, k, v, out, lse, g):
            return fa._flash_bwd((q, k, v, out, lse), g, scale, causal,
                                 block_q, block_k)

        cands.append(Candidate(f"fused:{block_q}x{block_k}", "pallas",
                               fused_bwd, {"impl": "fused"}))

    if dq_blocks and dkv_blocks:
        def split_bwd(q, k, v, out, lse, g):
            return fa._flash_bwd_split(
                (q, k, v, out, lse), g, scale, causal,
                dq_blocks=dq_blocks, dkv_blocks=dkv_blocks)

        cands.append(Candidate("split", "pallas", split_bwd,
                               {"impl": "split", "dq": dq_blocks,
                                "dkv": dkv_blocks}))

    def make_args():
        return _example_bwd_res(bbh, bsq, bskv, d, dtype, scale, causal)

    def eligible(c):
        if c.meta["impl"] == "xla":
            return True
        if c.meta["impl"] == "fused":
            return fa.supports(s_q, s_kv, d, block_q, block_k)
        return (fa.supports(s_q, s_kv, d, *c.meta["dq"])
                and fa.supports(s_q, s_kv, d, *c.meta["dkv"]))

    bucket = flash_fwd_bucket(bh, s_q, s_kv, d, dtype, causal) + (
        ("fbq", int(block_q)), ("fbk", int(block_k)))
    return get_tuner().pick("flash_bwd", bucket, cands, make_args,
                            eligible)


def choose_paged_decode(b, n_q_heads, n_kv_heads, head_dim, page_size,
                        pages_per_seq, dtype, quant):
    """Measured dispatch for single-token paged decode. Candidates: XLA
    dense-gather, the per-page Pallas kernel, and (float 16-token pages,
    group-aligned tables, FLAGS_paged_grouped_kernel opted in) the
    grouped-fetch kernel. Winner meta:
    {"impl": "xla" | "pallas" | "grouped"}."""
    return _memo(
        ("paged_decode", b, n_q_heads, n_kv_heads, head_dim, page_size,
         pages_per_seq, str(dtype), bool(quant)),
        lambda: _choose_paged_decode(b, n_q_heads, n_kv_heads, head_dim,
                                     page_size, pages_per_seq, dtype,
                                     quant))


def _choose_paged_decode(b, n_q_heads, n_kv_heads, head_dim, page_size,
                         pages_per_seq, dtype, quant):
    if not measurement_allowed():
        return None
    import jax
    import jax.numpy as jnp

    from . import paged_attention as pa

    bucket = (("b", bucket_pow2(b)), ("qh", int(n_q_heads)),
              ("kvh", int(n_kv_heads)), ("d", int(head_dim)),
              ("page", int(page_size)),
              ("pps", bucket_pow2(pages_per_seq)),
              ("dt", str(dtype)), ("quant", int(bool(quant))))
    bb = bucket_pow2(b)
    bpps = bucket_pow2(pages_per_seq)

    def make_args():
        n_pages = bb * bpps
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        # int8-KV buckets still decode with a FLOAT query (only the
        # pages are int8) — timing an all-integer pipeline would rank
        # candidates by a workload production never runs
        q = jax.random.normal(kq, (bb, n_q_heads, head_dim), jnp.float32)
        if not quant:
            q = q.astype(dtype)
        if quant:
            kp = (jax.random.normal(
                kk, (n_kv_heads, n_pages, page_size, head_dim)) * 64
            ).astype(jnp.int8)
            vp = (jax.random.normal(
                kv, (n_kv_heads, n_pages, page_size, head_dim)) * 64
            ).astype(jnp.int8)
            sc = jnp.full((n_kv_heads, n_pages, pa._SCALE_LANES),
                          1.0 / 64, jnp.float32)
            extra = (sc, sc)
        else:
            kp = jax.random.normal(
                kk, (n_kv_heads, n_pages, page_size, head_dim),
                jnp.float32).astype(dtype)
            vp = jax.random.normal(
                kv, (n_kv_heads, n_pages, page_size, head_dim),
                jnp.float32).astype(dtype)
            extra = ()
        tables = jnp.arange(n_pages, dtype=jnp.int32).reshape(bb, bpps)
        lens = jnp.full((bb,), bpps * page_size - 1, jnp.int32)
        return (q, kp, vp, tables, lens) + extra

    if quant:
        def xla_fn(q, kp, vp, tb, ln, ks, vs):
            return pa.paged_attention_xla(q, kp, vp, tb, ln,
                                          k_scales=ks, v_scales=vs)

        def pallas_fn(q, kp, vp, tb, ln, ks, vs):
            return pa.paged_attention(q, kp, vp, tb, ln,
                                      k_scales=ks, v_scales=vs)
    else:
        def xla_fn(q, kp, vp, tb, ln):
            return pa.paged_attention_xla(q, kp, vp, tb, ln)

        def pallas_fn(q, kp, vp, tb, ln):
            return pa.paged_attention(q, kp, vp, tb, ln)

    from ..framework import config as _config

    cands = [Candidate("xla", "xla", xla_fn, {"impl": "xla"}),
             Candidate("pallas", "pallas", pallas_fn, {"impl": "pallas"})]
    # the grouped-fetch kernel stays behind its opt-in flag even under
    # autotune: timing validates SPEED, not numerics, and the repo policy
    # is that un-Mosaic-validated kernels never enter the serving hot
    # path by default (same stance as the flash dropout gating)
    grouped_ok = (not quant and page_size == 16
                  and bpps % pa._GROUP_PAGES == 0
                  and _config.get_flag("FLAGS_paged_grouped_kernel",
                                       False))
    if grouped_ok:
        cands.append(Candidate(
            "grouped", "pallas", pa.paged_attention_grouped,
            {"impl": "grouped"}))

    def eligible(c):
        if c.meta["impl"] == "grouped":
            return pages_per_seq % pa._GROUP_PAGES == 0
        return True

    return get_tuner().pick("paged_decode", bucket, cands, make_args,
                            eligible)


def choose_quant_matmul(m, k, n, weight_dtype, group_size, dtype):
    """Measured dispatch for the weight-only quantized linear
    (kernels/quant_matmul.py). Candidates: the XLA traced-dequant
    matmul and the fused dequant-in-kernel Pallas variant over the
    (block_n, block_k) grid. Winner meta: {"impl": "xla"} or
    {"impl": "fused", "block_n": bn, "block_k": bk}."""
    return _memo(
        ("quant_matmul", m, k, n, str(weight_dtype), int(group_size),
         str(dtype)),
        lambda: _choose_quant_matmul(m, k, n, weight_dtype, group_size,
                                     dtype))


def _choose_quant_matmul(m, k, n, weight_dtype, group_size, dtype):
    if not measurement_allowed():
        return None

    import jax
    import jax.numpy as jnp

    from . import quant_matmul as qm

    bm = bucket_pow2(m)
    bucket = (("m", bm), ("k", int(k)), ("n", int(n)),
              ("wd", str(weight_dtype)), ("gs", int(group_size)),
              ("dt", str(dtype)))

    def xla_fn(x, qw, s):
        return qm.quant_matmul_xla(x, qw, s, weight_dtype)

    cands: List[Candidate] = [
        Candidate("xla", "xla", xla_fn, {"impl": "xla"})]
    for bn in qm.BLOCK_GRID_N:
        for bk in qm.BLOCK_GRID_K:
            if not qm.supports(bm, k, n, weight_dtype, group_size, bn,
                               bk):
                continue

            def fused_fn(x, qw, s, _bn=bn, _bk=bk):
                return qm.quant_matmul_fused(x, qw, s, weight_dtype,
                                             group_size, _bn, _bk)

            cands.append(Candidate(f"fused:{bn}x{bk}", "pallas",
                                   fused_fn,
                                   {"impl": "fused", "block_n": bn,
                                    "block_k": bk}))

    def make_args():
        kx, kw = jax.random.split(jax.random.PRNGKey(4))
        x = jax.random.normal(kx, (bm, k), jnp.float32).astype(dtype)
        rows = k // 2 if weight_dtype == "int4" else k
        qw = (jax.random.normal(kw, (rows, n)) * 64).astype(jnp.int8)
        groups = 1 if group_size == -1 else k // group_size
        shape = (n,) if group_size == -1 else (groups, n)
        s = jnp.full(shape, 1.0 / 64, jnp.float32)
        return x, qw, s

    def eligible(c):
        if c.meta["impl"] == "xla":
            return True
        return qm.supports(m, k, n, weight_dtype, group_size,
                           c.meta["block_n"], c.meta["block_k"])

    return get_tuner().pick("quant_matmul", bucket, cands, make_args,
                            eligible)


def choose_matmul(m, k, n, dtype):
    """Measured dispatch for the dense linear/MLP matmul
    (kernels/matmul.py — the largest compute bucket in the roofline
    report). Candidates: XLA's default lowering and the blocked Pallas
    kernel over the (block_n, block_k) grid. Winner meta: {"impl":
    "xla"} or {"impl": "pallas", "block_n": bn, "block_k": bk}."""
    return _memo(("matmul", m, k, n, str(dtype)),
                 lambda: _choose_matmul(m, k, n, dtype))


def _choose_matmul(m, k, n, dtype):
    if not measurement_allowed():
        return None

    import jax
    import jax.numpy as jnp

    from . import matmul as mm

    bm = bucket_pow2(m)
    bucket = (("m", bm), ("k", int(k)), ("n", int(n)), ("dt", str(dtype)))

    cands: List[Candidate] = [
        Candidate("xla", "xla", mm.matmul_xla, {"impl": "xla"})]
    for bn in mm.BLOCK_GRID_N:
        for bk in mm.BLOCK_GRID_K:
            if not mm.supports(bm, k, n, bn, bk):
                continue

            def pal_fn(x, w, _bn=bn, _bk=bk):
                return mm.matmul_fused(x, w, _bn, _bk)

            cands.append(Candidate(f"pallas:{bn}x{bk}", "pallas", pal_fn,
                                   {"impl": "pallas", "block_n": bn,
                                    "block_k": bk}))

    def make_args():
        kx, kw = jax.random.split(jax.random.PRNGKey(5))
        x = jax.random.normal(kx, (bm, k), jnp.float32).astype(dtype)
        w = jax.random.normal(kw, (k, n), jnp.float32).astype(dtype)
        return x, w

    def eligible(c):
        if c.meta["impl"] == "xla":
            return True
        return mm.supports(m, k, n, c.meta["block_n"], c.meta["block_k"])

    return get_tuner().pick("matmul", bucket, cands, make_args, eligible)


def choose_rms_norm(rows, cols, dtype):
    """Measured dispatch for fused RMSNorm. Candidates: the fused XLA
    expression and the Pallas kernel across the row-block grid. Winner
    meta: {"impl": "xla"} or {"impl": "pallas", "block_rows": n}."""
    return _memo(("rms_norm", rows, cols, str(dtype)),
                 lambda: _choose_rms_norm(rows, cols, dtype))


def _choose_rms_norm(rows, cols, dtype):
    if not measurement_allowed():
        return None

    import jax
    import jax.numpy as jnp

    from . import rms_norm as rn

    brows = bucket_pow2(rows)
    bucket = (("rows", brows), ("cols", int(cols)), ("dt", str(dtype)))

    def xla_fn(x, w):
        # timing stand-in for norm.py's fused XLA fallback; eps is fixed
        # (it shifts numerics, not cost) — dispatch still runs the real
        # norm.py expression with the caller's epsilon
        xf = x.astype(jnp.float32)
        r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                          + jnp.float32(1e-6))
        return (xf * r * w.astype(jnp.float32)).astype(x.dtype)

    itemsize = jnp.dtype(dtype).itemsize
    cands = [Candidate("xla", "xla", xla_fn, {"impl": "xla"})]
    # the grid, plus the kernel's own width-dependent default: at wide
    # rows it is the only block that fits VMEM
    for br in sorted({*BLOCK_GRID, rn._block(brows, cols, itemsize)}):
        if rn.supports(brows, cols, block_rows=br, itemsize=itemsize):
            def pal_fn(x, w, _br=br):
                return rn.rms_norm_2d(x, w, 1e-6, _br)

            cands.append(Candidate(f"pallas:{br}", "pallas", pal_fn,
                                   {"impl": "pallas", "block_rows": br}))

    def make_args():
        x = jax.random.normal(jax.random.PRNGKey(2), (brows, cols),
                              jnp.float32).astype(dtype)
        w = jnp.ones((cols,), dtype)
        return x, w

    def eligible(c):
        if c.meta["impl"] == "xla":
            return True
        return rn.supports(rows, cols, block_rows=c.meta["block_rows"],
                           itemsize=itemsize)

    return get_tuner().pick("rms_norm", bucket, cands, make_args, eligible)
