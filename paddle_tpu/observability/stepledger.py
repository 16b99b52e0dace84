"""Step-time ledger: device-time attribution, per-op roofline, and the
waterfall that names the next perf move (README.md "Step-time ledger",
sixth telemetry channel).

The first five channels (metrics, tracing, fleet, memwatch,
compilewatch) make the HOST legible; none of them can say where DEVICE
time goes — the tracing critical path ends at "step_compute: 41 ms"
with no decomposition, which is exactly the blind spot in front of the
ROADMAP MFU and decode-speed items. This module reconciles every
train/decode step's wall time into named buckets so the next
optimization target is read off a table instead of guessed:

- **Measured buckets** (`begin()`/`end()` around each compiled
  dispatch, wired in `models/trainer.py` and `inference/serving.py`):
  with `FLAGS_stepledger` on, `end()` blocks on the step's outputs
  (`jax.block_until_ready`, every `FLAGS_stepledger_block_every`-th
  step) so the dispatch window includes the true device tail, then
  splits the step period into

      data_wait    host gap before the call (dataloader stalls)
      compile      XLA compile seconds inside the window (compilewatch
                   delta — 0 when FLAGS_compilewatch is off)
      collective   eager-collective wait inside the window
                   (collective_wait_seconds_total delta — 0 when the
                   fleet layer is off)
      host         dispatch-side host time (trace + argument prep +
                   dispatch) net of compile/collective
      compute      the blocked device window after dispatch returned
      residual     the "unexplained" fraction, ITSELF a gauge
                   (stepledger_residual_fraction); tools/ci.sh gates
                   it under 25%. In-process a healthy window
                   reconciles by construction (host is the attributed
                   remainder of the dispatch window), so the gate's
                   teeth are in the EXPORT: `waterfall()` recomputes
                   residual from the independently exported wall
                   counter vs the bucket counters, so a partial
                   exposition, mixed-version rank shards, or a counter
                   reset mid-run surface as residual instead of
                   silently shrinking the waterfall.

  Exported as `stepledger_*` families (steps / per-bucket seconds /
  wall seconds per entry point), per rank via the fleet flusher
  (`rank_<i>/ledger.prom`), and summarized by `tools/step_ledger.py`.

- **Analytical roofline per compiled executable**
  (`register_cost()` / `register_from_lowered()`): the entry point's
  `compiled.cost_analysis()` FLOPs / bytes-accessed (the same
  extraction `paddle_tpu.flops()` uses) against the device peak table
  (`observability/device_peaks.py` — ONE table shared with PerfMeter
  and bench.py) classifies each program compute-bound vs HBM-bound
  (arithmetic intensity vs the ridge point), or comms-bound when the
  measured collective share dominates, and an MFU gauge per entry
  point (`stepledger_mfu{entry}`) closes the loop to the ROADMAP
  targets. `register_from_lowered` lowers on ShapeDtypeStructs (shape/
  dtype only — safe AFTER a donating call consumed the real buffers)
  and compiles once per entry point, only under the flag.

Zero-overhead contract: `FLAGS_stepledger` unset = ONE flag read per
step (`begin()` returns None), zero ledger records and zero registry
allocations — pinned by tests/test_stepledger.py, the memwatch/
compilewatch alloc-guard discipline.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from . import device_peaks as _peaks
from . import metrics as _metrics

# bucket names in waterfall display order ("residual" always last)
BUCKETS = ("compute", "host", "collective", "data_wait", "compile",
           "residual")

LEDGER_FAMILY_PREFIX = "stepledger_"

# span-name prefix -> ledger bucket: the join key between the tracer's
# critical path and this channel (tools/trace_report.py prints it as a
# `bucket` column when a ledger export sits next to the trace). Order
# matters — first prefix match wins.
SPAN_BUCKETS = (
    ("train.data_wait", "data_wait"),
    ("train.step_compute", "compute"),
    ("serving.queue", "host"),
    ("serving.prefill", "compute"),
    ("serving.decode", "compute"),
    ("collective.", "collective"),
    ("compile.", "compile"),
    ("dataloader.", "data_wait"),
    ("checkpoint.", "host"),
)

# bucket -> the ROADMAP move it implicates (the "what do I do about it"
# column of the report; compute defers to the roofline classification)
ADVICE = {
    "collective": "overlap the collective with compute: bucketed async "
                  "dp reduce-scatter in distributed/parallel.py "
                  "(ROADMAP item 3)",
    "data_wait": "double-buffer host->device data staging / prefetch "
                 "in the dataloader (ROADMAP item 3)",
    "compile": "prepay compiles in warmup and shape-bucket churning "
               "inputs (the compilewatch storm report cites the "
               "offending shapes)",
    "host": "amortize per-dispatch host cost: raise decode_burst / "
            "async_depth (serving) or the batch operating point "
            "(tools/mfu_sweep.py)",
    "residual": "unattributed time — enable FLAGS_compilewatch and "
                "FLAGS_telemetry_dir so compile and collective wait "
                "are named",
}
ADVICE_COMPUTE = {
    "hbm-bound": "cut HBM traffic: fused dequant-matmul + int8/int4-KV "
                 "paged-attention kernels (ROADMAP item 2), remat "
                 "policy",
    "compute-bound": "raise the MFU operating point (tools/"
                     "mfu_sweep.py) (ROADMAP item 3)",
    "comms-bound": "overlap communication with compute "
                   "(ROADMAP item 3)",
    "unknown": "register the entry point's cost_analysis "
               "(stepledger.register_from_lowered) to classify "
               "compute-bound vs HBM-bound",
}


def bucket_of_span(name: str) -> Optional[str]:
    """Ledger bucket for a tracer span name (prefix match), or None."""
    for prefix, bucket in SPAN_BUCKETS:
        if name.startswith(prefix):
            return bucket
    return None


def _flags():
    from ..framework import config as _config

    return _config


def enabled() -> bool:
    """One flag read — the whole per-step cost when the ledger is
    off."""
    return bool(_flags().get_flag("FLAGS_stepledger", False))


def block_every() -> int:
    try:
        v = int(_flags().get_flag("FLAGS_stepledger_block_every", 1))
        return v if v > 0 else 1
    except (TypeError, ValueError):
        return 1


# every recorded step / registered cost — the off-path guard asserts
# this stays flat (Registry.allocations discipline)
_counts = {"steps": 0, "costs": 0}
# per-entry step sequence for the block_every cadence: a process-global
# modulus would alias against the interleaving of entry points (two
# strictly-alternating entries under block_every=2 → one blocks always,
# the other never, and its device time lands in residual)
_entry_seq: Dict[str, int] = {}
_lock = threading.Lock()
# entry -> {"steps", "wall", "tokens", "blocked", "buckets": {...}}
_agg: Dict[str, dict] = {}
# entry -> {"flops", "bytes_accessed", "n_devices", "peak_flops",
#           "peak_bw", ...}
_costs: Dict[str, dict] = {}


def steps_recorded() -> int:
    return _counts["steps"]


# ---------------------------------------------------------------------------
# registry handles
# ---------------------------------------------------------------------------


def _make_handles(reg):
    return {
        "steps": reg.counter(
            "stepledger_steps_total",
            "Steps reconciled by the step-time ledger, per entry point "
            "(populated when FLAGS_stepledger is on).",
            labels=("entry",)),
        "seconds": reg.counter(
            "stepledger_seconds_total",
            "Step wall time attributed to each ledger bucket (compute /"
            " host / collective / data_wait / compile / residual), per "
            "entry point.", labels=("entry", "bucket")),
        "wall": reg.counter(
            "stepledger_wall_seconds_total",
            "Total step wall time (host gap + blocked dispatch window) "
            "per entry point — the denominator the buckets reconcile "
            "against.", labels=("entry",)),
        "residual_frac": reg.gauge(
            "stepledger_residual_fraction",
            "Running residual/wall fraction per entry point — the "
            "'unexplained' share of step time; tools/ci.sh gates this "
            "under 0.25 on the traced smoke.", labels=("entry",)),
        "flops": reg.gauge(
            "stepledger_flops_per_step",
            "XLA cost_analysis FLOPs per execution of the entry "
            "point's compiled program.", labels=("entry",)),
        "bytes": reg.gauge(
            "stepledger_bytes_per_step",
            "XLA cost_analysis bytes accessed per execution of the "
            "entry point's compiled program.", labels=("entry",)),
        "peak_flops": reg.gauge(
            "stepledger_peak_flops",
            "Device bf16 peak FLOPs/s used for this entry point's "
            "roofline/MFU (observability/device_peaks.py; 0 = unknown "
            "device).", labels=("entry",)),
        "peak_bw": reg.gauge(
            "stepledger_peak_bytes_per_s",
            "Device HBM bytes/s used for this entry point's roofline "
            "(0 = unknown device).", labels=("entry",)),
        "n_devices": reg.gauge(
            "stepledger_n_devices",
            "Device count the entry point's compiled program spans — "
            "the per-chip MFU denominator factor (exported so an MFU "
            "recomputed from the .prom ledger matches the in-process "
            "stepledger_mfu gauge on multi-chip runs).",
            labels=("entry",)),
        "mfu": reg.gauge(
            "stepledger_mfu",
            "Measured model-FLOPs utilization per entry point: "
            "cost_analysis FLOPs / (mean step wall * device peak * "
            "n_devices).", labels=("entry",)),
        "overlap": reg.gauge(
            "stepledger_overlap_efficiency",
            "Collective overlap efficiency per entry point: the share "
            "of raw collective wait hidden behind the step's dispatch "
            "window (hidden / raw; 1.0 = fully overlapped, 0.0 = every "
            "collective second exposed). The `collective` bucket "
            "reports only the EXPOSED remainder.", labels=("entry",)),
    }


_handles: Optional[_metrics.HandleCache] = None


def _h():
    global _handles
    if _handles is None:
        _handles = _metrics.HandleCache(_make_handles)
    return _handles.get()


# ---------------------------------------------------------------------------
# counter sources for the compile / collective buckets
# ---------------------------------------------------------------------------


def _compile_seconds() -> float:
    """Total XLA compile seconds compilewatch has attributed so far
    (0 when the channel is off/quiet) — delta over a step window is the
    `compile` bucket."""
    try:
        from . import compilewatch as _cw

        # snapshot() takes the watch lock — a concurrent compile on
        # another thread must not blow up the iteration (the blanket
        # except would silently zero this step's compile bucket)
        return float(sum(r["compile_s"]
                         for r in _cw.default_watch()
                         .snapshot().values()))
    except Exception:  # noqa: BLE001 — telemetry must never raise
        return 0.0


def _collective_seconds(registry=None) -> float:
    """Total eager-collective wait seconds (the fleet channel's
    `collective_wait_seconds_total` family; 0 when absent)."""
    try:
        reg = registry or _metrics.default_registry()
        fam = reg.get("collective_wait_seconds_total")
        if fam is None:
            return 0.0
        return float(sum(cell.value for _, cell in fam.samples()))
    except Exception:  # noqa: BLE001
        return 0.0


# ---------------------------------------------------------------------------
# the measured ledger
# ---------------------------------------------------------------------------


def begin() -> Optional[Tuple[float, float, float]]:
    """Open a step window: returns the (t0, compile_s, collective_s)
    snapshot to hand back to `end()`, or None (one flag read) when the
    ledger is off."""
    if not enabled():
        return None
    return (time.perf_counter(), _compile_seconds(),
            _collective_seconds())


def _mfu(cost: dict, steps: int, wall: float) -> Optional[float]:
    """THE one MFU formula — flops*steps / (wall * peak * n_devices) —
    shared by the stepledger_mfu gauge, roofline(), and the CLI report
    so the three can never drift apart. None when cost/peak/wall is
    missing."""
    flops = float(cost.get("flops") or 0.0)
    peak = float(cost.get("peak_flops") or 0.0)
    if not flops or not peak or not wall or wall <= 0:
        return None
    return flops * steps / (
        wall * peak * max(int(cost.get("n_devices", 1) or 1), 1))


def _block_on(out):
    """block_until_ready on every array leaf of `out` (Tensors
    unwrapped): the measured window then includes the device tail
    (chip_smoke.py's sync check shows the call waits for completion on
    the chip). Never raises — a deleted/donated leaf must not take the
    step down."""
    import jax

    try:
        leaves = jax.tree_util.tree_leaves(out)
    except Exception:  # noqa: BLE001
        leaves = [out]
    for leaf in leaves:
        data = getattr(leaf, "_data", leaf)
        block = getattr(data, "block_until_ready", None)
        if block is None:
            continue
        try:
            block()
        except Exception:  # noqa: BLE001
            continue


def end(snap, entry: str, t_dispatch: float, out=None, data_wait=0.0,
        tokens: int = 0, registry=None) -> float:
    """Close a step window opened by `begin()` and attribute it.

    `t_dispatch` is when the compiled call returned to the host (the
    caller already measures it for its latency histograms); `out` is
    the call's output pytree — blocked on (every
    FLAGS_stepledger_block_every-th step) so the window includes the
    device tail; `data_wait` is the host gap before the call. Returns
    the post-block timestamp so the caller can re-anchor its
    "time since last step" bookkeeping (otherwise the block shows up
    AGAIN as the next step's data wait)."""
    t0, c0, w0 = snap
    _counts["steps"] += 1
    with _lock:
        seq = _entry_seq[entry] = _entry_seq.get(entry, 0) + 1
    blocked = out is not None and (seq % block_every() == 0)
    if blocked:
        _block_on(out)
    t2 = time.perf_counter()
    compile_d = max(_compile_seconds() - c0, 0.0)
    coll_d = max(_collective_seconds(registry) - w0, 0.0)
    dw = max(float(data_wait or 0.0), 0.0)
    compute = max(t2 - t_dispatch, 0.0)
    # the compile/collective sources are PROCESS-global counters, so a
    # concurrent step on another thread (trainer + serving in one
    # process) can push the deltas past this entry's dispatch window —
    # cap them proportionally to the window so the named buckets can
    # never exceed the exported wall (fractions stay <= 100%). For the
    # collective counter the clamp IS the overlap attribution: wait
    # seconds in excess of the host dispatch window were, by
    # construction, hidden behind compute (the bucketed async reducer
    # issues reduces that drain while the device keeps working), so
    # the `collective` bucket reports only the EXPOSED remainder and
    # the hidden share feeds stepledger_overlap_efficiency.
    raw_coll = coll_d
    window = max(t_dispatch - t0, 0.0)
    over = compile_d + coll_d
    if over > window:
        scale = window / over if over > 0 else 0.0
        compile_d *= scale
        coll_d *= scale
    hidden_coll = max(raw_coll - coll_d, 0.0)
    host = max(window - compile_d - coll_d, 0.0)
    wall = max(t2 - t0, 0.0) + dw
    named = dw + compute + host + compile_d + coll_d
    residual = max(wall - named, 0.0)
    buckets = {"compute": compute, "host": host, "collective": coll_d,
               "data_wait": dw, "compile": compile_d,
               "residual": residual}
    with _lock:
        a = _agg.get(entry)
        if a is None:
            a = _agg[entry] = {"steps": 0, "wall": 0.0, "tokens": 0,
                               "blocked": 0,
                               "coll_raw": 0.0, "coll_hidden": 0.0,
                               "buckets": {b: 0.0 for b in BUCKETS}}
        a["steps"] += 1
        a["wall"] += wall
        a["tokens"] += int(tokens or 0)
        a["blocked"] += 1 if blocked else 0
        a["coll_raw"] += raw_coll
        a["coll_hidden"] += hidden_coll
        for b, v in buckets.items():
            a["buckets"][b] += v
        agg_wall, agg_res = a["wall"], a["buckets"]["residual"]
        agg_steps = a["steps"]
        agg_raw, agg_hidden = a["coll_raw"], a["coll_hidden"]
    h = _make_handles(registry) if registry is not None else _h()
    h["steps"].labels(entry).inc()
    h["wall"].labels(entry).inc(wall)
    for b, v in buckets.items():
        h["seconds"].labels(entry, b).inc(v)
    h["residual_frac"].labels(entry).set(
        agg_res / agg_wall if agg_wall > 0 else 0.0)
    h["overlap"].labels(entry).set(
        agg_hidden / agg_raw if agg_raw > 0 else 0.0)
    cost = _costs.get(entry)
    if cost:
        mfu = _mfu(cost, agg_steps, agg_wall)
        if mfu is not None:
            h["mfu"].labels(entry).set(mfu)
    return t2


# ---------------------------------------------------------------------------
# analytical cost + roofline
# ---------------------------------------------------------------------------


def cost_from_compiled(compiled) -> Dict[str, float]:
    """FLOPs / bytes-accessed of a compiled XLA program (the same
    cost_analysis extraction paddle_tpu.flops() uses; older jax returns
    [dict])."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    return {
        "flops": float(cost.get("flops", 0) or 0),
        "bytes_accessed": float(cost.get("bytes accessed", 0) or 0),
    }


def has_cost(entry: str) -> bool:
    return entry in _costs


def register_cost(entry: str, flops: float = 0.0,
                  bytes_accessed: float = 0.0, n_devices: int = 1,
                  peak_flops=None, peak_bw=None,
                  registry=None, quant=None,
                  quant_bytes_delta: float = 0.0) -> dict:
    """Record an entry point's analytical cost (and the device peaks it
    rooflines against) and publish the gauges. Peaks default to the
    shared device_peaks table for the process's device; unknown devices
    (CPU test backend) record 0 and classify `unknown`.

    quant / quant_bytes_delta: weight-only-quantized executables tag
    the entry (the roofline row carries `quant`) and correct the
    cost_analysis byte count — XLA bills the dequantized bf16/f32
    weight intermediate as memory traffic, but the HBM bytes a
    dequant-in-kernel (or load-fused) matmul actually moves are the
    int8/int4 ones, so the caller subtracts the (float - int) weight
    delta to keep intensity classification and stepledger_mfu honest
    for quantized decode."""
    if peak_flops is None:
        peak_flops = _peaks.detect_peak_flops()
    if peak_bw is None:
        peak_bw = _peaks.detect_peak_hbm_bytes_per_s()
    _counts["costs"] += 1
    nbytes = float(bytes_accessed or 0.0)
    if quant_bytes_delta:
        nbytes = max(nbytes - float(quant_bytes_delta), 0.0)
    cost = {
        "flops": float(flops or 0.0),
        "bytes_accessed": nbytes,
        "n_devices": max(int(n_devices), 1),
        "peak_flops": float(peak_flops or 0.0),
        "peak_bw": float(peak_bw or 0.0),
    }
    if quant:
        cost["quant"] = str(quant)
    with _lock:
        _costs[entry] = cost
    h = _make_handles(registry) if registry is not None else _h()
    h["flops"].labels(entry).set(cost["flops"])
    h["bytes"].labels(entry).set(cost["bytes_accessed"])
    h["peak_flops"].labels(entry).set(cost["peak_flops"])
    h["peak_bw"].labels(entry).set(cost["peak_bw"])
    h["n_devices"].labels(entry).set(cost["n_devices"])
    return cost


def _abstract(obj):
    """args -> ShapeDtypeStructs (shape/dtype only): lowering input
    that is safe to build AFTER a donating call deleted the real
    buffers, and that never touches device data. Static leaves (the
    jit-cache structure tuples, ints, strings) pass through by
    value."""
    import jax

    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    data = getattr(obj, "_data", None)  # paddle Tensor
    if data is not None and hasattr(data, "shape"):
        return _abstract(data)
    if isinstance(obj, dict):
        return {k: _abstract(v) for k, v in obj.items()}
    if isinstance(obj, tuple):
        return tuple(_abstract(o) for o in obj)
    if isinstance(obj, list):
        return [_abstract(o) for o in obj]
    return obj


def register_from_lowered(entry: str, jitted, args,
                          kwargs=None, quant=None,
                          quant_bytes_delta: float = 0.0
                          ) -> Optional[dict]:
    """Register `entry`'s cost by AOT-lowering the jitted callable on
    the abstracted `args` and reading the compiled program's
    cost_analysis. Once per entry point; compiles the program a second
    time (the AOT path does not share the jit executable cache), so it
    only runs under FLAGS_stepledger. Never raises — a lowering failure
    records a zero-cost sentinel so it is not retried every step.
    quant/quant_bytes_delta: see register_cost — quantized-weight
    executables correct the bf16-intermediate byte overcount."""
    if not enabled() or entry in _costs:
        return _costs.get(entry)
    try:
        abs_args = tuple(_abstract(a) for a in args)
        abs_kw = {k: _abstract(v) for k, v in (kwargs or {}).items()}
        compiled = jitted.lower(*abs_args, **abs_kw).compile()
        c = cost_from_compiled(compiled)
        try:
            import jax

            n_dev = max(len(jax.devices()), 1)
        except Exception:  # noqa: BLE001
            n_dev = 1
        return register_cost(entry, c["flops"], c["bytes_accessed"],
                             n_devices=n_dev, quant=quant,
                             quant_bytes_delta=quant_bytes_delta)
    except Exception as e:  # noqa: BLE001 — cost is optional telemetry
        with _lock:
            _costs[entry] = {"flops": 0.0, "bytes_accessed": 0.0,
                             "n_devices": 1, "peak_flops": 0.0,
                             "peak_bw": 0.0,
                             "error": f"{type(e).__name__}: {e}"[:160]}
        return None


def classify(flops: float, bytes_accessed: float, peak_flops=None,
             peak_bw=None, comm_fraction: float = 0.0,
             comm_threshold: float = 0.4) -> str:
    """Roofline classification of one executable: `comms-bound` when
    the measured collective share of step time crosses
    `comm_threshold`, else compute- vs HBM-bound by arithmetic
    intensity (flops/byte) against the device ridge point
    (peak_flops/peak_bw); `unknown` when any input is missing."""
    if comm_fraction and comm_fraction >= comm_threshold:
        return "comms-bound"
    if not flops or not bytes_accessed or not peak_flops or not peak_bw:
        return "unknown"
    intensity = flops / bytes_accessed
    ridge = peak_flops / peak_bw
    return "compute-bound" if intensity >= ridge else "hbm-bound"


def roofline(entry: str) -> dict:
    """In-process roofline row for one entry point: cost, intensity,
    ridge, classification (comms-bound folds in the measured collective
    share), and MFU when measurable."""
    with _lock:
        cost = dict(_costs.get(entry) or {})
        a = _agg.get(entry)
        agg = {"steps": a["steps"], "wall": a["wall"],
               "coll": a["buckets"]["collective"]} if a else None
    comm_frac = (agg["coll"] / agg["wall"]
                 if agg and agg["wall"] > 0 else 0.0)
    flops = cost.get("flops", 0.0)
    nbytes = cost.get("bytes_accessed", 0.0)
    pf = cost.get("peak_flops", 0.0)
    pb = cost.get("peak_bw", 0.0)
    out = {
        "entry": entry,
        "flops": flops,
        "bytes_accessed": nbytes,
        "intensity": flops / nbytes if nbytes else None,
        "ridge": pf / pb if pf and pb else None,
        "comm_fraction": round(comm_frac, 4),
        "bound": classify(flops, nbytes, pf, pb, comm_frac),
    }
    if cost.get("quant"):
        # weight-only-quantized executable: bytes_accessed above already
        # carries the int-weight-traffic correction (register_cost)
        out["quant"] = cost["quant"]
    if agg:
        mfu = _mfu(cost, agg["steps"], agg["wall"])
        if mfu is not None:
            out["mfu"] = mfu
    return out


# ---------------------------------------------------------------------------
# exposition + report
# ---------------------------------------------------------------------------


def is_ledger_family(name: str) -> bool:
    return name.startswith(LEDGER_FAMILY_PREFIX)


def ledger_exposition(registry=None, const_labels=None) -> str:
    """Prometheus text of the stepledger families ONLY (the
    `rank_<i>/ledger.prom` fleet shard); the full registry keeps
    exporting everything via metrics.prom."""
    return _metrics.to_prometheus(
        registry or _metrics.default_registry(),
        const_labels=const_labels,
        family_filter=is_ledger_family)


def snapshot() -> dict:
    """{entry: {steps, wall, tokens, blocked, buckets{...},
    cost{...}}} — a mutation-safe copy."""
    with _lock:
        out = {}
        for entry, a in _agg.items():
            out[entry] = {**{k: v for k, v in a.items()
                             if k != "buckets"},
                          "buckets": dict(a["buckets"])}
            if entry in _costs:
                out[entry]["cost"] = dict(_costs[entry])
        for entry, c in _costs.items():
            out.setdefault(entry, {"steps": 0, "wall": 0.0, "tokens": 0,
                                   "blocked": 0,
                                   "buckets": {b: 0.0 for b in BUCKETS},
                                   "cost": dict(c)})
    return out


def waterfall(agg: Optional[dict] = None) -> List[dict]:
    """One row per entry point: steps, wall seconds, per-bucket
    {seconds, frac}. `agg` defaults to the in-process snapshot; the CLI
    passes an aggregate parsed from a Prometheus export."""
    agg = agg if agg is not None else snapshot()
    rows = []
    for entry in sorted(agg, key=lambda e: -agg[e].get("wall", 0.0)):
        a = agg[entry]
        wall = float(a.get("wall", 0.0))
        if a.get("steps", 0) <= 0 or wall <= 0:
            continue
        # residual is recomputed against the independently exported
        # wall counter, not just read back: a measured window
        # reconciles by construction (end() derives host as the
        # attributed remainder), so the recorded residual is ~0 — but
        # bucket samples lost between record and report (a partial
        # exposition, mixed-version rank shards, a counter reset
        # mid-run) must surface as unexplained time, not as a silently
        # smaller waterfall. max() keeps any recorded residual too.
        named = sum(float(a["buckets"].get(b, 0.0))
                    for b in BUCKETS if b != "residual")
        resid = max(float(a["buckets"].get("residual", 0.0)),
                    wall - named)
        seconds = {b: float(a["buckets"].get(b, 0.0)) for b in BUCKETS}
        seconds["residual"] = resid
        buckets = {
            b: {"seconds": seconds[b], "frac": seconds[b] / wall}
            for b in BUCKETS}
        rows.append({"entry": entry, "steps": int(a["steps"]),
                     "wall_s": wall,
                     "tokens": int(a.get("tokens", 0)),
                     "buckets": buckets,
                     "residual_frac": buckets["residual"]["frac"],
                     "cost": a.get("cost")})
    return rows


def _bound_of_row(row) -> str:
    cost = row.get("cost") or {}
    return classify(cost.get("flops", 0.0),
                    cost.get("bytes_accessed", 0.0),
                    cost.get("peak_flops", 0.0),
                    cost.get("peak_bw", 0.0),
                    row["buckets"]["collective"]["frac"])


def targets(rows: Optional[List[dict]] = None,
            top: int = 3) -> List[dict]:
    """The top optimization targets across all entries: every
    (entry, bucket) share of that entry's wall, largest first, each
    with the ROADMAP move it implicates. Compute buckets defer to the
    entry's roofline classification for their advice."""
    rows = waterfall() if rows is None else rows
    cands = []
    for row in rows:
        bound = _bound_of_row(row)
        for b in BUCKETS:
            share = row["buckets"][b]["frac"]
            secs = row["buckets"][b]["seconds"]
            if share <= 0.01:
                continue
            advice = ADVICE_COMPUTE.get(bound, ADVICE_COMPUTE["unknown"]) \
                if b == "compute" else ADVICE[b]
            cands.append({"entry": row["entry"], "bucket": b,
                          "seconds": secs,
                          "share": share,
                          "bound": bound if b == "compute" else None,
                          "advice": advice})
    cands.sort(key=lambda c: (-c["seconds"], c["entry"], c["bucket"]))
    return cands[:top] if top else cands


def format_report(rows: Optional[List[dict]] = None,
                  top: int = 3) -> str:
    """The operator-facing waterfall + roofline + top-N targets text
    (tools/step_ledger.py prints this)."""
    rows = waterfall() if rows is None else rows
    lines: List[str] = []
    if not rows:
        return ("no step-time ledger samples — was FLAGS_stepledger "
                "set on the workload?\n")
    for row in rows:
        per_step = row["wall_s"] / row["steps"] * 1e3
        lines.append(
            f"== step-time waterfall: {row['entry']} "
            f"({row['steps']} steps, {row['wall_s']:.3f} s wall, "
            f"{per_step:.3f} ms/step) ==")
        lines.append(f"  {'bucket':<12} {'seconds':>10} {'share':>7}")
        for b in BUCKETS:
            v = row["buckets"][b]
            lines.append(f"  {b:<12} {v['seconds']:>10.4f} "
                         f"{v['frac'] * 100.0:>6.1f}%")
        cost = row.get("cost") or {}
        if cost.get("flops"):
            bound = _bound_of_row(row)
            intensity = (cost["flops"] / cost["bytes_accessed"]
                         if cost.get("bytes_accessed") else None)
            ridge = (cost["peak_flops"] / cost["peak_bw"]
                     if cost.get("peak_flops") and cost.get("peak_bw")
                     else None)
            mfu = _mfu(cost, row["steps"], row["wall_s"])
            detail = f"  roofline: {bound}"
            if intensity is not None:
                detail += f" (intensity {intensity:.1f} flops/B"
                detail += f" vs ridge {ridge:.1f})" if ridge is not None \
                    else ")"
            if mfu is not None:
                detail += f", mfu {mfu:.3f}"
            lines.append(detail)
        lines.append("")
    tg = targets(rows, top=top)
    if tg:
        lines.append(f"== top {len(tg)} optimization targets ==")
        for i, t in enumerate(tg):
            bound = f" [{t['bound']}]" if t.get("bound") else ""
            lines.append(
                f" {i + 1}. {t['entry']} · {t['bucket']} "
                f"{t['share'] * 100.0:.1f}% of step{bound} -> "
                f"{t['advice']}")
        lines.append("")
    return "\n".join(lines) + "\n"


def samples_from_prom_files(paths) -> Dict[str, list]:
    """Parse one or more Prometheus exposition files and merge their
    sample lists per family (rank shards SUM downstream in
    aggregate_from_samples) — the one merge loop shared by
    tools/step_ledger.py and tools/trace_report.py."""
    from .fleet import _parse_prom_samples

    merged: Dict[str, list] = {}
    for path in paths:
        with open(path) as fh:
            for name, rows in _parse_prom_samples(fh.read()).items():
                merged.setdefault(name, []).extend(rows)
    return merged


def aggregate_from_samples(samples: Dict[str, List[Tuple[dict, float]]]
                           ) -> dict:
    """Rebuild the waterfall aggregate from parsed Prometheus samples
    (`fleet._parse_prom_samples` output) — sums across ranks, so a
    merged fleet exposition aggregates cleanly. The pure-function half
    of tools/step_ledger.py."""
    agg: Dict[str, dict] = {}

    def _entry(labels):
        e = labels.get("entry")
        if e is None:
            return None
        a = agg.get(e)
        if a is None:
            a = agg[e] = {"steps": 0, "wall": 0.0, "tokens": 0,
                          "blocked": 0,
                          "buckets": {b: 0.0 for b in BUCKETS}}
        return a

    for labels, v in samples.get("stepledger_steps_total", []):
        a = _entry(labels)
        if a is not None:
            a["steps"] += int(v)
    for labels, v in samples.get("stepledger_wall_seconds_total", []):
        a = _entry(labels)
        if a is not None:
            a["wall"] += float(v)
    for labels, v in samples.get("stepledger_seconds_total", []):
        a = _entry(labels)
        b = labels.get("bucket")
        if a is not None and b in a["buckets"]:
            a["buckets"][b] += float(v)
    costs: Dict[str, dict] = {}
    for name, field in (("stepledger_flops_per_step", "flops"),
                        ("stepledger_bytes_per_step", "bytes_accessed"),
                        ("stepledger_peak_flops", "peak_flops"),
                        ("stepledger_peak_bytes_per_s", "peak_bw"),
                        ("stepledger_n_devices", "n_devices")):
        for labels, v in samples.get(name, []):
            e = labels.get("entry")
            if e is None:
                continue
            costs.setdefault(e, {})[field] = float(v)
    for e, c in costs.items():
        if e in agg:
            c["n_devices"] = max(int(c.get("n_devices", 1)), 1)
            agg[e]["cost"] = c
    return agg


def _reset_for_tests():
    global _handles
    with _lock:
        _agg.clear()
        _costs.clear()
        _entry_seq.clear()
    _counts["steps"] = 0
    _counts["costs"] = 0
    _handles = None
