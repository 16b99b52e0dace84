"""Runtime telemetry: metrics registry + exporters + stall flight
recorder + span tracer (SURVEY.md §5 "Metrics / logging").

- `metrics` — Counter/Gauge/Histogram cells, labeled families, the
  process-default registry, Prometheus-text and JSONL exporters.
- `flight_recorder` — bounded event ring + watchdog thread that turns a
  silent hang into a thread-stack dump and a `stalls_total` increment.
- `tracing` — per-request / per-step span timelines with head-based
  sampling (`FLAGS_trace_sample`) and Chrome trace-event export that
  Perfetto loads directly; `tools/trace_report.py` prints TTFT
  breakdowns and the critical path from the exported JSON.
- `fleet` — rank-sharded export of all channels
  (`FLAGS_telemetry_dir` → `rank_<i>/` shards on a background flusher),
  a per-op collective sequence log, and the cross-rank aggregator:
  merged fleet exposition + multi-rank Chrome trace, dead-rank
  detection, the collective straggler report, and the HBM-skew table
  (`tools/fleet_report.py`).
- `memwatch` — live HBM accounting (fourth channel): per-step
  watermark gauges from `device.memory_stats()` / live-buffer sweeps,
  static breakdown gauges (params / optimizer / KV pages / XLA
  `memory_analysis()` splits), and the always-on OOM forensics handler
  (`is_oom` / `dump_oom` — ranked live-buffer report through the
  atomic writers; the serving engine preempts one slot before
  poisoning).
- `compilewatch` — compile accounting (fifth channel): every wrapped
  jit entry point (StaticFunction, train_step, serving programs) gets per-callable compile counts + compile-time
  spans, and recompile storms after warmup are detected and reported
  with the offending argument shapes.
- `httpd` — the live telemetry plane (seventh channel, the first
  pull-based one): a per-rank stdlib HTTP server
  (`FLAGS_telemetry_port`) serving `/metrics` (registry-locked
  Prometheus exposition), `/healthz` (poison/stall/heartbeat
  liveness), `/readyz` (warmup + KV-pool admission gate), `/statusz`
  (JSON status), `/debug/stacks`, `/debug/trace?secs=N`; fleet
  heartbeats advertise the endpoint for `fleet_report --scrape`.
- `slo` — declarative SLO engine: objectives as data (ttft_p95 /
  decode_p50 / error_rate / availability), sliding-window compliance
  from histogram snapshots, SRE multi-window burn-rate alerts
  (`slo_compliance` / `slo_burn_rate` / `slo_alert` gauges) and the
  composite `serving_load_score` admission signal.
- `stepledger` — step-time ledger (sixth channel): each train/decode
  step's wall time reconciled into named buckets (device compute via
  `block_until_ready` windows under `FLAGS_stepledger`, collective
  wait, data wait, compile, host dispatch, residual), plus a per-
  executable roofline classification and MFU from
  `compiled.cost_analysis()` against the shared `device_peaks` table;
  `tools/step_ledger.py` prints the waterfall and the top
  optimization targets.
- `device_peaks` — the ONE per-chip bf16-peak-FLOPs / HBM-bandwidth
  table shared by PerfMeter's MFU gauge, bench.py, tools/mfu_sweep.py,
  and the stepledger roofline.
- `lockwatch` — runtime deadlock detector + lock contention telemetry
  (ninth channel, `FLAGS_lockwatch`, the dynamic half of the tpu-lint
  concurrency rules): instrumented Lock/RLock/Condition factories
  adopted by the metrics registry, httpd, fleet exporter, router and
  replica; per-lock wait/hold stats, the runtime lock-order graph, and
  ABBA-inversion verdicts (flight-recorder event + cycle chains citing
  the static `lock-order-cycle` rule) detected from *sequential*
  executions — no actual deadlock required. Exposition feeds /statusz
  and the fleet report's "lock contention per rank" section; off path
  returns plain threading primitives (flag read at creation time).

The channels correlate: spans and flight-recorder breadcrumbs carry
the same `rid`/`trace_id` fields, the watchdog stall dump appends the
in-flight span stack AND the current memory report, slow traces bump
`trace_slow_requests_total`, and compiles land as `compile.<name>`
spans on the same timeline as the steps they stall. The serving
engine's own phases (`tracing.phase`) are profiler annotations as well,
flag or no flag, so a `jax.profiler` session holds them on the device
trace's clock.

Exported metric names are documented in README.md ("Observability").
"""
from .metrics import (  # noqa: F401
    LATENCY_BUCKETS,
    Counter,
    Gauge,
    HandleCache,
    Histogram,
    Registry,
    default_registry,
    fleet_labels,
    rank_world,
    set_default_registry,
    snapshot,
    to_prometheus,
    write_jsonl,
    write_prometheus,
)
from . import compilewatch  # noqa: F401  (compile counts + storm detect)
from . import device_peaks  # noqa: F401  (the shared per-chip peak table)
from . import fleet  # noqa: F401  (rank-sharded export + aggregation)
from . import httpd  # noqa: F401  (per-rank HTTP exposition plane)
from . import lockwatch  # noqa: F401  (runtime deadlock detector)
from . import memwatch  # noqa: F401  (HBM accounting + OOM forensics)
from . import slo  # noqa: F401  (SLO objectives + burn-rate alerts)
from . import stepledger  # noqa: F401  (step-time ledger + roofline)
from .flight_recorder import (  # noqa: F401
    FlightRecorder,
    Watchdog,
    beat_all,
    default_recorder,
    record_event,
)
from .tracing import (  # noqa: F401
    Trace,
    Tracer,
    default_tracer,
    open_spans,
    set_default_tracer,
    span,
    start_trace,
    to_chrome_trace,
    write_trace,
)
