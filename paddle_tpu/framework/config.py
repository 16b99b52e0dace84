"""Global framework configuration: default dtype, flags registry.

Reference parity: paddle's gflags `FLAGS_*` registry settable via env and
`paddle.set_flags` (ref: paddle/phi/core/flags.cc era registry; SURVEY.md §5
"Config / flag system"). Here: one typed in-process registry seeded from
`FLAGS_*` environment variables at import.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict

_lock = threading.Lock()


class _Flag:
    __slots__ = ("name", "default", "value", "type", "help")

    def __init__(self, name, default, type_, help_):
        self.name = name
        self.default = default
        self.type = type_
        self.help = help_
        env = os.environ.get(name)
        if env is not None:
            self.value = _parse(env, type_)
        else:
            self.value = default


def _parse(text: str, type_):
    if type_ is bool:
        return text.lower() in ("1", "true", "yes", "on")
    return type_(text)


_FLAGS: Dict[str, _Flag] = {}


def define_flag(name: str, default: Any, help_: str = "", type_=None):
    with _lock:
        if name in _FLAGS:
            return _FLAGS[name]
        f = _Flag(name, default, type_ or type(default), help_)
        _FLAGS[name] = f
        return f


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: _FLAGS[n].value for n in names if n in _FLAGS}


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if k not in _FLAGS:
            define_flag(k, v)
        else:
            _FLAGS[k].value = _parse(v, _FLAGS[k].type) if isinstance(v, str) else v


def get_flag(name: str, default=None):
    f = _FLAGS.get(name)
    return f.value if f is not None else default


# Core flags mirroring the reference set (SURVEY.md §5).
define_flag("FLAGS_check_nan_inf", False, "Check every op output for NaN/Inf.")
define_flag("FLAGS_static_strict_placeholders", False,
            "Raise (instead of warn) when a static-graph placeholder is "
            "coerced to a Python scalar during program capture.")
define_flag("FLAGS_benchmark", False, "Per-op timing dumps.")
define_flag("FLAGS_use_pallas_kernels", True, "Use Pallas fused kernels where available.")
define_flag("FLAGS_cp_ring_balance", "",
            "Context-parallel ring-attention load balancing for the "
            "contiguous-layout path (models/llama.py): 'zigzag' opts "
            "into per-call relayout so every rank does equal causal "
            "work per ring tick (~2x kernel wall-clock at large cp); "
            "empty (default) keeps the contiguous ring — the relayout "
            "gather cost is not chip-measured yet. Streams already in "
            "zigzag layout ignore this flag.")
define_flag("FLAGS_flash_dropout_kernel", False,
            "Route training SDPA with dropout_p>0 to the in-kernel "
            "threefry flash-attention dropout path. Opt-in: "
            "chip_smoke.py holds the kernel to its reference on the "
            "chip, but no benchmark cell trains with dropout, so "
            "nothing has measured it against the XLA path (ROADMAP "
            "D2). Off: dropout "
            "attention takes the XLA reference path; dropout-free "
            "attention still uses the flash kernel.")
define_flag("FLAGS_trace_sample", 0.0,
            "Span-tracing head-sampling probability "
            "(observability/tracing.py): 0 (default) disables tracing "
            "entirely (zero per-step allocations); 1 traces every "
            "request/step; 0<p<1 keeps a deterministic p fraction of "
            "traces. Export with observability.write_trace() — Chrome "
            "trace-event JSON that Perfetto loads directly.",
            type_=float)
define_flag("FLAGS_trace_slow_ms", 0.0,
            "Always-sample-on-slow escape hatch: with tracing enabled, "
            "a trace whose total latency crosses this many milliseconds "
            "is committed to the trace ring even when head sampling "
            "dropped it, and trace_slow_requests_total increments. "
            "0 disables the escape hatch.", type_=float)
define_flag("FLAGS_telemetry_dir", "",
            "Rank-sharded fleet telemetry export root "
            "(observability/fleet.py): when set, a background flusher "
            "writes this rank's shard <dir>/rank_<i>/{metrics.prom,"
            "events.jsonl,trace.json,heartbeat.json,collectives.jsonl} "
            "every FLAGS_telemetry_flush_s seconds and once more at "
            "exit, and eager collectives record (op, seq, enter-time, "
            "duration, bytes) into a bounded ring for cross-rank "
            "straggler alignment (tools/fleet_report.py). Empty "
            "(default) = the fleet layer is fully off: zero "
            "per-collective-call allocations, pinned by "
            "tests/test_fleet_telemetry.py.")
define_flag("FLAGS_telemetry_flush_s", 5.0,
            "Fleet telemetry shard flush interval in seconds "
            "(FLAGS_telemetry_dir). The dead-rank detector treats a "
            "heartbeat more than ~3x this behind the fleet's newest "
            "beat as a stopped rank.", type_=float)
define_flag("FLAGS_timeseries_interval_s", 0.0,
            "Time-series telemetry history "
            "(observability/timeseries.py): when > 0, a per-rank "
            "daemon thread samples load score, SLO burn rates, KV "
            "occupancy and queue depth into a bounded ring every this "
            "many seconds; the fleet flusher exports the ring as "
            "rank_<i>/history.jsonl and /debug/timeseries?secs=N "
            "serves it live (fleet_report renders the per-rank trend). "
            "0 (default) = off: one flag read, zero allocations, "
            "pinned by tests/test_timeseries.py.", type_=float)
define_flag("FLAGS_timeseries_capacity", 1024,
            "Samples retained per time-series history ring "
            "(observability/timeseries.py). Each sample is one small "
            "dict (~200-400 bytes: load, queue depth, KV occupancy, "
            "burn rates), so the memory bound is roughly "
            "capacity * 0.4 KiB per rank — the default 1024 holds "
            "~85 min of history at a 5 s interval in under ~0.5 MiB. "
            "Raise it for long-window anomaly detection "
            "(FLAGS_anomaly) so slow leaks aren't truncated out of "
            "the ring before the detector can see them.", type_=int)
define_flag("FLAGS_anomaly", False,
            "Anomaly detection over the telemetry history "
            "(observability/anomaly.py): after each time-series "
            "sample (requires FLAGS_timeseries_interval_s > 0) run "
            "monotone-growth leak detection on KV/host-tier "
            "occupancy, windowed mean-shift change-points on "
            "TTFT/load/queue, time-to-saturation extrapolation on "
            "queue growth and recovery-storm detection; each verdict "
            "raises an anomaly_active{kind} gauge, a flight-recorder "
            "breadcrumb, and shows in /debug/anomalies, /statusz and "
            "fleet_doctor. Off (default) = one flag read per sample, "
            "zero registry/ring allocations, pinned by "
            "tests/test_anomaly.py.")
define_flag("FLAGS_canary_interval_s", 0.0,
            "Black-box canary prober (observability/canary.py): when "
            "> 0, a daemon thread periodically sends a fixed "
            "synthetic greedy prompt through the registered serving "
            "target (ReplicaServer HTTP loopback or Router), "
            "bit-compares the tokens against the golden reference "
            "(first successful probe self-anchors when no explicit "
            "golden is set), records canary_ttft_seconds/"
            "canary_e2e_seconds with an always-sampled trace, and on "
            "mismatch or timeout flips /healthz to degraded and "
            "raises a canary anomaly verdict. 0 (default) = off: one "
            "flag read, zero allocations, pinned by "
            "tests/test_canary.py.", type_=float)
define_flag("FLAGS_canary_timeout_s", 10.0,
            "Per-probe timeout in seconds for the canary prober; a "
            "probe exceeding this counts as a canary_timeout failure "
            "(degraded /healthz + anomaly verdict).", type_=float)
define_flag("FLAGS_memwatch", False,
            "Memory observability channel (observability/memwatch.py): "
            "per-step HBM watermark gauges from device memory_stats "
            "(live-buffer-sweep fallback on backends without allocator "
            "stats), KV page-pool occupancy + fragmentation histograms "
            "in serving, and static breakdown gauges "
            "(params/optimizer/kv_pages). Off (default) costs one flag "
            "read per step (pinned by tests/test_memwatch.py). OOM "
            "forensic dumps are ALWAYS on — catching a "
            "RESOURCE_EXHAUSTED costs nothing until it fires, and that "
            "is exactly when the data is needed.")
define_flag("FLAGS_memwatch_dump_dir", "",
            "Directory for OOM forensic dumps "
            "(oom_<name>_r<rank>_<pid>_<n>.txt, written through the "
            "atomic writers); empty = current directory, the same "
            "default as the watchdog stall dumps.")
define_flag("FLAGS_memwatch_top", 10,
            "Rows in the ranked live-buffer table of memory reports "
            "and OOM forensic dumps.", type_=int)
define_flag("FLAGS_compilewatch", False,
            "Compile observability channel "
            "(observability/compilewatch.py): counts XLA backend "
            "compiles per watched callable (jit entry points, serving "
            "prefill/decode programs) with "
            "compile-time spans on the tracer, and detects recompile "
            "storms — a callable compiling for more than "
            "FLAGS_compilewatch_storm_shapes distinct argument-shape "
            "signatures after its warmup mark. Off (default) costs one "
            "flag read per wrapped call (pinned by "
            "tests/test_compilewatch.py).")
define_flag("FLAGS_compilewatch_storm_shapes", 4,
            "Distinct post-warmup shape signatures per callable that "
            "trigger a recompile-storm report citing the offending "
            "shapes (shape churn belongs in pow2 buckets, not the jit "
            "executable cache).", type_=int)
define_flag("FLAGS_stepledger", False,
            "Step-time ledger channel (observability/stepledger.py): "
            "reconcile every train/decode step's wall time into named "
            "buckets (device compute via block_until_ready windows, "
            "collective wait, data wait, compile, host dispatch, "
            "residual), exported as stepledger_* families and per rank "
            "via the fleet flusher (rank_<i>/ledger.prom); "
            "tools/step_ledger.py prints the waterfall + per-op "
            "roofline + top optimization targets. Blocking on step "
            "outputs serializes async dispatch — a measurement mode, "
            "not a production default. Off (default) costs one flag "
            "read per step (pinned by tests/test_stepledger.py).")
define_flag("FLAGS_stepledger_block_every", 1,
            "With FLAGS_stepledger on, block_until_ready on the step "
            "outputs every N-th step (1 = every step) so the measured "
            "dispatch window includes the true device tail; unblocked "
            "steps attribute only the host-visible window.", type_=int)
define_flag("FLAGS_telemetry_port", 0,
            "Live telemetry plane (observability/httpd.py): when > 0, a "
            "per-rank daemon-thread HTTP server (stdlib http.server, "
            "zero new deps) binds this port and serves /metrics "
            "(Prometheus text), /healthz (liveness: watchdog stall, "
            "engine poison, heartbeat freshness), /readyz (warmup done "
            "+ KV pool non-exhausted), /statusz (JSON status), "
            "/debug/stacks and /debug/trace?secs=N. 0 (default) = off: "
            "one flag read per step, zero registry/span allocations "
            "(pinned by tests/test_telemetry_httpd.py). Launcher "
            "--telemetry_port assigns base+rank per worker.", type_=int)
define_flag("FLAGS_healthz_stale_s", 0.0,
            "/healthz heartbeat-freshness threshold in seconds: when "
            "> 0 and the last serving/train step heartbeat is older "
            "than this, /healthz reports unhealthy (503). 0 (default) "
            "= report the age but never fail on it — an idle serving "
            "engine between requests is healthy, not dead.",
            type_=float)
define_flag("FLAGS_slo_window_s", 300.0,
            "Base SLO evaluation window in seconds (observability/"
            "slo.py). Burn-rate alert policies derive their window "
            "pairs from it: fast_burn = (1x, 12x) at burn >= 14.4, "
            "slow_burn = (6x, 72x) at burn >= 6 — the SRE multi-window "
            "multi-burn-rate pattern. The default 300 reproduces the "
            "classic 5m/1h + 30m/6h ladder.", type_=float)
define_flag("FLAGS_slo_ttft_p95_ms", 1000.0,
            "TTFT SLO threshold in milliseconds: the ttft_p95 "
            "objective requires 95% of requests to see their first "
            "token within this budget (evaluated from the "
            "serving_ttft_seconds histogram; thresholds snap to the "
            "shared latency bucket ladder).", type_=float)
define_flag("FLAGS_slo_router_ttft_p95_ms", 1500.0,
            "Routed-TTFT SLO threshold in milliseconds for the "
            "multi-replica router (inference/router.py): the "
            "router_ttft_p95 objective requires 95% of routed "
            "requests to see their first token within this budget, "
            "measured submit -> first committed token across router "
            "queue + route + replica prefill (the router_ttft_seconds "
            "histogram; evaluated by the router's own SloEngine, not "
            "default_objectives()).", type_=float)
define_flag("FLAGS_slo_decode_p50_ms", 250.0,
            "Per-token decode SLO threshold in milliseconds: the "
            "decode_p50 objective requires 50% of decode steps to "
            "commit each token within this budget (evaluated from the "
            "serving_token_decode_seconds histogram).", type_=float)
define_flag("FLAGS_slo_error_budget", 0.01,
            "Error-budget fraction for the error_rate SLO objective: "
            "UNRECOVERED serving failures (engine poisons, requests "
            "dropped after their retry budget; serving_errors_total) "
            "may be at most this fraction of outcomes (errors + "
            "finished requests) before the budget burns. Failures the "
            "engine heals from (drain->rebuild->re-admit) count into "
            "serving_recoveries_total instead and do not burn budget.",
            type_=float)
define_flag("FLAGS_quant_matmul", "xla",
            "Dispatch for the weight-only quantized linear matmul "
            "(kernels/quant_matmul.py): 'xla' (default) is the "
            "traced-dequant XLA expression; 'fused' takes the fused "
            "dequant-in-kernel Pallas path at the largest supported "
            "block grid, and the XLA expression where the shape is not "
            "supported.")
define_flag("FLAGS_spec_decode", 0,
            "Self-speculative decoding window for the serving engine "
            "(inference/serving.py): when >= 2, greedy decode drafts "
            "window-1 tokens with the cheap draft path, verifies the "
            "whole window in ONE batched target forward over the paged "
            "KV cache, and commits the greedy-exact accepted prefix "
            "plus one corrected token (output token streams are "
            "bit-identical to non-speculative greedy decoding; "
            "rejection rewinds by page-table/context truncation). 0 "
            "(default) = off. Engine kwarg spec_decode overrides.",
            type_=int)
define_flag("FLAGS_spec_draft_layers", 0,
            "Layers in the shallow-exit self-speculative draft path: "
            "the draft runs the first N decoder layers + final norm + "
            "lm head (LayerSkip-style), reusing the target's exact "
            "paged KV for those layers. 0 (default) = half the model's "
            "layers (rounded up). Ignored when the engine was given a "
            "separate draft_model.", type_=int)
define_flag("FLAGS_chaos", "",
            "Deterministic fault-injection schedule (faults/chaos.py): "
            "';'-separated entries `site@key=val:key=val`. Sites: "
            "collective.stall, collective.fail, decode.oom, "
            "checkpoint.torn_write, rank.kill, rank.slow, "
            "dataloader.hang. Triggers: step=N (fire when the caller's "
            "step — or the site's invocation index — equals N), p=F "
            "(seeded pseudo-probability per invocation), n=K (max "
            "fires), rank=R (only this rank), delay=S (seconds, for "
            "stall/slow/hang). Empty (default) = chaos off; the "
            "disabled path is one flag read, zero allocations.")
define_flag("FLAGS_chaos_seed", 0,
            "Seed for the FLAGS_chaos p= pseudo-probability triggers: "
            "fire/no-fire is a pure hash of (seed, site, invocation "
            "index), so a schedule replays identically across runs and "
            "ranks.", type_=int)
define_flag("FLAGS_chaos_dir", "",
            "When set, n=-limited chaos fires persist sentinel files "
            "here so a schedule survives a process restart — e.g. "
            "`rank.kill@step=5:n=1` kills once and stays quiet after "
            "the elastic controller restarts the pod (the drill in "
            "tools/chaos_drill.py). Empty: fire counts are in-memory "
            "only.")
define_flag("FLAGS_serving_max_recoveries", 3,
            "Recovery budget for the serving engine's self-healing "
            "path (inference/serving.py): at most this many "
            "drain->rebuild->re-admit cycles per engine before the "
            "next fatal fault poisons it permanently. Each recovery "
            "backs off exponentially from "
            "FLAGS_serving_recovery_backoff_s.", type_=int)
define_flag("FLAGS_serving_request_retries", 2,
            "Per-request retry budget across engine recoveries: an "
            "in-flight request is re-queued (prompt + tokens committed "
            "so far) at most this many times; past the budget it is "
            "dropped and counts as an unrecovered failure "
            "(serving_errors_total).", type_=int)
define_flag("FLAGS_serving_recovery_backoff_s", 0.5,
            "Base of the exponential backoff the serving engine sleeps "
            "between draining and re-admitting during a recovery: "
            "backoff * 2^(recovery-1) seconds. 0 disables the sleep "
            "(tests).", type_=float)
define_flag("FLAGS_collective_timeout_s", 0.0,
            "Watchdog deadline for eager collectives "
            "(distributed/collective.py): when > 0, a collective that "
            "has not returned after this many seconds records a "
            "flight-recorder event, increments "
            "collective_timeouts_total, and raises CollectiveTimeout "
            "in the stalled thread — converting an indefinite fleet "
            "stall into a nonzero exit the elastic controller can "
            "restart. 0 (default) = no watchdog; the disabled path is "
            "one flag read.", type_=float)
define_flag("FLAGS_train_overlap", True,
            "Master switch for the train-step overlap engine. On "
            "(default): DataParallel.sync_gradients coalesces grads "
            "into size-bucketed flat reduces dispatched "
            "asynchronously (distributed/parallel.py) and the jitted "
            "train_step annotates its grad tree bucket-by-bucket so "
            "XLA's latency-hiding scheduler can overlap bucket N's "
            "collective with bucket N+1's backward compute "
            "(jit/api.py). Off: the legacy one-all_reduce-per-param "
            "loop — bit-identical losses either way (the reductions "
            "are elementwise over the same addends).")
define_flag("FLAGS_grad_bucket_mb", 25,
            "Coalescing bucket size (MiB) for the bucketed gradient "
            "reducer (distributed/parallel.py, jit/api.py): grads are "
            "flattened into flat buffers of at most this many MiB in "
            "reverse-backward order, so the first bucket's reduce can "
            "start while earlier layers are still computing grads. "
            "Matches the Paddle DataParallel comm_buffer_size default "
            "of 25. <= 0 degenerates to one bucket per param.",
            type_=int)
define_flag("FLAGS_prefetch_depth", 2,
            "Bounded staging depth of the double-buffered device "
            "prefetcher (io/dataloader.py DevicePrefetcher): a "
            "background thread keeps up to this many batches "
            "device_put ahead of the consuming train loop (sharded "
            "correctly from the start), so batch N+1's host->device "
            "transfer overlaps batch N's compute and the stepledger "
            "data_wait bucket trends to zero. <= 0 disables "
            "prefetching (the iterator is passed through unchanged).",
            type_=int)
define_flag("FLAGS_scheduler_policy", "fifo",
            "SchedulerPolicy the serving engine resolves at "
            "construction (inference/scheduler.py registry): 'fifo' "
            "(default — head-of-line admission, youngest-victim "
            "recompute preemption, pow2/page-multiple prefill buckets, "
            "{1, decode_burst} burst sizing; bit-identical to the "
            "pre-extraction engine) or 'slo' (TTFT-burn-aware: sheds "
            "head-of-line blocking for shortest-prompt-first while the "
            "fast TTFT burn alert fires, and preempts the slot with "
            "the most remaining budget instead of the youngest). An "
            "explicit scheduler= argument to ServingEngine wins over "
            "the flag.")
define_flag("FLAGS_router_policy", "least_loaded",
            "Replica-choice policy of the serving router "
            "(inference/router.py): 'least_loaded' (default — lowest "
            "serving_load_score among ready replicas, the contract "
            "documented on SloEngine.load_score), 'round_robin', or "
            "'cache_affinity' (rendezvous-hash the request's "
            "page-aligned prompt prefix so repeat prefixes land on the "
            "replica whose prefix cache owns the pages; requests "
            "without a full-page prefix fall back to least-loaded). "
            "Replicas failing /readyz (mid-recovery, poisoned, KV "
            "exhausted) drain automatically under every policy.")
define_flag("FLAGS_prefix_cache", 0,
            "Prefix-cache KV reuse for the serving engine "
            "(inference/prefix_cache.py): when 1, freshly prefilled "
            "FULL pages are cached in a content-addressed trie and "
            "admission matches the longest page-aligned cached prefix, "
            "sharing those pages (ref-counted) into the new slot's "
            "block-table row so only the uncached suffix is prefilled. "
            "Zero-ref pages are LRU-evicted under pool pressure. "
            "Greedy output token streams are bit-identical to cache-off "
            "decoding. 0 (default) = off. Engine kwarg prefix_cache "
            "overrides. Incompatible with a separate draft_model.",
            type_=int)
define_flag("FLAGS_prefill_chunk", 0,
            "Chunked-prefill token budget for the serving engine: when "
            "> 0, prompt prefill (the uncached suffix, when "
            "FLAGS_prefix_cache hits) runs in page-aligned chunks of at "
            "most this many tokens through the paged window program, "
            "interleaved with decode bursts — a long prefill no longer "
            "stalls every in-flight request's ITL. The scheduler "
            "policy's prefill_chunk_budget hook can shrink a step's "
            "chunk (slo halves it under TTFT burn). 0 (default) = "
            "dense one-shot prefill. Engine kwarg prefill_chunk "
            "overrides. Incompatible with a separate draft_model.",
            type_=int)
define_flag("FLAGS_kv_host_cache_mb", 0,
            "Host-RAM tier of the tiered prefix cache "
            "(inference/prefix_cache.py TieredStore): when > 0, KV "
            "pages the trie LRU-evicts under pool pressure spill "
            "their bytes into a host-RAM store bounded by this many "
            "MB instead of being dropped; a later admission matching "
            "a spilled chunk promotes the page back into the paged "
            "pool (scatter) and prefills only what no tier holds. "
            "Over budget, the LRU host entries demote to the disk "
            "tier (FLAGS_kv_disk_cache_dir) or drop. 0 (default) = "
            "off: eviction drops pages exactly as before, zero "
            "allocations on the serving hot path. Engine kwarg "
            "kv_host_cache_mb overrides. Requires FLAGS_prefix_cache.",
            type_=int)
define_flag("FLAGS_kv_disk_cache_dir", "",
            "Disk tier of the tiered prefix cache: directory for "
            "spilled KV page files (one length-prefixed file per "
            "page, content-keyed by the page's token-chunk chain "
            "digest). Pages land here when the host tier is full or "
            "absent; FLAGS_kv_disk_cache_mb bounds the directory "
            "(LRU delete). A truncated/corrupt page file reads as a "
            "clean cache miss (counted), never a crash. '' (default) "
            "= no disk tier. Engine kwarg kv_disk_cache_dir "
            "overrides. Requires FLAGS_prefix_cache.")
define_flag("FLAGS_kv_disk_cache_mb", 256,
            "Size bound (MB) of the disk tier under "
            "FLAGS_kv_disk_cache_dir: past it the least-recently-"
            "used page files are deleted. Only read when the disk "
            "tier is on.", type_=int)
define_flag("FLAGS_router_admission", True,
            "Router admission control: when every ready replica's "
            "fast TTFT burn alert is firing (or no replica is ready), "
            "new requests are shed with 429 instead of queued — "
            "protecting in-flight SLOs instead of building an "
            "unbounded queue. Off: the router always enqueues.")
define_flag("FLAGS_router_queue_depth", 256,
            "Hard cap on the router's own queue (per router, across "
            "replicas): past it requests shed with 429 regardless of "
            "burn state — bounds memory and tail latency under "
            "overload.", type_=int)
define_flag("FLAGS_requestlog", False,
            "Per-request accounting ledger "
            "(observability/requestlog.py): when on, every FINISHED "
            "serving request appends one structured record (trace_id, "
            "tenant from the X-PT-Tenant header, prompt/output token "
            "counts, queue/TTFT/ITL/total latencies, prefix-cache hit "
            "ratio, KV tier promotions, spec-decode acceptance, "
            "retries/recoveries touched, outcome) to a bounded ring; "
            "/debug/requests?tenant=&last=N serves it live, the fleet "
            "flusher exports rank_<i>/requests.jsonl, and "
            "usage_tokens_total{tenant,kind} + per-tenant latency "
            "families + TTFT/decode trace_id exemplars land in "
            "/metrics. Off (default) = one flag read per finished "
            "request, zero allocations, pinned by "
            "tests/test_requestlog.py.")
define_flag("FLAGS_requestlog_capacity", 2048,
            "Records retained in the per-request accounting ring "
            "(observability/requestlog.py). Each record is one small "
            "dict (~300 bytes: ids, tenant, token counts, latencies), "
            "so the memory bound is roughly capacity * 0.3 KiB per "
            "rank; the tenant usage rollup (/debug/requests, "
            "fleet_report's usage-per-tenant section) only sees what "
            "the ring still holds — raise it on long-lived replicas "
            "so billing windows aren't truncated.", type_=int)
define_flag("FLAGS_lockwatch", 0,
            "Runtime lock instrumentation "
            "(observability/lockwatch.py): when on, the locks the "
            "shared-state owners create through the lockwatch "
            "factories (metrics registry, httpd route/engine tables, "
            "fleet exporter, router policy, serving replica) measure "
            "per-acquire wait and hold times "
            "(lock_wait_seconds_total{lock} / lock_hold_seconds{lock} "
            "appended to /metrics and fleet shards, surfaced in "
            "/statusz and fleet_report's lock-contention section) and "
            "maintain the runtime lock-order graph from per-thread "
            "held-sets: an observed ABBA inversion — two locks taken "
            "in opposite orders anywhere in the process's lifetime, "
            "no deadlock required — raises a flight-recorder verdict "
            "citing the static lock-order-cycle rule plus "
            "lockwatch_inversions_total. Off (default) the factories "
            "return plain threading primitives: one flag read at "
            "lock creation, zero per-acquire overhead. Read at lock "
            "CREATION time — set the env var (or set_flags) before "
            "building the engine/server. Pinned by "
            "tests/test_lockwatch.py; tools/lockwatch_smoke.py is "
            "the CI gate (synthetic ABBA must be caught, real "
            "scrape-vs-decode stress must stay inversion-free).",
            type_=int)


# ---------------------------------------------------------------------------
# Default dtype (paddle.get_default_dtype / set_default_dtype)
# ---------------------------------------------------------------------------
_default_dtype_name = "float32"


def set_default_dtype(d):
    global _default_dtype_name
    from . import dtype as dtype_mod

    nd = dtype_mod.to_np_dtype(d)
    _default_dtype_name = dtype_mod.from_np_dtype(nd).name


def get_default_dtype() -> str:
    return _default_dtype_name


def get_default_dtype_obj():
    from . import dtype as dtype_mod

    return dtype_mod.DType._registry[_default_dtype_name]
