"""LLM serving engine: paged KV cache + continuous batching.

Reference parity: the fused_multi_transformer_op serving configuration
(SURVEY.md §2.1 "Fused transformer ops" — "the serving engine";
BASELINE.json config 5). TPU-native design (vLLM-style split): the host owns
the scheduler — slot admission, page accounting, EOS/eviction — while the
device runs ONE jitted decode step for all active slots over the paged
Pallas cache (kernels/paged_attention.py). Prefill runs per-request through
the model's dense-cache path, then scatters K/V into that request's pages.
"""
from __future__ import annotations

import time as _time_mod

from dataclasses import dataclass, field
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as _faults
from ..autograd import tape as _tape
from ..kernels import paged_attention as _pa
from ..observability import compilewatch as _cw
from ..observability import fleet as _fleet
from ..observability import flight_recorder as _flight
from ..observability import httpd as _httpd
from ..observability import memwatch as _memwatch
from ..observability import metrics as _om
from ..observability import requestlog as _reqlog
from ..observability import slo as _slo
from ..observability import stepledger as _stepledger
from ..observability import tracing as _trace
from ..tensor import Tensor, as_array
from . import kv_fabric as _fab
from . import prefix_cache as _pc
from . import scheduler as _sched

# what a prefill program hands on of the counts its model makes: they ride
# on the `serving.emit` phase that commits its first tokens as `prefill_<k>`
# (a burst's counts keep their own names on the phase after the burst)
_PREFILL_COUNTS = ("expert_pairs", "expert_rows")


class _EngineMetrics:
    """Serving metric handles, resolved ONCE per engine against the
    current default registry — the decode loop then only touches plain
    float cells (the overhead guard test asserts zero registry
    allocations per step). Metric names documented in README.md
    ("Observability")."""

    __slots__ = ("ttft", "step_lat", "token_lat", "queue_depth",
                 "queue_wait", "occupancy", "page_util", "prefill_hits",
                 "prefill_misses", "preemptions", "aborts", "tokens",
                 "finished", "poisoned", "errors", "recoveries",
                 "kv_occupancy", "kv_frag", "kv_free", "spec_proposed",
                 "spec_accepted", "spec_acceptance", "cache_hits",
                 "cache_misses", "cache_evictions", "cached_ratio",
                 "tier_hits", "tier_misses", "tier_spills",
                 "tier_demotions", "tier_drops", "tier_corrupt",
                 "tier_promote_lat", "tier_pages", "usage_tokens",
                 "tenant_ttft", "tenant_total")

    def __init__(self, reg=None):
        reg = reg or _om.default_registry()
        self.ttft = reg.histogram(
            "serving_ttft_seconds",
            "Time from add_request() to the request's first committed "
            "token (queue wait + prefill).")
        self.step_lat = reg.histogram(
            "serving_decode_step_seconds",
            "Wall time of one compiled decode dispatch + token harvest "
            "(a burst counts as one step).")
        self.token_lat = reg.histogram(
            "serving_token_decode_seconds",
            "Per-token decode latency: step wall time / tokens committed "
            "that step (one observation per step).")
        self.queue_depth = reg.gauge(
            "serving_queue_depth",
            "Requests waiting for a slot (pending, not yet prefilled).")
        self.queue_wait = reg.histogram(
            "serving_queue_wait_seconds",
            "Time a request spent queued before admission to a slot.")
        self.occupancy = reg.gauge(
            "serving_batch_occupancy",
            "Active slots / max_batch at the last decode step.")
        self.page_util = reg.gauge(
            "serving_page_pool_utilization",
            "Fraction of KV pages allocated (1 - free/total).")
        self.prefill_hits = reg.counter(
            "serving_prefill_bucket_hits_total",
            "Prefill calls served by an already-compiled "
            "(batch, token-bucket) program.")
        self.prefill_misses = reg.counter(
            "serving_prefill_bucket_misses_total",
            "Prefill calls that compiled a new bucket program "
            "(in-traffic compiles; warmup() prepays these).")
        self.preemptions = reg.counter(
            "serving_preemptions_total",
            "Slots evicted by page-pool exhaustion (recompute policy).")
        self.aborts = reg.counter(
            "serving_aborts_total", "Requests dropped via abort().")
        self.tokens = reg.counter(
            "serving_tokens_total",
            "Tokens committed to request streams (prefill-sampled first "
            "tokens included).")
        self.finished = reg.counter(
            "serving_requests_finished_total",
            "Requests that ran to eos or their max_new_tokens budget.")
        self.poisoned = reg.gauge(
            "serving_engine_poisoned",
            "1 once a compiled decode call raised after donating the KV "
            "page pools (engine must be recreated; step()/run() fail "
            "fast).")
        self.errors = reg.counter(
            "serving_errors_total",
            "UNRECOVERED serving failures: engine poisons and requests "
            "dropped after exhausting their recovery retry budget. "
            "Failures the engine heals from (drain->rebuild->re-admit) "
            "count into serving_recoveries_total instead. The "
            "error_rate SLO objective (observability/slo.py) burns its "
            "budget on these, against serving_requests_finished_total "
            "as the good-event counter.")
        self.recoveries = reg.counter(
            "serving_recoveries_total",
            "Successful engine self-heals (drain->rebuild->re-admit; "
            "README.md \"Fault tolerance\"), by cause: decode_oom "
            "(a dispatch-time RESOURCE_EXHAUSTED), oom_storm (OOM "
            "persisted past the single preemption round), "
            "donated_buffers (a compiled call raised after donating "
            "the KV pools). Bounded by FLAGS_serving_max_recoveries.",
            labels=("cause",))
        # memwatch channel (README.md "Memory & compile observability"):
        # per-step KV page-pool distributions, observed only when
        # FLAGS_memwatch is on — handles still resolve here so the on
        # path allocates nothing per step
        self.kv_occupancy = reg.histogram(
            "serving_kv_pool_occupancy",
            "Per-step fraction of KV pages allocated (distribution of "
            "serving_page_pool_utilization over steps; FLAGS_memwatch).",
            buckets=_memwatch.RATIO_BUCKETS)
        self.kv_frag = reg.histogram(
            "serving_kv_fragmentation",
            "Per-step internal fragmentation of allocated KV pages: "
            "1 - cached tokens / (allocated pages * page_size). High "
            "values mean page_size is too coarse for the traffic's "
            "context lengths (FLAGS_memwatch).",
            buckets=_memwatch.RATIO_BUCKETS)
        self.kv_free = reg.gauge(
            "serving_kv_pages_free",
            "KV pages currently free in the pool (FLAGS_memwatch).")
        # speculative decoding (spec_decode >= 2): draft-token economics.
        # acceptance = accepted / proposed; each verify forward commits
        # accepted + 1 tokens, so decode throughput scales with it
        self.spec_proposed = reg.counter(
            "spec_tokens_proposed_total",
            "Draft tokens proposed by the speculative-decoding draft "
            "path (per active slot per spec round: window-1, capped at "
            "the slot's remaining token budget so acceptance measures "
            "draft quality, not budget geometry).")
        self.spec_accepted = reg.counter(
            "spec_tokens_accepted_total",
            "Proposed draft tokens the target verify forward accepted "
            "(greedy-exact prefix match; the +1 corrected token each "
            "round is not counted here).")
        self.spec_acceptance = reg.histogram(
            "serving_spec_acceptance_ratio",
            "Per-request draft acceptance rate observed at request "
            "finish (accepted / proposed over the request's life).",
            buckets=_memwatch.RATIO_BUCKETS)
        # prefix cache (FLAGS_prefix_cache): token-level reuse economics.
        # hit rate = hits / (hits + misses) — the fleet report's per-rank
        # cache_hit% column; counters only move while the cache is on
        self.cache_hits = reg.counter(
            "serving_prefix_cache_hits_total",
            "Prompt tokens served from the prefix cache at admission "
            "(page-aligned shared-page reuse; their prefill is skipped).")
        self.cache_misses = reg.counter(
            "serving_prefix_cache_misses_total",
            "Prompt tokens NOT covered by a cached prefix at admission "
            "(the suffix the engine actually prefills).")
        self.cache_evictions = reg.counter(
            "serving_prefix_cache_evictions_total",
            "Cached KV pages evicted under pool pressure (zero-ref LRU; "
            "recovery cache drops count here too).")
        self.cached_ratio = reg.histogram(
            "serving_prefix_cached_token_ratio",
            "Per-request fraction of the prompt served from the prefix "
            "cache, observed at admission (0.0 rows are cold misses).",
            buckets=_memwatch.RATIO_BUCKETS)
        # tiered prefix cache (FLAGS_kv_host_cache_mb /
        # FLAGS_kv_disk_cache_dir): handles resolve here, label
        # children resolve once at tier construction — the counters
        # only move while a tier is on
        self.tier_hits = reg.counter(
            "serving_kv_tier_hits_total",
            "KV pages promoted back into the paged pool from a spill "
            "tier at admission, by tier (host | disk).",
            labels=("tier",))
        self.tier_misses = reg.counter(
            "serving_kv_tier_misses_total",
            "Spill-tier lookups that found no payload (the chunk fell "
            "off every tier — admission recomputes it).")
        self.tier_spills = reg.counter(
            "serving_kv_tier_spills_total",
            "Evicted KV pages whose bytes spilled into a tier instead "
            "of being dropped, by the tier they landed in.",
            labels=("tier",))
        self.tier_demotions = reg.counter(
            "serving_kv_tier_demotions_total",
            "LRU demotions from the host-RAM tier to the disk tier "
            "under FLAGS_kv_host_cache_mb pressure.")
        self.tier_drops = reg.counter(
            "serving_kv_tier_drops_total",
            "Spilled pages that fell off the bottom tier (disk over "
            "FLAGS_kv_disk_cache_mb, or host overflow with no disk "
            "tier).")
        self.tier_corrupt = reg.counter(
            "serving_kv_tier_corrupt_total",
            "Disk-tier page files that failed the length/checksum "
            "verify on read (truncated/corrupt -> clean miss, file "
            "removed).")
        self.tier_promote_lat = reg.histogram(
            "serving_kv_tier_promote_seconds",
            "Wall time of one admission's spill-tier promotion batch "
            "(payload decode + device scatter dispatch), by source "
            "tier.", labels=("tier",))
        self.tier_pages = reg.gauge(
            "serving_kv_tier_pages",
            "KV pages currently resident per spill tier (host | "
            "disk); the hbm tier is the trie's cached_pages.",
            labels=("tier",))
        # per-tenant accounting families (FLAGS_requestlog): fed once
        # per FINISHED request at _finish, never on the decode path.
        # Tenant children resolve lazily into the engine's
        # _tenant_cells cache (tenants are dynamic — the _tier_cells
        # resolve-once discipline, per tenant instead of per tier)
        self.usage_tokens = reg.counter(
            "usage_tokens_total",
            "Tokens accounted to a tenant at request finish, by kind "
            "(prompt | output). Tenant comes from the X-PT-Tenant "
            "header (default \"default\") and survives the "
            "disaggregated prefill->decode handoff; the request "
            "ledger (observability/requestlog.py, /debug/requests) "
            "records the same attribution per request.",
            labels=("tenant", "kind"))
        self.tenant_ttft = reg.histogram(
            "tenant_ttft_seconds",
            "Per-tenant time-to-first-token, observed at request "
            "finish from the ledger's retained timing "
            "(FLAGS_requestlog; answers 'which tenant burned the "
            "TTFT budget').", labels=("tenant",))
        self.tenant_total = reg.histogram(
            "tenant_request_seconds",
            "Per-tenant end-to-end request latency (enqueue/attach "
            "to finish), observed at request finish "
            "(FLAGS_requestlog).", labels=("tenant",))


@dataclass
class _Slot:
    request_id: int = -1
    tokens: list = field(default_factory=list)  # generated tokens
    prompt_len: int = 0
    context_len: int = 0  # tokens currently in the paged cache
    max_new_tokens: int = 0
    active: bool = False
    n_pages: int = 0      # pages currently allocated to this slot
    admit_seq: int = 0    # admission order (preemption picks the youngest)
    needs_first_sample: bool = False  # consume prefill-time sample next step
    _first_token: int = -1
    trace_id: int = -1    # span-tracing correlation id (-1: not traced)
    # speculative decoding per-request accounting (acceptance histogram
    # observed at finish; reset at admission)
    spec_proposed: int = 0
    spec_accepted: int = 0
    # chunked-prefill continuation: while `prefilling` the slot owns its
    # pages and a PARTIAL context (context_len < len(_pf_ctx)) and is
    # excluded from decode dispatches; _prefill_chunk_round advances it
    # one scheduler-budgeted chunk per step until the suffix completes
    prefilling: bool = False
    _pf_ctx: object = None        # full target context (np int64)
    _pf_chunks_done: int = 0
    _pf_n_chunks: int = 0         # estimate at admission (trace attrs)
    # per-request sampling: only the greedy flag lives on the slot (the
    # all-greedy fast path reads it every step); numeric params stay in
    # ServingEngine._req_params — ONE source of truth across preemption


@dataclass
class KVHandoff:
    """A prefilled request detached from one engine for adoption by
    another (the disaggregated prefill->decode handoff): the host-side
    gather of its KV pages plus everything the decode engine needs to
    resume — context, committed tokens, the not-yet-committed
    prefill-time sample, and the per-request sampling params."""

    prompt_ids: np.ndarray
    tokens: list
    context_len: int
    max_new_tokens: int
    needs_first_sample: bool
    first_token: int
    req_params: dict
    page_size: int
    kv_cache_quant: object
    k: list          # per layer: [that layer's kvh, n_pages, page_size, width]
    v: list
    k_scales: object  # per layer or None (int8 KV only)
    v_scales: object
    # distributed-trace identity (tracing.inject() of the prefill-side
    # trace, None when untraced): the attaching engine adopts it so
    # prefill and decode land on ONE stitched timeline
    trace_ctx: object = None


@dataclass
class FinishedRequest:
    request_id: int
    prompt_ids: np.ndarray
    output_ids: np.ndarray
    # span-tracing correlation: the request's trace_id (None when tracing
    # was off at add_request) — grep the Chrome trace / flight-recorder
    # ring for the same id
    trace_id: object = None


class ServingEngine:
    """Continuous-batching decoder over a paged KV cache.

    engine = ServingEngine(model, max_batch=8, max_seq_len=512)
    rid = engine.add_request(prompt_ids, max_new_tokens=64)
    finished = engine.run()          # or: engine.step() in a loop

    page_size: 16 (vLLM-style) minimizes fragmentation; on the chip float
    pages of 128 tokens and more decode through the page-grid Pallas
    kernel, which reads the live pages only (PERF.md section 6, PR 28:
    7.8x on the decode step at page 256), while smaller pages gather the
    whole mapped context (`kernels.paged_attention_dispatch`).
    """

    def __init__(self, model, max_batch=4, max_seq_len=256, page_size=16,
                 decode_strategy="greedy_search", temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=None, seed=0, mesh=None,
                 decode_burst=1, kv_cache_quant=None, async_depth=0,
                 spec_decode=None, spec_draft_layers=None,
                 draft_model=None, scheduler=None, prefix_cache=None,
                 prefill_chunk=None, kv_host_cache_mb=None,
                 kv_disk_cache_dir=None):
        if max_seq_len % page_size:
            raise ValueError("max_seq_len must be a multiple of page_size")
        max_pos = getattr(model.config, "max_position_embeddings", None)
        if max_pos is not None and max_seq_len > max_pos:
            # learned-position models would silently clamp the gather at
            # max_pos and decode garbage; rope models shouldn't serve
            # past their trained window either — fail at construction,
            # where the mismatch is statically knowable
            raise ValueError(
                f"max_seq_len={max_seq_len} exceeds the model's "
                f"max_position_embeddings={max_pos}")
        self.model = model
        # TP-sharded serving (reference: fused_multi_transformer_op with
        # mp_degree>1, SURVEY.md §2.1): params lay out per their GSPMD
        # specs, KV pages shard over tp on the kv-head dim, and the decode
        # step's paged attention runs in a shard_map manual over tp
        # (models.llama.forward_paged) — each chip owns its heads' pages.
        from ..distributed import mesh as _mesh_mod

        self.mesh = mesh if mesh is not None else _mesh_mod.get_mesh(
            optional=True)
        self.cfg = model.config
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.page_size = page_size
        self.pages_per_seq = max_seq_len // page_size
        self.decode_strategy = decode_strategy
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_token_id = eos_token_id
        n_pages = max_batch * self.pages_per_seq
        self._free_pages = list(range(n_pages))
        # per-page reference counts: one ref per slot block-table entry
        # plus one per prefix-trie node. The pool invariant
        # sum(_page_refs) + len(_free_pages) == n_pages holds between
        # steps whether or not the prefix cache is on (cache off: every
        # allocated page's ref is exactly 1 and the alloc/free order
        # matches the old exclusive-ownership pop/extend bit for bit).
        self._page_refs = [0] * n_pages
        L = self.cfg.num_hidden_layers
        # what a layer caches is the model's to say, layer by layer: (k, v)
        # of every kv head for full attention, ONE pool of latent rows for
        # latent attention, K and V of a head count and of widths of the
        # layer's own where layers differ (`CausalLMBase.kv_cache_layouts`).
        # `k_pages` holds the first pool of each layer and `v_pages` the
        # second; layouts of one pool leave `v_pages` empty (their rows are
        # the one key head of absorbed-form attention, and carry the value
        # in themselves)
        self._kv_layouts = tuple(tuple(tuple(pool) for pool in layout)
                                 for layout in model.kv_cache_layouts())
        if len({len(layout) for layout in self._kv_layouts}) != 1:
            raise ValueError(
                "every layer's cache must be made of as many pools: got "
                f"{[len(layout) for layout in self._kv_layouts]}")
        self._one_pool = len(self._kv_layouts[0]) == 1
        layers_differ = len(set(self._kv_layouts)) > 1
        # ... and how many positions a layer keeps: every one (pages from
        # the allocator through the block tables), or a window of them (a
        # ring of `_rings[layer]` pages a slot in pools of the layer's own,
        # which the allocator never sees: `CausalLMBase.kv_cache_windows`)
        self._rings = tuple(
            None if w is None else _pa.ring_pages(w, page_size)
            for w in model.kv_cache_windows())
        self._has_rings = any(self._rings)
        # a prefill hands such a layout's K and V back a layer an entry,
        # and each is written by a program of its layer's own kind
        self._layer_writes = self._has_rings or layers_differ
        # anything but the same (k, v) pages of every position in every
        # layer
        self._mixed_layout = self._one_pool or self._layer_writes
        # (the kv heads tp shards and int8 scales follow: every layer's
        # alike wherever either is allowed)
        kvh = self._kv_layouts[0][0][0]
        self._check_mixed_layout_support(
            kv_cache_quant=kv_cache_quant, spec_decode=spec_decode,
            draft_model=draft_model, prefix_cache=prefix_cache,
            prefill_chunk=prefill_chunk)
        # refused before anything is allocated or placed: a construction
        # that raises leaves the caller's model where it was
        tp = int(self.mesh.shape["tp"]) if self.mesh is not None \
            and "tp" in self.mesh.axis_names else 1
        if tp > 1 and self._mixed_layout:
            raise ValueError(
                "a mixed layout (a latent page pool of one head, window "
                "layers' rings, or layers whose pools differ) cannot be "
                f"sharded over tp={tp}: it serves at tp=1 (ROADMAP R9 and "
                "R10 keep the sharded forms)")
        if tp > 1 and kvh % tp:
            raise ValueError(
                f"TP serving shards the {kvh} kv heads over tp={tp}; "
                f"the kv-head count must be divisible by tp")
        # KV pages in the MODEL's dtype (round-2 verdict weak #5: hard-coded
        # f32 pages made a bf16 model pay 2x KV memory + bandwidth); the
        # paged kernel upcasts per-block to f32 for the softmax/accum
        try:
            kv_dtype = next(iter(model.parameters()))._data.dtype
        except StopIteration:
            kv_dtype = jnp.float32
        # kv_cache_quant="int8": pages hold int8 + per-(head, page, slot)
        # f32 scales written at token time — ~2x KV capacity/bandwidth vs
        # bf16 (reference: fused_multi_transformer int8 cachekv variants)
        if kv_cache_quant not in (None, "int8"):
            raise ValueError("kv_cache_quant must be None or 'int8'")
        self.kv_cache_quant = kv_cache_quant
        if kv_cache_quant == "int8":
            kv_dtype = jnp.int8
            self.k_scales, self.v_scales = map(list, zip(*[
                _pa.alloc_page_scales(n_pages, page_size, kvh)
                for _ in range(L)]))
        else:
            self.k_scales = self.v_scales = None
        self.kv_dtype = kv_dtype
        self._n_pages_total = n_pages
        self.k_pages, self.v_pages = self._alloc_pools()
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..models.trainer import place_model

            place_model(model, self.mesh)
            self._page_sharding = NamedSharding(
                self.mesh, P("tp") if tp > 1 else P())
            self._pin_pages()
        else:
            self._page_sharding = None
        self.block_tables = np.zeros((max_batch, self.pages_per_seq),
                                     np.int32)
        self.slots = [_Slot() for _ in range(max_batch)]
        # the four scheduling decisions (admission order, preemption
        # victim, prefill packing, burst sizing) are delegated to a
        # pluggable policy; scheduler= accepts an instance, a registry
        # name, or None (FLAGS_scheduler_policy)
        self.scheduler = _sched.resolve_policy(scheduler)
        self._pending: List = []  # queued (rid, ids, max_new, prior_tokens)
        self._prompts: Dict[int, np.ndarray] = {}
        self._req_params: Dict[int, dict] = {}  # per-request sampling
        self._next_rid = 0
        self._admit_seq = 0
        # bumped by every _release_slot (finish/abort/preempt): the async
        # pipeline snapshots it around replay to detect ANY page release —
        # freed pages must not be reallocated while stale-carry bursts are
        # still in flight writing to them
        self._release_gen = 0
        self._key = jax.random.PRNGKey(seed)
        self._decode_fns: Dict[bool, object] = {}
        self._burst_fns: Dict[tuple, object] = {}
        self._prefill_fns: Dict[tuple, object] = {}
        self._page_write_fns: Dict[tuple, object] = {}
        # multi-step scheduling (vLLM-style): run `decode_burst` decode
        # steps inside ONE compiled lax.scan — on-device sampling feeds
        # the next step, per-slot budget/eos masks deactivate finished
        # rows — and sync with the host once per burst, so a burst of K
        # pays the per-step host round-trip once instead of K times
        # (what that round-trip costs on the chip: ROADMAP S2, not
        # measured). Token callbacks still fire per token (in
        # order, after the burst), so streaming semantics are unchanged;
        # abort() from a callback takes effect at burst granularity.
        self.decode_burst = max(1, int(decode_burst))
        # async scheduling (vLLM-style lookahead): during pure decode the
        # scalar state (last token, lens, active, budget, rng key) stays
        # ON DEVICE — burst N+1 is dispatched off burst N's output
        # futures BEFORE burst N's tokens are harvested, keeping up to
        # `async_depth` bursts in flight so the host round-trip and token
        # replay overlap device compute. Greedy token streams are
        # bitwise-identical to the sync path; sampling streams differ
        # only in rng consumption order (the key chains on device instead
        # of being re-split per burst on the host).
        self.async_depth = max(0, int(async_depth))
        # self-speculative decoding (README.md "Quantized decode +
        # speculative decoding"): greedy rounds draft window-1 tokens
        # with a cheap path — the first spec_draft_layers decoder layers
        # (LayerSkip-style shallow exit over the target's own paged KV)
        # or an optional separate draft_model with its own page pools —
        # then verify the whole window in ONE batched target forward
        # over the paged cache; the greedy-exact accepted prefix plus
        # one corrected token commits, and rejection rewinds by context
        # truncation (the pages past the accepted prefix simply stay
        # masked). Output token streams are bit-identical to
        # non-speculative greedy decoding.
        from ..framework import config as _config

        sd = spec_decode if spec_decode is not None \
            else _config.get_flag("FLAGS_spec_decode", 0)
        self.spec_decode = int(sd) if int(sd) >= 2 else 0
        if self.spec_decode and self.async_depth:
            raise ValueError(
                "spec_decode and async_depth are mutually exclusive: "
                "the speculative round already keeps the device busy "
                "across the window, and the async pipeline's stale-"
                "carry pages cannot express the verify rewind")
        self._draft_model = draft_model if self.spec_decode else None
        L = self.cfg.num_hidden_layers
        if self._draft_model is not None:
            self.spec_draft_layers = None
        else:
            dl = spec_draft_layers if spec_draft_layers is not None \
                else _config.get_flag("FLAGS_spec_draft_layers", 0)
            dl = int(dl) if int(dl) > 0 else -(-L // 2)
            self.spec_draft_layers = max(1, min(dl, L))
        self._spec_draft_fns: Dict[int, object] = {}
        self._spec_verify_fns: Dict[int, object] = {}
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._draft_params = None
        self._draft_buffers = None
        self._draft_k_scales = self._draft_v_scales = None
        if self._draft_model is not None:
            # the draft model decodes the SAME positions, so it shares
            # the block tables / context lens and only needs its own
            # page payloads (its layer count / kv geometry differ)
            dcfg = self._draft_model.config
            dkvh = getattr(dcfg, "num_key_value_heads",
                           dcfg.num_attention_heads)
            dhd = dcfg.hidden_size // dcfg.num_attention_heads
            dL = dcfg.num_hidden_layers
            try:
                d_dtype = next(
                    iter(self._draft_model.parameters()))._data.dtype
            except StopIteration:
                d_dtype = jnp.float32
            if kv_cache_quant == "int8":
                d_dtype = jnp.int8
                self._draft_k_scales, self._draft_v_scales = map(
                    list, zip(*[_pa.alloc_page_scales(
                        n_pages, page_size, dkvh) for _ in range(dL)]))
            self._draft_k_pages = [
                jnp.zeros((dkvh, n_pages, page_size, dhd), d_dtype)
                for _ in range(dL)]
            self._draft_v_pages = [
                jnp.zeros((dkvh, n_pages, page_size, dhd), d_dtype)
                for _ in range(dL)]
            if self.mesh is not None:
                from ..models.trainer import place_model

                place_model(self._draft_model, self.mesh)
        else:
            self._draft_k_pages = self._draft_v_pages = None
        # prefix-cache KV reuse + chunked prefill (README.md "Prefix
        # cache + chunked prefill"): prefix_cache=1 shares page-aligned
        # prompt-prefix pages across requests via a refcounted trie;
        # prefill_chunk=N runs every prefill suffix in N-token window
        # chunks interleaved with decode. Greedy token streams stay
        # bit-identical to cache-off dense prefill either way.
        pc = prefix_cache if prefix_cache is not None \
            else _config.get_flag("FLAGS_prefix_cache", 0)
        self.prefix_cache_enabled = bool(int(pc))
        ck = prefill_chunk if prefill_chunk is not None \
            else _config.get_flag("FLAGS_prefill_chunk", 0)
        ck = int(ck)
        # page-align the chunk budget: continuation scatters land full
        # window positions into pages, so a ragged budget buys nothing
        self.prefill_chunk = -(-ck // page_size) * page_size \
            if ck > 0 else 0
        if (self.prefix_cache_enabled or self.prefill_chunk) and \
                self._draft_model is not None:
            raise ValueError(
                "prefix_cache / prefill_chunk cannot serve with a "
                "separate draft_model: the chunked continuation fills "
                "only the target's pages, so the draft pools would "
                "decode against an unwritten prompt (shallow-exit "
                "spec_decode shares the target pages and composes fine)")
        self._prefix_cache = _pc.PrefixCache(
            page_size, self._page_refs, self._free_pages) \
            if self.prefix_cache_enabled else None
        self._chunk_fns: Dict[tuple, object] = {}
        # host-side token tallies for /statusz + bench (the metric
        # counters are registry-global; these are THIS engine's)
        self._prefix_hits_total = 0
        self._prefix_misses_total = 0
        # params pytree cached across steps (round-2 verdict weak #5:
        # rebuilding it every decode step); call refresh_params() after
        # mutating model weights
        self._params = None
        self._buffers = None
        # telemetry: handles resolved once (README.md "Observability");
        # set when a compiled decode call raises AFTER donating the page
        # pools — the engine then holds deleted buffers and every
        # subsequent step()/run() fails fast instead of crashing on
        # deleted-buffer access (ADVICE.md round-5)
        self._poisoned = None
        self._m = _EngineMetrics()
        # tiered spill (README.md "Tiered KV cache + cross-host
        # handoff"): evicted prefix pages keep their bytes in host RAM
        # (FLAGS_kv_host_cache_mb) then disk (FLAGS_kv_disk_cache_dir)
        # and promote back on a trie hit. Off by default: _kv_tiers
        # stays None and eviction drops pages exactly as before —
        # nothing below allocates on the hot path.
        hm = kv_host_cache_mb if kv_host_cache_mb is not None \
            else _config.get_flag("FLAGS_kv_host_cache_mb", 0)
        dd_dir = kv_disk_cache_dir if kv_disk_cache_dir is not None \
            else _config.get_flag("FLAGS_kv_disk_cache_dir", "")
        self._kv_tiers = None
        self._tier_seen = None
        self._tier_cells = None
        if self._prefix_cache is not None and (int(hm) > 0 or dd_dir):
            disk_mb = int(_config.get_flag("FLAGS_kv_disk_cache_mb",
                                           256))
            self._kv_tiers = _pc.TieredStore(
                host_bytes=int(hm) << 20, disk_dir=str(dd_dir),
                disk_bytes=disk_mb << 20)
            self._prefix_cache.attach_tiers(self._kv_tiers,
                                            self._gather_page_blob)
            # label children resolve ONCE here, so the spill/promote
            # paths only touch plain cells (same discipline as every
            # other serving metric)
            m = self._m
            self._tier_cells = {
                "hits_host": m.tier_hits.labels("host"),
                "hits_disk": m.tier_hits.labels("disk"),
                "spills_host": m.tier_spills.labels("host"),
                "spills_disk": m.tier_spills.labels("disk"),
                "pages_host": m.tier_pages.labels("host"),
                "pages_disk": m.tier_pages.labels("disk"),
                "promote_host": m.tier_promote_lat.labels("host"),
                "promote_disk": m.tier_promote_lat.labels("disk"),
            }
            self._tier_seen = self._tier_snapshot()
        # stepledger quant correction (observability/stepledger.py):
        # XLA's cost_analysis bills the dequantized float weight
        # intermediate as bytes accessed, but the HBM traffic of a
        # load-fused / dequant-in-kernel matmul is the int8/int4 bytes —
        # compute the (float - int) weight delta ONCE so every decode
        # entry's roofline classifies against honest bytes
        self._quant_algo, self._quant_bytes_delta = \
            self._quant_weight_delta()
        # OOM graceful degradation (memwatch channel): a decode-time
        # RESOURCE_EXHAUSTED gets ONE preemption round (shed the
        # youngest slot, retry) before the engine poisons — see
        # _handle_decode_oom
        self._oom_retried = False
        # self-healing (README.md "Fault tolerance"): instead of
        # permanently poisoning on a donated-pool failure or an OOM
        # storm, the engine drains in-flight requests back to the queue,
        # rebuilds its page pools, and re-admits — bounded by
        # FLAGS_serving_max_recoveries over its lifetime and by
        # FLAGS_serving_request_retries per request (_begin_recovery).
        # /readyz is 503 while _recovering; /healthz reports "degraded"
        # once _recoveries > 0.
        self._recovering = False
        self._recoveries = 0
        self._retry_counts: Dict[int, int] = {}  # rid -> requeue count
        # the counts of the prefill programs since the last commit of first
        # tokens (`_take_prefill_counts`)
        self._prefill_counts: Dict[str, int] = {}
        # per-(engine, tenant) accounting cells, resolved lazily at the
        # first finish for each tenant (FLAGS_requestlog; tenants are
        # dynamic, so the _tier_cells resolve-once discipline applies
        # per tenant, cached here)
        self._tenant_cells: Dict[str, tuple] = {}
        # warmup()'s throwaway requests run the full finish path but
        # are synthetic self-traffic: never billed to a tenant
        self._warming = False
        # live telemetry plane (README.md "Live telemetry plane"):
        # /readyz is 503 until warmup() completes and while the KV pool
        # is exhausted; tracking is a weakref append — the engine never
        # holds a server handle
        self._warmup_done = False
        _httpd.track_engine(self)
        if _memwatch.enabled():
            self._record_static_breakdown()
        # span tracing (README.md "Observability"): one Trace per request
        # while tracing is enabled, keyed by rid. Empty when
        # FLAGS_trace_sample=0, so every hot-path guard below is one
        # falsy dict check — the alloc-guard test pins zero span
        # allocations per decode step with tracing off.
        self._traces: Dict[int, object] = {}

    def _alloc_pools(self):
        """(k_pages, v_pages): empty pools for every layer, each shaped by
        that LAYER's entry of the model's cache layouts (layers may differ
        in kv heads, and a layer's key pool in width from its value pool);
        `v_pages` is [] for layouts of one pool. A layer that keeps every
        position has the allocator's pages, a window layer its slots'
        rings."""
        pools = [[jnp.zeros((heads, self._n_pages_total if ring is None
                             else self.max_batch * ring, self.page_size,
                             width), self.kv_dtype)
                  for heads, width in layout]
                 for layout, ring in zip(self._kv_layouts, self._rings)]
        return [p[0] for p in pools], \
            [] if self._one_pool else [p[1] for p in pools]

    def kv_pool_bytes(self):
        """{"kv_pool_bytes_full", "kv_pool_bytes_window"}: what the pools
        of the layers that keep every position hold, and what the window
        layers' rings hold."""
        out = {"kv_pool_bytes_full": 0, "kv_pool_bytes_window": 0}
        for li, ring in enumerate(self._rings):
            out["kv_pool_bytes_full" if ring is None
                else "kv_pool_bytes_window"] += sum(
                int(pools[li].nbytes)
                for pools in (self.k_pages, self.v_pages) if pools)
        return out

    def _check_mixed_layout_support(self, **asked):
        """A mixed layout (one pool of latent rows a layer, window layers'
        rings beside full layers' pages, or layers whose pools differ in
        heads or widths) has no int8 pages, no
        window step (speculative decoding, chunked prefill and with it
        the prefix cache and its tiers) and no draft pools yet: asking
        for one raises here, at construction, and nothing falls back."""
        if not self._mixed_layout:
            return
        from ..framework import config as _config

        flags = {"spec_decode": "FLAGS_spec_decode",
                 "prefix_cache": "FLAGS_prefix_cache",
                 "prefill_chunk": "FLAGS_prefill_chunk"}
        for name, value in asked.items():
            if value is None and name in flags:
                value = _config.get_flag(flags[name], 0)
            if value is not None and value != 0 and value != "":
                raise ValueError(
                    f"{name}={value!r} is not built for a mixed layout (a "
                    "model that caches a latent in one page pool a layer, "
                    "or keeps a window of some layers in rings; decoded "
                    "one token a row): serve it without, or see ROADMAP R9 "
                    "and R10")

    def _pin_pages(self):
        """Lay the page pools out in the serving sharding (kv heads over
        tp); a no-op without a mesh."""
        if self._page_sharding is not None:
            self.k_pages = [jax.device_put(p, self._page_sharding)
                            for p in self.k_pages]
            self.v_pages = [jax.device_put(p, self._page_sharding)
                            for p in self.v_pages]
            if self.k_scales is not None:
                self.k_scales = [jax.device_put(p, self._page_sharding)
                                 for p in self.k_scales]
                self.v_scales = [jax.device_put(p, self._page_sharding)
                                 for p in self.v_scales]

    def _cached_params(self):
        if self._params is None:
            self._params = self.model.parameters_pytree()
            self._buffers = self.model.buffers_pytree()
        return self._params, self._buffers

    def refresh_params(self):
        """Drop the cached weights pytree (call after updating the model,
        e.g. live weight reload between requests)."""
        self._params = None
        self._buffers = None
        self._draft_params = None
        self._draft_buffers = None

    def _cached_draft_params(self):
        if self._draft_params is None:
            self._draft_params = self._draft_model.parameters_pytree()
            self._draft_buffers = self._draft_model.buffers_pytree()
        return self._draft_params, self._draft_buffers

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens=32,
                    decode_strategy=None, temperature=None, top_k=None,
                    top_p=None, eos_token_id=None, on_token=None,
                    tenant=None) -> int:
        """Queue a request. Sampling params default to the engine-level
        settings; per-request overrides ride the request through
        preemption/re-admission (one compiled decode step serves mixed
        greedy/sampling batches — params are runtime [b] arrays).

        eos_token_id: per-request stop token (falls back to the engine's).
        on_token: optional callable(rid, token_id) streamed each time a
        token is COMMITTED for this request (host-side, after the decode
        step). On preemption the already-streamed tokens are preserved
        with the request and NOT re-streamed — streaming resumes from the
        next new token after re-admission. Calling engine.abort() from
        inside the callback is supported.
        tenant: accounting identity for the per-request ledger and
        usage_tokens_total (falls back to the X-PT-Tenant header the
        httpd parked on this thread, then \"default\")."""
        ids = np.asarray(as_array(prompt_ids)).reshape(-1).astype(np.int64)
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(ids) + int(max_new_tokens) > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(ids)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.max_seq_len})")
        rid = self._next_rid
        self._next_rid += 1
        self._prompts[rid] = ids
        strategy = decode_strategy if decode_strategy is not None \
            else self.decode_strategy
        self._req_params[rid] = dict(
            greedy=strategy == "greedy_search",
            temperature=float(temperature if temperature is not None
                              else self.temperature),
            top_k=int(top_k if top_k is not None else self.top_k),
            top_p=float(top_p if top_p is not None else self.top_p),
            eos=eos_token_id if eos_token_id is not None
            else self.eos_token_id,
            on_token=on_token,
            # accounting identity + retained timing: t_enq is popped at
            # the one-shot TTFT observe, so the ledger keeps its own
            # never-popped t_start (and the recovery counter watermark)
            tenant=_reqlog.normalize_tenant(
                tenant if tenant is not None
                else _reqlog.pending_tenant()),
            t_start=_time_mod.perf_counter(),
            recov0=self._recoveries,
            t_enq=_time_mod.perf_counter())
        # queue only — admission happens at the next step() so requests
        # arriving together prefill together in one batched compiled call
        self._pending.append((rid, ids, int(max_new_tokens), []))
        self._m.queue_depth.set(len(self._pending))
        trace_id = None
        if _trace.enabled():
            tr = _trace.start_trace("serving.request", own_track=True,
                                    rid=rid, prompt_len=len(ids),
                                    max_new=int(max_new_tokens))
            if tr.trace_id is not None:
                self._traces[rid] = tr
                trace_id = tr.trace_id
                tr.begin("serving.queue", rid=rid)
        _flight.record_event("serving.add_request", rid=rid,
                             prompt_len=len(ids),
                             max_new=int(max_new_tokens),
                             trace_id=trace_id)
        return rid

    def _admit(self):
        with _trace.phase("serving.admit"):
            new = self._admit_slots()
        # the policy says how many of them one round takes (all, unless
        # its batch bucket is smaller). A round that ended in a recovery has
        # sent every request back to the queue, and the rest with it
        while new and all(self.slots[si].active for si, _ in new):
            nb, _bucket = self.scheduler.prefill_bucket(self, new)
            nb = max(1, min(int(nb), self.max_batch))
            self._prefill_batch(new[:nb])
            new = new[nb:]

    def _admit_slots(self):
        # collect ALL admissible requests first, then prefill them in ONE
        # compiled batched call — admission no longer serializes at batch 1
        # (VERDICT round-1: per-request prefill dominates serving cost).
        # Pages are allocated ON DEMAND (round-2 verdict weak #5: reserving
        # the full pages_per_seq up front voided paging's memory
        # elasticity): admission takes only the prompt's pages; decode
        # grows the allocation page by page (_ensure_pages), and exhaustion
        # preempts the youngest slot (vLLM's recompute policy).
        new: List[tuple] = []  # (slot_idx, context_ids)
        while self._pending:
            slot_idx = next(
                (i for i, s in enumerate(self.slots) if not s.active), None)
            if slot_idx is None:
                break
            # admission ORDER is the scheduler policy's call (default:
            # strict head-of-line FIFO); the page-fit commit check stays
            # here so a policy bug cannot underflow the pool
            pick = self.scheduler.select_admission(self)
            if pick is None:
                break
            rid, ids, max_new, prior = self._pending[pick]
            ctx = np.concatenate([ids, np.asarray(prior, np.int64)]) \
                if prior else ids
            need = -(-len(ctx) // self.page_size)  # ceil: prompt pages only
            # prefix-cache match: take TENTATIVE slot refs on the
            # matched pages first, so the LRU reclaim below can never
            # evict the very pages this admission is about to reuse
            cached_pages: List[int] = []
            cached_tokens = 0
            n_promoted = 0
            if self._prefix_cache is not None:
                cached_pages, cached_tokens = \
                    self._prefix_cache.match(ctx)
                for p in cached_pages:
                    self._page_refs[p] += 1
                if self._kv_tiers is not None:
                    cached_pages, cached_tokens, n_promoted = \
                        self._promote_spilled(ctx, cached_pages,
                                              cached_tokens)
            need_fresh = need - len(cached_pages)
            if len(self._free_pages) < need_fresh:
                self._reclaim_pages(need_fresh - len(self._free_pages))
            if len(self._free_pages) < need_fresh:
                for p in cached_pages:
                    # roll back the tentative refs; the trie's own refs
                    # keep the matched pages resident
                    self._page_refs[p] -= 1
                break
            self._pending.pop(pick)
            rp = self._req_params.get(rid)
            # one-shot: a preempted request re-enters _pending with its
            # original t_enq — re-observing would book its prior decode
            # time as "queue wait"
            requeue = int(bool(rp is not None and rp.get("qw_seen")))
            if rp is not None and "t_enq" in rp \
                    and not rp.get("qw_seen"):
                rp["qw_seen"] = True
                qw = _time_mod.perf_counter() - rp["t_enq"]
                # retained for the request ledger (the histogram
                # observation alone forgets which request it was)
                rp["queue_s"] = qw
                self._m.queue_wait.observe(qw)
            # the moment this request got its slot, on the phases'
            # timeline; a re-admission after preemption says so and
            # repeats the FIRST admission's wait
            _trace.mark("serving.admitted", rid=rid,
                        queued_us=int(1e6 * (rp or {}).get("queue_s", 0.0)),
                        requeue=requeue)
            if rp is not None and n_promoted:
                rp["tier_promoted"] = \
                    rp.get("tier_promoted", 0) + int(n_promoted)
            pages = cached_pages + [self._alloc_page()
                                    for _ in range(need_fresh)]
            self.block_tables[slot_idx, :need] = np.asarray(pages, np.int32)
            s = self.slots[slot_idx]
            s.request_id, s.tokens = rid, list(prior)
            s.prompt_len = len(ids)
            s.max_new_tokens = max_new
            s.n_pages = need
            s.greedy = self._req_params[rid]["greedy"]
            s.admit_seq = self._admit_seq
            self._admit_seq += 1
            s.spec_proposed = 0
            s.spec_accepted = 0
            s._pf_chunks_done = 0
            if self._prefix_cache is not None:
                # token-level cache economics, observed at admission
                suffix = len(ctx) - cached_tokens
                self._prefix_hits_total += cached_tokens
                self._prefix_misses_total += suffix
                self._m.cache_hits.inc(cached_tokens)
                self._m.cache_misses.inc(suffix)
                self._m.cached_ratio.observe(cached_tokens / len(ctx))
                if rp is not None:
                    # a preempted request keeps its FIRST admission's
                    # ratio (re-admission hits its own just-cached
                    # pages, which would overstate reuse)
                    rp.setdefault("prefix_hit_ratio",
                                  round(cached_tokens / len(ctx), 4))
            if cached_tokens:
                _flight.record_event("serving.prefix_cache_hit",
                                     rid=rid, cached=cached_tokens,
                                     ctx=len(ctx))
            if self.prefill_chunk or cached_tokens:
                # chunked-prefill / cache-continuation route: only the
                # uncached suffix runs, in window-mode chunks
                # (_prefill_chunk_round), interleaved with decode; the
                # slot stays out of decode until the suffix completes
                s.context_len = cached_tokens
                s.prefilling = True
                s._pf_ctx = ctx
                s.needs_first_sample = False
                cw = self.prefill_chunk or \
                    -(-(len(ctx) - cached_tokens) // self.page_size) \
                    * self.page_size
                s._pf_n_chunks = -(-(len(ctx) - cached_tokens) // cw)
            else:
                s.context_len = len(ctx)
                s.prefilling = False
                s.needs_first_sample = True
                new.append((slot_idx, ctx))
            s.active = True
            if self._traces:
                tr = self._traces.get(rid)
                if tr is not None:
                    # close the queue phase; the prefill span follows in
                    # _prefill_batch / _prefill_chunk_round on the same
                    # request track
                    tr.end("serving.queue", slot=slot_idx)
                    if cached_tokens:
                        tr.instant("serving.prefix_cache_hit",
                                   cached=cached_tokens,
                                   prompt=len(ctx))
                    s.trace_id = tr.trace_id
        self._m.queue_depth.set(len(self._pending))
        return new

    def warmup(self, prompt_len=None, sampling=None):
        """Pre-compile the serving programs BEFORE traffic: runs one
        throwaway greedy request end to end (prefill bucket + the
        all-greedy decode specialization), plus a sampling request for
        the per-row-sampler variants when sampling=True — or by default
        whenever the ENGINE's decode_strategy is a sampling one. Must be
        called on an idle engine (queued work would be drained and its
        outputs discarded). Returns wall seconds."""
        import time as _time

        if self.has_work():
            raise RuntimeError(
                "warmup() must run on an idle engine: queued/active "
                "requests would be decoded and their outputs discarded")
        if sampling is None:
            sampling = self.decode_strategy != "greedy_search"
        t0 = _time.perf_counter()
        # a burst engine's first decode call sizes its scan at the full
        # decode_burst: ask for decode_burst + 1 new tokens (first one
        # comes from the prefill-time sample) so warmup compiles the SAME
        # burst program traffic will use. step() still falls back to the
        # single-step program when every active row is on its last token,
        # so a second 2-token request warms that program too. A spec
        # engine's greedy request must carry window+1 of budget so the
        # draft scan + the batched verify forward compile here, not
        # under traffic.
        max_new = max(self.decode_burst, self.spec_decode) + 1
        plen = int(prompt_len) if prompt_len is not None else max(
            1, min(self.page_size, self.max_seq_len - max_new))
        if prompt_len is not None and \
                (self.decode_burst > 1 or self.spec_decode) and \
                plen + max_new > self.max_seq_len:
            raise ValueError(
                f"warmup(prompt_len={plen}) leaves no room for a "
                f"decode_burst={self.decode_burst} / "
                f"spec_decode={self.spec_decode} budget within "
                f"max_seq_len={self.max_seq_len}: the burst program would "
                f"NOT be compiled and the first real request would pay "
                f"the compile in-traffic. Use a shorter prompt_len (<= "
                f"{self.max_seq_len - max_new}) or a smaller decode_burst.")
        max_new = max(2, min(max_new, self.max_seq_len - plen))
        budgets = [max_new] + ([2] if self.decode_burst > 1 and
                               max_new > 2 else [])
        strategies = ["greedy_search"] + (["sampling"] if sampling else [])
        self._warming = True
        try:
            for strategy in strategies:
                for mx in budgets:
                    # eos -1 can never match a token id: the throwaway
                    # request is guaranteed to reach the decode step (an
                    # engine-level eos matching the first sampled token
                    # would otherwise finish at prefill and skip the
                    # decode compile entirely)
                    self.add_request(np.zeros((plen,), np.int64),
                                     max_new_tokens=mx,
                                     decode_strategy=strategy,
                                     eos_token_id=-1)
                    self.run()
        finally:
            self._warming = False
        # compile observability: from here on, any serving program
        # compile is an IN-TRAFFIC recompile (compilewatch counts them;
        # tools/ci.sh gates the smoke on zero decode recompiles)
        _cw.mark_warmup_done("serving.")
        # readiness gate: /readyz flips to 200 only now — a router that
        # admitted traffic earlier would eat the compile cliff warmup
        # exists to prepay
        self._warmup_done = True
        return _time.perf_counter() - t0

    def _req_eos(self, rid):
        rp = self._req_params.get(rid)
        return rp["eos"] if rp is not None else self.eos_token_id

    def _stream(self, rid, token):
        # ONE commit point for every token that enters a request's
        # stream — the token counter lives here so sync/burst/async
        # paths can't drift apart
        self._m.tokens.inc()
        rp = self._req_params.get(rid)
        cb = rp.get("on_token") if rp is not None else None
        if cb is not None:
            cb(rid, int(token))

    # ------------------------------------------------------------------
    # page accounting: alloc takes a ref, release decrefs — a page
    # reaches the free list only at refcount zero, so a prefix page
    # shared with the trie (or gathered into another slot's row) is
    # never double-freed by finish/preempt/abort/OOM-preemption
    # ------------------------------------------------------------------
    def _alloc_page(self) -> int:
        page = self._free_pages.pop()
        self._page_refs[page] += 1
        return page

    def _decref_page(self, page):
        page = int(page)
        self._page_refs[page] -= 1
        if self._page_refs[page] == 0:
            self._free_pages.append(page)

    def _avail_pages(self) -> int:
        """Pages admission may count on: free now plus evictable from
        the prefix cache (zero-ref LRU residents the reclaim below can
        free on demand). == len(_free_pages) when the cache is off."""
        n = len(self._free_pages)
        if self._prefix_cache is not None:
            n += self._prefix_cache.evictable()
        return n

    def _reclaim_pages(self, need: int) -> int:
        """Evict up to `need` zero-ref cached pages back to the free
        list (LRU); returns pages actually freed. With spill tiers on,
        each evicted page's bytes land in host RAM / disk
        (PrefixCache._drop -> TieredStore) instead of being lost."""
        if self._prefix_cache is None or need <= 0:
            return 0
        freed = self._prefix_cache.evict(need)
        if freed:
            self._m.cache_evictions.inc(freed)
            _flight.record_event("serving.prefix_cache_evict",
                                 pages=freed)
            if self._kv_tiers is not None:
                self._sync_tier_metrics()
        return freed

    # -- tiered spill / promote (README.md "Tiered KV cache") ----------
    def _gather_page_blob(self, page: int) -> bytes:
        """Host-copy ONE page's per-layer K/V bytes (+ int8 scales)
        into the shared length-prefixed serialization — the trie's
        spill gather. Runs between compiled calls, so the device
        buffers are valid; np.asarray blocks on any in-flight dispatch
        that still owns them."""
        idx = np.asarray([int(page)])
        k = [np.asarray(kp[:, idx]) for kp in self.k_pages]
        v = [np.asarray(vp[:, idx]) for vp in self.v_pages]
        if self.k_scales is not None:
            ks = [np.asarray(sc[:, idx]) for sc in self.k_scales]
            vs = [np.asarray(sc[:, idx]) for sc in self.v_scales]
        else:
            ks = vs = None
        return _fab.pack_pages(k, v, ks, vs)

    def _tier_snapshot(self) -> dict:
        st = self._kv_tiers
        return {"hits_host": st.hits["host"],
                "hits_disk": st.hits["disk"],
                "spills_host": st.spills["host"],
                "spills_disk": st.spills["disk"],
                "misses": st.misses, "demotions": st.demotions,
                "drops": st.drops, "corrupt": st.corrupt}

    def _sync_tier_metrics(self):
        """Mirror the TieredStore's plain-int counters into the
        registry families (delta since the last sync) and refresh the
        per-tier page gauges. Called only on spill/promote paths —
        never on the decode hot path."""
        cur = self._tier_snapshot()
        prev, self._tier_seen = self._tier_seen, cur
        cells = self._tier_cells
        m = self._m
        for key in ("hits_host", "hits_disk", "spills_host",
                    "spills_disk"):
            d = cur[key] - prev[key]
            if d:
                cells[key].inc(d)
        for key, cell in (("misses", m.tier_misses),
                          ("demotions", m.tier_demotions),
                          ("drops", m.tier_drops),
                          ("corrupt", m.tier_corrupt)):
            d = cur[key] - prev[key]
            if d:
                cell.inc(d)
        cells["pages_host"].set(self._kv_tiers.host_entries())
        cells["pages_disk"].set(self._kv_tiers.disk_entries())

    def _promote_spilled(self, ctx, pages, tokens):
        """Continue a resident prefix match into the spill tiers:
        fetch the contiguous run of spilled chunks that extend the
        match (bounded by the scheduler's promotion_budget hook),
        scatter their payloads into freshly allocated pages (the
        dispatch is async — decode work can overlap it), and re-adopt
        the chunks into the trie. Returns the extended
        (pages, tokens, n_promoted). Admission then prefills only the
        suffix NO tier holds. Corrupt payloads read as clean misses."""
        keys = self._prefix_cache.spilled_suffix(ctx, len(pages))
        if not keys:
            return pages, tokens, 0
        budget = int(self.scheduler.promotion_budget(self, len(keys)))
        keys = keys[:max(0, budget)]
        got = []  # (tier, (k, v, ks, vs)) per chunk, in path order
        for key in keys:
            tier, blob = self._kv_tiers.get(key)
            if blob is None:
                break
            try:
                got.append((tier, _fab.unpack_pages(blob)))
            except ValueError:
                # undecodable payload: a clean miss — drop the entry
                # and recompute from here on
                self._kv_tiers.pop(key)
                self._kv_tiers.corrupt += 1
                break
        dst: List[int] = []
        for _ in got:
            if not self._free_pages:
                self._reclaim_pages(1)
            if not self._free_pages:
                break  # pool pinned by live slots: partial promote
            dst.append(self._alloc_page())
        got = got[:len(dst)]
        if not dst:
            self._sync_tier_metrics()
            return pages, tokens, 0
        t0 = _time_mod.perf_counter()
        dd = jnp.asarray(np.asarray(dst, np.int32))
        L = len(self.k_pages)
        for li in range(L):
            kcat = np.concatenate([g[1][0][li] for g in got], axis=1)
            vcat = np.concatenate([g[1][1][li] for g in got], axis=1)
            self.k_pages[li] = self.k_pages[li].at[:, dd].set(
                jnp.asarray(kcat, self.k_pages[li].dtype))
            self.v_pages[li] = self.v_pages[li].at[:, dd].set(
                jnp.asarray(vcat, self.v_pages[li].dtype))
            if self.k_scales is not None:
                kscat = np.concatenate([g[1][2][li] for g in got],
                                       axis=1)
                vscat = np.concatenate([g[1][3][li] for g in got],
                                       axis=1)
                self.k_scales[li] = self.k_scales[li].at[:, dd].set(
                    jnp.asarray(kscat))
                self.v_scales[li] = self.v_scales[li].at[:, dd].set(
                    jnp.asarray(vscat))
        if self._page_sharding is not None:
            self._pin_pages()
        dt = _time_mod.perf_counter() - t0
        # re-adopt into the trie: insert() increfs each promoted page
        # (the trie's ref) and pops the spilled copies, so every page
        # lives in exactly one tier; _alloc_page above already took
        # the slot's tentative ref — same accounting as a resident hit
        all_pages = list(pages) + dst
        self._prefix_cache.insert(
            ctx[:len(all_pages) * self.page_size], all_pages)
        tiers = [g[0] for g in got]
        for tier in ("host", "disk"):
            n = tiers.count(tier)
            if n:
                self._tier_cells[f"hits_{tier}"].inc(n)
                self._tier_cells[f"promote_{tier}"].observe(dt)
        # the store's own hit counters were mirrored just above —
        # rebase the snapshot so the next sync doesn't double-count
        self._tier_seen = self._tier_snapshot()
        self._sync_tier_metrics()
        _flight.record_event("serving.kv_promote", pages=len(dst),
                             host=tiers.count("host"),
                             disk=tiers.count("disk"),
                             s=round(dt, 6))
        return all_pages, tokens + len(dst) * self.page_size, len(dst)

    def _release_slot(self, slot_idx):
        """Decref a slot's pages and deactivate it (shared by finish /
        preempt / abort / OOM preemption). Pages whose refcount drops to
        zero return to the pool; pages the prefix trie still caches stay
        resident for the next matching admission."""
        s = self.slots[slot_idx]
        for page in self.block_tables[slot_idx, :s.n_pages].tolist():
            self._decref_page(page)
        s.n_pages = 0
        s.active = False
        s.prefilling = False
        s._pf_ctx = None
        s.trace_id = -1  # don't leak the id into the slot's next tenant
        self._release_gen += 1

    def abort(self, request_id: int) -> bool:
        """Drop a request: dequeue it if still pending, or free its slot
        and pages if running (safe to call from an on_token callback).
        Returns True if it was found. Nothing is emitted for an aborted
        request (vLLM abort semantics)."""
        for i, (rid, *_rest) in enumerate(self._pending):
            if rid == request_id:
                self._pending.pop(i)
                self._prompts.pop(request_id, None)
                self._req_params.pop(request_id, None)
                self._retry_counts.pop(request_id, None)
                self._m.aborts.inc()
                self._m.queue_depth.set(len(self._pending))
                self._finish_trace(request_id, aborted="queue")
                _flight.record_event("serving.abort", rid=request_id,
                                     where="queue")
                return True
        for idx, s in enumerate(self.slots):
            if s.active and s.request_id == request_id:
                self._release_slot(idx)
                self._prompts.pop(request_id, None)
                self._req_params.pop(request_id, None)
                self._retry_counts.pop(request_id, None)
                self._m.aborts.inc()
                self._finish_trace(request_id, aborted="slot")
                _flight.record_event("serving.abort", rid=request_id,
                                     where="slot")
                return True
        return False

    def _finish_trace(self, rid, **attrs):
        """Detach and commit the request's trace (finish/abort); returns
        its trace_id or None."""
        tr = self._traces.pop(rid, None)
        if tr is None:
            return None
        if "aborted" in attrs:
            tr.instant("serving.abort", where=attrs["aborted"])
        # close the aggregate decode interval on EVERY exit path — a
        # slow request aborted by a client timeout spent its life in
        # decode, and that is exactly the span its trace must show
        d0 = tr.marks.get("decode_t0")
        if d0 is not None:
            tr.emit("serving.decode", d0, _time_mod.perf_counter(),
                    tokens=attrs.get("tokens"))
        tr.finish(**attrs)
        return tr.trace_id

    def _ensure_pages(self, slot_idx, steps) -> bool:
        """Grow the slot's allocation to cover `steps` successive decode
        writes starting at context_len (1 for a single step, up to the
        burst length for multi-step decode). Returns False if the pool is
        exhausted (caller preempts)."""
        s = self.slots[slot_idx]
        need = -(-(s.context_len + steps) // self.page_size)
        while s.n_pages < need:
            if not self._free_pages and not self._reclaim_pages(1):
                return False
            self.block_tables[slot_idx, s.n_pages] = self._alloc_page()
            s.n_pages += 1
        return True

    def _preempt(self, slot_idx):
        """Evict a slot (page exhaustion): free its pages and requeue it at
        the FRONT of pending with its context so far; it re-prefills when
        pages free up — the reference/vLLM recompute-preemption policy."""
        s = self.slots[slot_idx]
        self._release_slot(slot_idx)
        self._pending.insert(
            0, (s.request_id, self._prompts[s.request_id],
                s.max_new_tokens, list(s.tokens)))
        self._m.preemptions.inc()
        self._m.queue_depth.set(len(self._pending))
        if self._traces:
            tr = self._traces.get(s.request_id)
            if tr is not None:
                # annotate the eviction and re-open the queue phase; the
                # aggregate decode span restarts after re-admission
                tr.instant("serving.preempt",
                           tokens_so_far=len(s.tokens))
                d0 = tr.marks.pop("decode_t0", None)
                if d0 is not None:
                    tr.emit("serving.decode", d0,
                            _time_mod.perf_counter(), preempted=True)
                tr.begin("serving.queue", requeue=True)
        _flight.record_event("serving.preempt", rid=s.request_id,
                             tokens_so_far=len(s.tokens))

    # ------------------------------------------------------------------
    # prefill: batched dense-cache forward on the admitted prompts, then
    # one compiled write per layer of their K/V into the donated pages
    # ------------------------------------------------------------------
    def _get_prefill_fn(self, nb, bucket, all_greedy, which="target"):
        """One compiled prefill per (batch-bucket, token-bucket,
        all-greedy?): prompts pad to a page multiple, batch pads to a
        power of two. The all-greedy specialization skips the per-row
        sampler's vocab sort entirely (argmax only). which="draft"
        compiles the same program over the separate draft model (its
        pages must hold the prompt too; the sampled first token is
        ignored — the target's prefill sample is the stream's)."""
        fn = self._prefill_fns.get((nb, bucket, all_greedy, which))
        if fn is not None:
            self._m.prefill_hits.inc()
            return fn
        self._m.prefill_misses.inc()
        _flight.record_event("serving.prefill_compile", nb=nb,
                             bucket=bucket, all_greedy=all_greedy,
                             which=which)
        model = self.model if which == "target" else self._draft_model
        rings = self._rings if self._layer_writes else None
        from ..jit.api import _LayerScope
        from ..models.generation import (sample_logits,
                                         sample_logits_per_row)

        def pure_prefill(params, buffers, ids, true_lens, seed,
                         greedy, temp, tk, tp):
            with _tape.no_grad(), _LayerScope(model, params, buffers):
                # dense prefill caches in the dtype the model computes
                # K/V in: f32 caches under a bf16 model would run the
                # prefill attention in f32 and leave the page scatter to
                # downcast implicitly
                caches = model.init_kv_caches(
                    nb, bucket, dtype=next(iter(params.values())).dtype)
                # causal mask => position true_len-1 ignores the padding;
                # what the model counts of its own work while it is traced
                # (an expert layer's pairs and rows) rides out last
                with _trace.device_counts() as counts:
                    last, caches = model.forward_prefill(
                        Tensor(ids), caches, true_lens)
                counts = {k: counts[k] for k in _PREFILL_COUNTS
                          if k in counts}
                # first token sampled ON DEVICE (round-2 verdict weak #5:
                # the host-side sample paid a [nb, vocab] transfer),
                # per-request params as runtime [nb] arrays
                key = jax.random.wrap_key_data(seed)
                if all_greedy:
                    first, _ = sample_logits(last, key, "greedy_search")
                else:
                    first, _ = sample_logits_per_row(last, key, greedy,
                                                     temp, tk, tp)
                if rings is not None:
                    # a layout with rings, or whose layers differ: a
                    # layer's own (k, v) an entry, and of a window layer
                    # the part of the prompts its ring keeps (the rest of its K/V dies with the layer,
                    # and no stack copies what is left)
                    return first, tuple(
                        tuple(as_array(a) if ring is None else _pa.ring_tail(
                            as_array(a), true_lens, ring, self.page_size)
                            for a in c)
                        for c, ring in zip(caches, rings)), counts
                # one stack per pool of the layout: (ks, vs) of [L, nb,
                # bucket, kvh, hd] for full attention, the latent rows
                # alone for latent attention
                stacks = tuple(
                    jnp.stack([as_array(c[j]) for c in caches])
                    for j in range(len(caches[0])))
            return (first,) + stacks + (counts,)

        fn = self._prefill_fns[(nb, bucket, all_greedy, which)] = \
            _cw.watch_jit("serving.prefill", jax.jit(pure_prefill),
                          tag=(nb, bucket, all_greedy, which))
        return fn

    def _get_page_write_fn(self, nb, bucket, which="target"):
        """One compiled page write per (batch-bucket, token-bucket), the
        prefill programs' own key: layer `li` of a prefill's stacked K/V
        lands in that layer's pools, which are DONATED (written in
        place). Rows past the admitted ones carry a write length of 0,
        so every one of their positions is dropped. int8 pages bring
        their scale pools in the same tuple; which="draft" is the same
        program at the draft model's shapes."""
        fn = self._page_write_fns.get((nb, bucket, which))
        if fn is not None:
            return fn

        def pure_page_write(pools, ks, vs, tables, lens, li):
            if len(pools) == 1:  # a layout of one pool: `vs` is None
                return (_pa.prefill_paged_pool(
                    pools[0],
                    jax.lax.dynamic_index_in_dim(ks, li, keepdims=False),
                    tables, lens),)
            write = _pa.prefill_paged_kv_cache_q8 if len(pools) == 4 \
                else _pa.prefill_paged_kv_cache
            return write(
                *pools, jax.lax.dynamic_index_in_dim(ks, li, keepdims=False),
                jax.lax.dynamic_index_in_dim(vs, li, keepdims=False),
                tables, lens)

        fn = self._page_write_fns[(nb, bucket, which)] = _cw.watch_jit(
            "serving.kv_scatter",
            jax.jit(pure_page_write, donate_argnums=(0,)),
            tag=(nb, bucket, which))
        return fn

    def _get_layer_write_fn(self, nb, bucket, ring, layout):
        """The page write of a layout with rings or whose layers differ, a
        compiled program a (batch-bucket, token-bucket, kind of layer: its
        ring and its pools' heads and widths): ONE layer's K and V as the
        prefill returned them land in that layer's donated pools. A full
        layer's (`ring` None) go through the rows' block tables; a window
        layer's are `ring_tail`s and go into the rings of the rows' SLOTS
        ([nb]), the pages a later step can still see."""
        key = (nb, bucket, "layer", ring, layout)
        fn = self._page_write_fns.get(key)
        if fn is not None:
            return fn

        def pure_layer_write(pools, k, v, where, lens):
            if ring is None:
                return _pa.prefill_paged_kv_cache(*pools, k, v, where, lens)
            return _pa.prefill_ring_kv_cache(*pools, k, v, where, lens,
                                             ring, bucket)

        fn = self._page_write_fns[key] = _cw.watch_jit(
            "serving.kv_scatter",
            jax.jit(pure_layer_write, donate_argnums=(0,)),
            tag=(nb, bucket, "ring" if ring else "full", layout[0][0]))
        return fn

    def _write_prefill_pages(self, write, k_pages, v_pages, k_scales,
                             v_scales):
        """Write every layer of a prefill's K/V into its pools:
        `write(li, pools)` is the compiled write of layer `li` with that
        layer's pools donated, and each layer's pools are re-bound to what
        it returns. False when an OOM was absorbed by a recovery (every
        request is back in the queue and the round is void)."""
        # the scales, where the pages are int8, in the order the q8 write
        # takes them: whether they exist is a fact of the tuple
        # (a layout of one pool, latent rows, has no `v_pages`)
        lists = (k_pages,) if not v_pages \
            else (k_pages, v_pages) if k_scales is None \
            else (k_pages, k_scales, v_pages, v_scales)
        try:
            for li in range(len(k_pages)):
                written = write(li, tuple(pools[li] for pools in lists))
                for pools, new in zip(lists, written):
                    pools[li] = new
        except BaseException as e:
            if _memwatch.is_oom(e):
                # no preempt-and-retry here: the admitted rows have no
                # first token yet, so the whole round goes back to the
                # queue through the drain -> rebuild -> re-admit recovery
                path = _memwatch.dump_oom(
                    "serving_prefill_page_write", exc=e,
                    extra=self._page_table_report())
                _flight.record_event("serving.oom",
                                     where="prefill_page_write", dump=path)
                if self._begin_recovery(
                        "decode_oom",
                        f"prefill page write raised RESOURCE_EXHAUSTED "
                        f"(forensics: {path})"):
                    return False
                raise
            self._poison_if_donated(
                "prefill page write raised after donating the KV pages",
                *lists)
            raise
        return True

    def _rows_held(self):
        """Rows a prefill round keeps from their next step: slots that
        have emitted a token and have more due. A slot whose first token
        is pending is not one (its wait is time to first token)."""
        return sum(s.active and not s.prefilling
                   and not s.needs_first_sample for s in self.slots)

    def _prefill_batch(self, new):
        """new: list of (slot_idx, prompt_ids) — ONE compiled forward for
        all admitted prompts + ONE compiled page write per layer into
        that layer's donated pools."""
        n = len(new)
        with _trace.phase("serving.prefill_batch",
                          rows_held=self._rows_held()):
            t0_prefill = _time_mod.perf_counter() if self._traces else 0.0
            # packing is the scheduler policy's call (default: next-pow2
            # batch capped at max_batch, token bucket = next page multiple)
            nb, bucket = self.scheduler.prefill_bucket(self, new)
            # clamp against policy bugs: the batch must hold every prompt
            # and the token bucket must page-align and cover the longest
            nb = min(max(nb, n), self.max_batch)
            longest = max(len(ids) for _, ids in new)
            bucket = max(-(-bucket // self.page_size) * self.page_size,
                         -(-longest // self.page_size) * self.page_size)
            all_greedy = all(self.slots[si].greedy for si, _ in new)
            with _trace.phase("serving.prefill.launch",
                              prompt_tokens=sum(len(ids) for _, ids in new),
                              padded_tokens=int(nb * bucket)):
                fn = self._get_prefill_fn(nb, bucket, all_greedy)
                params, buffers = self._cached_params()
                padded = np.zeros((nb, bucket), np.int64)
                # a padded row holds no token: length 0 (its last-position
                # index wraps to a position nothing reads)
                true_lens = np.zeros((nb,), np.int32)
                greedy = np.ones((nb,), bool)
                temp = np.ones((nb,), np.float32)
                tk = np.zeros((nb,), np.int32)
                tp_arr = np.ones((nb,), np.float32)
                for row, (si, ids) in enumerate(new):
                    padded[row, :len(ids)] = ids
                    true_lens[row] = len(ids)
                    rp = self._req_params[self.slots[si].request_id]
                    greedy[row] = rp["greedy"]
                    temp[row] = rp["temperature"]
                    tk[row] = rp["top_k"]
                    tp_arr[row] = rp["top_p"]
                self._key, sk = jax.random.split(self._key)
                lens = jnp.asarray(true_lens)
                prefill_args = (
                    jnp.asarray(padded), lens,
                    jax.random.key_data(sk), jnp.asarray(greedy),
                    jnp.asarray(temp), jnp.asarray(tk),
                    jnp.asarray(tp_arr))
                # (a layout with rings: `ks` is a layer's (k, v) an entry)
                first, ks, *vs, counts = fn(params, buffers, *prefill_args)
                vs = vs[0] if vs else None
            # the compiled per-layer page write (and, with a separate
            # draft model, its own prefill call and write)
            with _trace.phase("serving.kv_scatter"):
                # padded to nb like the prefill: a padded row has a table
                # row of zeros and, as in the prefill, a length of 0
                tables = np.zeros((nb, self.pages_per_seq), np.int32)
                tables[:n] = self.block_tables[[si for si, _ in new]]
                tables, write_lens = jnp.asarray(tables), lens
                if self._layer_writes:
                    # a layout with rings, or whose layers differ: `ks`
                    # holds a layer's own (k, v) an entry, a window layer's
                    # cut to what its ring keeps, which goes into the rings
                    # of the rows' slots
                    slots = np.zeros((nb,), np.int32)
                    slots[:n] = [si for si, _ in new]
                    slots = jnp.asarray(slots)

                    def write(li, pools):
                        ring = self._rings[li]
                        return self._get_layer_write_fn(
                            nb, bucket, ring, self._kv_layouts[li])(
                            pools, *ks[li], tables if ring is None
                            else slots, write_lens)
                else:
                    fn_w = self._get_page_write_fn(nb, bucket)

                    def write(li, pools):
                        return fn_w(pools, ks, vs, tables, write_lens,
                                    np.int32(li))
                if not self._write_prefill_pages(
                        write, self.k_pages, self.v_pages, self.k_scales,
                        self.v_scales):
                    return
                if self._draft_model is not None:
                    # the separate draft model needs the prompt in ITS
                    # pages too (two-model speculative decoding prefills
                    # twice — the draft is small, that is the trade); its
                    # sampled token is ignored
                    fn_d = self._get_prefill_fn(nb, bucket, all_greedy,
                                                which="draft")
                    dparams, dbuffers = self._cached_draft_params()
                    _f, dks, dvs, _counts = fn_d(dparams, dbuffers,
                                                 *prefill_args)
                    fn_dw = self._get_page_write_fn(nb, bucket, "draft")
                    if not self._write_prefill_pages(
                            lambda li, pools: fn_dw(
                                pools, dks, dvs, tables, write_lens,
                                np.int32(li)),
                            self._draft_k_pages, self._draft_v_pages,
                            self._draft_k_scales, self._draft_v_scales):
                        return
            if self._prefix_cache is not None:
                # cache the freshly prefilled FULL pages; the partial tail
                # page never enters the trie (the copy-on-write guard —
                # decode keeps appending to it exclusively)
                for si, ids in new:
                    self._prefix_cache.insert(ids, self.block_tables[si])
            with _trace.phase("serving.prefill.sync"):
                first_np = np.asarray(first)  # [nb] ints — tiny transfer
                # the program is done once its tokens are here: its counts
                # wait for the phase that commits these first tokens
                for k, v in counts.items():
                    self._prefill_counts["prefill_" + k] = int(v) \
                        + self._prefill_counts.get("prefill_" + k, 0)
            for row, (si, _) in enumerate(new):
                self.slots[si]._first_token = int(first_np[row])
            if self._traces:
                # ONE batched compiled prefill served every admitted prompt:
                # each participating trace gets the shared interval with its
                # bucket attrs (the span naming scheme's `prefill[bucket]`)
                t1_prefill = _time_mod.perf_counter()
                for _row, (si, ids) in enumerate(new):
                    tr = self._traces.get(self.slots[si].request_id)
                    if tr is not None:
                        tr.emit("serving.prefill", t0_prefill, t1_prefill,
                                bucket=bucket, nb=nb, prompt_len=len(ids))

    # ------------------------------------------------------------------
    # chunked prefill: the uncached suffix streams through the model's
    # paged window mode (paged_step s>1) in scheduler-budgeted chunks,
    # interleaved with decode bursts — a long prefill no longer
    # head-of-line-blocks every in-flight request's ITL
    # ------------------------------------------------------------------
    def _get_chunk_fn(self, width, all_greedy):
        """One compiled prefill-continuation per (chunk width,
        all-greedy?) at the full max_batch geometry: a [B, width] token
        window lands at positions lens..lens+width-1 of the paged cache
        (limit_lens masks each row's real take; inactive rows drop
        their writes), and the last real position's logits sample a
        first token — consumed only when a row's suffix completes."""
        fn = self._chunk_fns.get((width, all_greedy))
        if fn is not None:
            return fn
        _flight.record_event("serving.prefill_chunk_compile",
                             width=width, all_greedy=all_greedy)
        model = self.model
        serving_mesh = self.mesh
        from ..jit.api import _LayerScope
        from ..models.generation import (sample_logits,
                                         sample_logits_per_row)

        def pure_chunk(params, buffers, k_pages, v_pages, k_scales,
                       v_scales, win, tables, lens, active, limit, seed,
                       greedy, temp, tk, tp):
            with _tape.no_grad(), _LayerScope(model, params, buffers):
                caches = list(zip(k_pages, v_pages, k_scales,
                                  v_scales)) if k_scales \
                    else list(zip(k_pages, v_pages))
                logits, new_caches = model.forward_paged(
                    Tensor(win), caches, tables, lens, active=active,
                    mesh=serving_mesh, limit_lens=limit)
                # last REAL position per row: limit - lens - 1 (clip
                # covers inactive rows, where limit == lens == 0)
                pos = jnp.clip(limit - lens - 1, 0, width - 1)
                last = as_array(logits)[
                    jnp.arange(win.shape[0]), pos, :]
                key = jax.random.wrap_key_data(seed)
                if all_greedy:
                    first, _ = sample_logits(last, key, "greedy_search")
                else:
                    first, _ = sample_logits_per_row(last, key, greedy,
                                                     temp, tk, tp)
                nk = tuple(as_array(c[0]) for c in new_caches)
                nv = tuple(as_array(c[1]) for c in new_caches)
                nks = tuple(as_array(c[2])
                            for c in new_caches) if k_scales else ()
                nvs = tuple(as_array(c[3])
                            for c in new_caches) if k_scales else ()
            return first, nk, nv, nks, nvs

        fn = self._chunk_fns[(width, all_greedy)] = _cw.watch_jit(
            "serving.prefill_chunk",
            jax.jit(pure_chunk, donate_argnums=(2, 3, 4, 5)),
            tag=(width, all_greedy))
        return fn

    def _prefill_chunk_round(self, pf):
        """One continuation chunk for every prefilling slot in a single
        compiled window dispatch. Chunk width is the scheduler's
        prefill_chunk_budget call (page-aligned; slo_aware shrinks it
        under TTFT burn); with chunking OFF (a pure cache-hit
        continuation) one chunk covers the longest remaining suffix.
        The final chunk's sampled first token hands off to the standard
        first-token commit path in the SAME step, so a single-chunk
        continuation keeps dense-prefill TTFT timing. Admission already
        allocated every prompt page, so no growth happens here."""
        rem = {i: len(self.slots[i]._pf_ctx) - self.slots[i].context_len
               for i in pf}
        if self.prefill_chunk:
            c = int(self.scheduler.prefill_chunk_budget(self, pf))
            c = max(self.page_size, min(c, self.prefill_chunk))
        else:
            c = max(rem.values())
        c = -(-c // self.page_size) * self.page_size
        all_greedy = all(self.slots[i].greedy for i in pf)
        fn = self._get_chunk_fn(c, all_greedy)
        params, buffers = self._cached_params()
        B = self.max_batch
        win = np.zeros((B, c), np.int64)
        lens = np.zeros((B,), np.int32)
        limit = np.zeros((B,), np.int32)
        act = np.zeros((B,), bool)
        greedy = np.ones((B,), bool)
        temp = np.ones((B,), np.float32)
        tk = np.zeros((B,), np.int32)
        tp_arr = np.ones((B,), np.float32)
        for i in pf:
            s = self.slots[i]
            take = min(c, rem[i])
            win[i, :take] = s._pf_ctx[s.context_len:s.context_len + take]
            lens[i] = s.context_len
            limit[i] = s.context_len + take
            act[i] = True
            rp = self._req_params[s.request_id]
            greedy[i] = rp["greedy"]
            temp[i] = rp["temperature"]
            tk[i] = rp["top_k"]
            tp_arr[i] = rp["top_p"]
        self._key, sk = jax.random.split(self._key)
        t0 = _time_mod.perf_counter()
        led = _stepledger.begin()
        try:
            # arg prep inside the try: transfer-time OOM must reach the
            # forensics + preempt-retry path (same rule as decode)
            chunk_args = (
                params, buffers, tuple(self.k_pages),
                tuple(self.v_pages), tuple(self.k_scales or ()),
                tuple(self.v_scales or ()), jnp.asarray(win),
                jnp.asarray(self.block_tables), jnp.asarray(lens),
                jnp.asarray(act), jnp.asarray(limit),
                jax.random.key_data(sk), jnp.asarray(greedy),
                jnp.asarray(temp), jnp.asarray(tk),
                jnp.asarray(tp_arr))
            first, nk, nv, nks, nvs = fn(*chunk_args)
        except BaseException as e:
            if _memwatch.is_oom(e) and \
                    self._handle_decode_oom(e, "prefill_chunk"):
                return
            self._poison_if_donated(
                "prefill chunk fn raised after donating the KV pages",
                self.k_pages, self.v_pages)
            raise
        if led is not None:
            _stepledger.end(led, "serving.prefill_chunk",
                            _time_mod.perf_counter(),
                            out=(nk, nv, first))
            _stepledger.register_from_lowered(
                "serving.prefill_chunk", fn, chunk_args,
                quant=self._quant_algo,
                quant_bytes_delta=self._quant_bytes_correction())
        self.k_pages, self.v_pages = list(nk), list(nv)
        if self.k_scales is not None:
            self.k_scales, self.v_scales = list(nks), list(nvs)
        first_np = np.asarray(first)
        t1 = _time_mod.perf_counter()
        for i in pf:
            s = self.slots[i]
            if not s.active or not s.prefilling:
                continue
            take = min(c, rem[i])
            s.context_len += take
            s._pf_chunks_done += 1
            if self._traces:
                tr = self._traces.get(s.request_id)
                if tr is not None:
                    tr.emit("serving.prefill_chunk", t0, t1,
                            chunk=s._pf_chunks_done,
                            n_chunks=s._pf_n_chunks, width=c,
                            tokens=take)
            if s.context_len >= len(s._pf_ctx):
                # suffix complete: cache the full pages, then hand the
                # sampled first token to the standard commit path
                if self._prefix_cache is not None:
                    self._prefix_cache.insert(s._pf_ctx,
                                              self.block_tables[i])
                s._first_token = int(first_np[i])
                s.needs_first_sample = True
                s.prefilling = False
                s._pf_ctx = None
        _flight.record_event("serving.prefill_chunk", n=len(pf),
                             width=c)

    # ------------------------------------------------------------------
    # decode step: one jitted forward for all slots
    # ------------------------------------------------------------------
    def _decode_step_core(self, all_greedy):
        """ONE single-token decode step (forward_paged + sampling + cache
        repack) shared by the one-step program and the burst scan body —
        the single place the decode semantics live, so the two programs
        cannot drift apart."""
        model = self.model
        from ..models.generation import (sample_logits,
                                         sample_logits_per_row)

        serving_mesh = self.mesh

        def core(tok, kps, vps, kss, vss, tables, lens, act, key, greedy,
                 temp, tk, tp):
            # kss/vss non-empty iff kv_cache_quant: per-layer cache entry
            # is then (k_pages, v_pages, k_scales, v_scales)
            # a layout of one pool leaves vps empty: (pool,) a layer
            caches = list(zip(kps, vps, kss, vss)) if kss \
                else list(zip(kps, vps)) if vps else [(kp,) for kp in kps]
            # what the model counts of its own work while it is traced
            # (token-expert pairs of an expert layer); {} for most
            with _trace.device_counts() as counts:
                logits, new_caches = model.forward_paged(
                    Tensor(tok[:, None]), caches, tables, lens,
                    active=act, mesh=serving_mesh)
            if all_greedy:
                # static specialization: no vocab sort, argmax only
                nxt, _ = sample_logits(as_array(logits)[:, 0], key,
                                       "greedy_search")
            else:
                nxt, _ = sample_logits_per_row(
                    as_array(logits)[:, 0], key, greedy, temp, tk, tp)
            nk = tuple(as_array(c[0]) for c in new_caches)
            nv = tuple(as_array(c[1]) for c in new_caches) if vps else ()
            nks = tuple(as_array(c[2]) for c in new_caches) if kss else ()
            nvs = tuple(as_array(c[3]) for c in new_caches) if kss else ()
            return nxt, nk, nv, nks, nvs, counts

        return core

    def _get_decode_fn(self, all_greedy):
        fn = self._decode_fns.get(all_greedy)
        if fn is not None:
            return fn
        model = self.model
        from ..jit.api import _LayerScope

        core = self._decode_step_core(all_greedy)

        def pure_decode(params, buffers, k_pages, v_pages, k_scales,
                        v_scales, tokens, tables, lens, active, seed,
                        greedy, temp, tk, tp):
            with _tape.no_grad(), _LayerScope(model, params, buffers):
                key = jax.random.wrap_key_data(seed)
                nxt, nk, nv, nks, nvs, counts = core(
                    tokens, k_pages, v_pages, k_scales, v_scales, tables,
                    lens, active, key, greedy, temp, tk, tp)
            return nxt, nk, nv, nks, nvs, counts

        fn = self._decode_fns[all_greedy] = _cw.watch_jit(
            "serving.decode",
            jax.jit(pure_decode, donate_argnums=(2, 3, 4, 5)),
            tag=("greedy" if all_greedy else "mixed",))
        return fn

    def _get_burst_fn(self, all_greedy, n_steps):
        """Compiled K-step decode: lax.scan over the single-token step with
        on-device sampling feeding the next iteration. Per-row masks mirror
        the host's finish rules exactly — a row stays active while its
        remaining-token budget is positive and it has not emitted its eos —
        so the host replay of (tokens, emitted) flags reconstructs the same
        streams single-stepping would have produced."""
        fn = self._burst_fns.get((all_greedy, n_steps))
        if fn is not None:
            return fn
        model = self.model
        from ..jit.api import _LayerScope

        core = self._decode_step_core(all_greedy)

        def pure_burst(params, buffers, k_pages, v_pages, k_scales,
                       v_scales, tokens, tables, lens, active, rem, eos,
                       seed, greedy, temp, tk, tp):
            with _tape.no_grad(), _LayerScope(model, params, buffers):
                def one(carry, _):
                    tok, kps, vps, kss, vss, ln, act, rm, key = carry
                    key, sk = jax.random.split(key)
                    nxt, nk, nv, nks, nvs, counts = core(
                        tok, kps, vps, kss, vss, tables, ln, act, sk,
                        greedy, temp, tk, tp)
                    nxt = nxt.astype(tok.dtype)
                    emitted = act
                    ln2 = ln + act.astype(ln.dtype)
                    rm2 = rm - act.astype(rm.dtype)
                    act2 = act & (rm2 > 0) & (nxt != eos)
                    tok2 = jnp.where(act, nxt, tok)
                    return (tok2, nk, nv, nks, nvs, ln2, act2, rm2, key), \
                        (nxt, emitted, counts)

                key = jax.random.wrap_key_data(seed)
                carry, (toks, emits, counts) = jax.lax.scan(
                    one, (tokens, k_pages, v_pages, k_scales, v_scales,
                          lens, active, rem, key),
                    None, length=n_steps)
                tok_f, nk, nv, nks, nvs, ln_f, act_f, rm_f, key_f = carry
            # the scalar decode state rides back out so an async scheduler
            # can chain burst N+1 directly off burst N's DEVICE outputs
            # (no host round-trip between dispatches); the sync path just
            # ignores these leaves
            # the model's counts, summed over the burst's steps, ride out
            # LAST, beside the tokens: read with them, no sync of their own
            return (toks, emits, nk, nv, nks, nvs,
                    tok_f, ln_f, act_f, rm_f, jax.random.key_data(key_f),
                    {k: jnp.sum(v) for k, v in counts.items()})

        fn = self._burst_fns[(all_greedy, n_steps)] = _cw.watch_jit(
            "serving.decode_burst",
            jax.jit(pure_burst, donate_argnums=(2, 3, 4, 5)),
            tag=("greedy" if all_greedy else "mixed", n_steps))
        return fn

    # ------------------------------------------------------------------
    # self-speculative decoding: draft cheap, verify the window in ONE
    # target forward, commit the greedy-exact accepted prefix + 1
    # ------------------------------------------------------------------
    def _get_spec_draft_fn(self, n_draft):
        """Compiled draft: a lax.scan of `n_draft` cheap greedy decode
        steps. Shallow-exit mode runs the TARGET's first
        spec_draft_layers decoder layers + final norm + lm head over the
        target's own (exact, verify-written) paged KV for those layers;
        draft-model mode runs the separate model over its own pools.
        Draft writes land at the window positions and are overwritten by
        the verify forward (shallow-exit) or stay draft-consistent for
        the accepted prefix (draft model), so no rollback is needed."""
        fn = self._spec_draft_fns.get(n_draft)
        if fn is not None:
            return fn
        model = self._draft_model if self._draft_model is not None \
            else self.model
        max_layers = None if self._draft_model is not None \
            else self.spec_draft_layers
        serving_mesh = self.mesh
        from ..jit.api import _LayerScope

        def pure_draft(params, buffers, k_pages, v_pages, k_scales,
                       v_scales, tokens, tables, lens, active, limit):
            with _tape.no_grad(), _LayerScope(model, params, buffers):
                def one(carry, _):
                    tok, kps, vps, kss, vss, ln = carry
                    caches = list(zip(kps, vps, kss, vss)) if kss \
                        else list(zip(kps, vps))
                    logits, new_caches = model.forward_paged(
                        Tensor(tok[:, None]), caches, tables, ln,
                        active=active, mesh=serving_mesh,
                        limit_lens=limit, max_layers=max_layers)
                    nxt = jnp.argmax(
                        as_array(logits)[:, 0].astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
                    nk = tuple(as_array(c[0]) for c in new_caches)
                    nv = tuple(as_array(c[1]) for c in new_caches)
                    nks = tuple(as_array(c[2])
                                for c in new_caches) if kss else ()
                    nvs = tuple(as_array(c[3])
                                for c in new_caches) if kss else ()
                    tok2 = jnp.where(active, nxt.astype(tok.dtype), tok)
                    return (tok2, nk, nv, nks, nvs,
                            ln + active.astype(ln.dtype)), nxt

                carry, drafts = jax.lax.scan(
                    one, (tokens, k_pages, v_pages, k_scales, v_scales,
                          lens), None, length=n_draft)
                _tok, nk, nv, nks, nvs, _ln = carry
            return drafts, nk, nv, nks, nvs  # drafts: [n_draft, b] i32

        fn = self._spec_draft_fns[n_draft] = _cw.watch_jit(
            "serving.spec_draft",
            jax.jit(pure_draft, donate_argnums=(2, 3, 4, 5)),
            tag=(n_draft,))
        return fn

    def _get_spec_verify_fn(self, window):
        """Compiled verify: ONE batched target forward over the [b,
        window] token window (the pending last token + the drafts) at
        positions lens..lens+window-1 of the paged cache — every
        position's greedy argmax in a single dispatch, exactly the
        parallel-verification trade speculative decoding buys."""
        fn = self._spec_verify_fns.get(window)
        if fn is not None:
            return fn
        model = self.model
        serving_mesh = self.mesh
        from ..jit.api import _LayerScope

        def pure_verify(params, buffers, k_pages, v_pages, k_scales,
                        v_scales, tokens, drafts, tables, lens, active,
                        limit):
            with _tape.no_grad(), _LayerScope(model, params, buffers):
                # drafts may carry one extra trailing step (draft-model
                # mode writes the last draft's KV into its own pools);
                # the window consumes exactly window-1 of them
                win = jnp.concatenate(
                    [tokens[:, None],
                     jnp.transpose(drafts)[:, :window - 1]
                     .astype(tokens.dtype)], axis=1)
                caches = list(zip(k_pages, v_pages, k_scales,
                                  v_scales)) if k_scales \
                    else list(zip(k_pages, v_pages))
                logits, new_caches = model.forward_paged(
                    Tensor(win), caches, tables, lens, active=active,
                    mesh=serving_mesh, limit_lens=limit)
                g = jnp.argmax(as_array(logits).astype(jnp.float32),
                               axis=-1).astype(jnp.int32)  # [b, window]
                nk = tuple(as_array(c[0]) for c in new_caches)
                nv = tuple(as_array(c[1]) for c in new_caches)
                nks = tuple(as_array(c[2])
                            for c in new_caches) if k_scales else ()
                nvs = tuple(as_array(c[3])
                            for c in new_caches) if k_scales else ()
            return g, nk, nv, nks, nvs

        fn = self._spec_verify_fns[window] = _cw.watch_jit(
            "serving.spec_verify",
            jax.jit(pure_verify, donate_argnums=(2, 3, 4, 5)),
            tag=(window,))
        return fn

    def _spec_window(self, active, rem_of):
        """The speculative window for this dispatch, or 0 when the round
        must take the classic path: spec off, a non-greedy row in the
        batch (acceptance is greedy-exact prefix matching), or every row
        on its last token (nothing to draft)."""
        if self.spec_decode < 2:
            return 0
        if max(rem_of.values()) <= 1:
            return 0
        if not all(self.slots[i].greedy for i in active):
            return 0
        return self.spec_decode

    def _dispatch_spec(self, window, active, st, tokens):
        """One speculative round for the active slots. Returns the list
        of requests it finished, or None when an OOM preemption round
        consumed a slot and the caller must rebuild its launch state and
        retry. Page reservation for min(window, rem) positions per row
        already happened in step()'s shared loop; overhang positions are
        masked on device via `limit`."""
        lens, act_mask = st["lens"], st["act_mask"]
        limit = (lens + np.minimum(st["rem"], window)).astype(np.int32)
        params, buffers = self._cached_params()
        t0 = _time_mod.perf_counter()
        tok0 = self._m.tokens.value
        if self._traces:
            for i in active:
                tr = self._traces.get(self.slots[i].request_id)
                if tr is not None and "decode_t0" not in tr.marks:
                    tr.mark("decode_t0", t0)
        led = _stepledger.begin()
        shallow = self._draft_model is None
        # shallow-exit drafts window-1 tokens (verify overwrites the
        # target pages anyway); a separate draft model runs ONE extra
        # step so the last draft token's KV lands in its own pools —
        # the verify forward never writes those, and without it the
        # next round's draft would attend a stale slot after a fully
        # accepted window
        n_scan = window - 1 if shallow else window
        draft_fn = self._get_spec_draft_fn(n_scan)
        verify_fn = self._get_spec_verify_fn(window)
        Ld = self.spec_draft_layers if shallow else None
        with _trace.phase("serving.decode.launch"):
            try:
                # arg prep inside the try: transfer-time OOM must reach the
                # forensics + preempt-retry path (same rule as burst/decode)
                tok_dev = jnp.asarray(tokens)
                tables_dev = jnp.asarray(self.block_tables)
                lens_dev = jnp.asarray(lens)
                act_dev = jnp.asarray(act_mask)
                lim_dev = jnp.asarray(limit)
                if shallow:
                    draft_args = (
                        params, buffers, tuple(self.k_pages[:Ld]),
                        tuple(self.v_pages[:Ld]),
                        tuple((self.k_scales or [])[:Ld]),
                        tuple((self.v_scales or [])[:Ld]),
                        tok_dev, tables_dev, lens_dev, act_dev, lim_dev)
                else:
                    dparams, dbuffers = self._cached_draft_params()
                    draft_args = (
                        dparams, dbuffers, tuple(self._draft_k_pages),
                        tuple(self._draft_v_pages),
                        tuple(self._draft_k_scales or ()),
                        tuple(self._draft_v_scales or ()),
                        tok_dev, tables_dev, lens_dev, act_dev, lim_dev)
                drafts, dk, dv, dks, dvs = draft_fn(*draft_args)
                # re-point the drafted pools at the live buffers BEFORE the
                # verify dispatch donates the engine's page lists again
                if shallow:
                    self.k_pages[:Ld] = list(dk)
                    self.v_pages[:Ld] = list(dv)
                    if self.k_scales is not None:
                        self.k_scales[:Ld] = list(dks)
                        self.v_scales[:Ld] = list(dvs)
                else:
                    self._draft_k_pages = list(dk)
                    self._draft_v_pages = list(dv)
                    if self._draft_k_scales is not None:
                        self._draft_k_scales = list(dks)
                        self._draft_v_scales = list(dvs)
                verify_args = (
                    params, buffers, tuple(self.k_pages),
                    tuple(self.v_pages), tuple(self.k_scales or ()),
                    tuple(self.v_scales or ()), tok_dev, drafts,
                    tables_dev, lens_dev, act_dev, lim_dev)
                g, nk, nv, nks, nvs = verify_fn(*verify_args)
            except BaseException as e:
                if _memwatch.is_oom(e) and \
                        self._handle_decode_oom(e, "spec_decode"):
                    return None
                self._poison_if_donated(
                    "spec decode fn raised after donating the KV pages",
                    self.k_pages, self.v_pages)
                raise
        if led is not None:
            # the verify program dominates the round's device time —
            # register ITS cost for the roofline; the draft rides in the
            # same measured dispatch window
            _stepledger.end(led, "serving.spec_verify",
                            _time_mod.perf_counter(), out=(nk, nv, g))
            _stepledger.register_from_lowered(
                "serving.spec_verify", verify_fn, verify_args,
                quant=self._quant_algo,
                quant_bytes_delta=self._quant_bytes_correction())
        self.k_pages, self.v_pages = list(nk), list(nv)
        if self.k_scales is not None:
            self.k_scales, self.v_scales = list(nks), list(nvs)
        with _trace.phase("serving.decode.sync"):
            drafts, g = np.asarray(drafts), np.asarray(g)
        with _trace.phase("serving.emit"):
            finished = self._commit_spec(drafts, g, active, window)
        self._step_metrics(t0, len(active), tok0)
        return finished

    def _commit_spec(self, drafts, g, active, window):
        """Host replay of one speculative round. drafts: [window-1, b];
        g: [b, window] target greedy tokens. Commit the longest prefix
        where draft j matched the target's token j (greedy-exact: the
        committed stream is exactly what non-speculative greedy decoding
        would have produced), plus the one corrected token; rewind is
        implicit — context_len only advances over the accepted inputs,
        so the rejected tail's page slots are dead until overwritten."""
        finished = []
        for i in active:
            s = self.slots[i]
            if not s.active:
                continue  # abort()ed from an on_token callback
            committed = [int(g[i, 0])]
            for j in range(1, window):
                if int(drafts[j - 1, i]) != int(g[i, j - 1]):
                    break
                committed.append(int(g[i, j]))
            rem = s.max_new_tokens - len(s.tokens)
            committed = committed[:max(rem, 0)]
            eos = self._req_eos(s.request_id)
            if eos is not None:
                for idx, tok in enumerate(committed):
                    if tok == eos:
                        committed = committed[:idx + 1]
                        break
            accepted = max(len(committed) - 1, 0)
            # proposed = drafts this row could have COMMITTED (budget
            # cap), not the raw scan length: a max_new_tokens=2 request
            # in a window-4 engine can accept at most 1 draft however
            # well the draft path agrees — charging 3 would make the
            # acceptance rate measure budget geometry, not draft
            # quality (eos truncation still deflates; eos ends the
            # request, that is real)
            proposed = max(min(window, rem) - 1, 0)
            s.spec_proposed += proposed
            s.spec_accepted += accepted
            self._spec_proposed_total += proposed
            self._spec_accepted_total += accepted
            self._m.spec_proposed.inc(proposed)
            self._m.spec_accepted.inc(accepted)
            for tok in committed:
                s.context_len += 1
                s.tokens.append(tok)
                self._stream(s.request_id, tok)
                if not s.active:
                    break  # the callback above aborted THIS request
                if len(s.tokens) >= s.max_new_tokens or (
                        eos is not None and tok == eos):
                    finished.append(self._finish(i))
                    break
        return finished

    def _take_prefill_counts(self):
        """The prefill programs' counts since the last call, as attributes
        of the phase that commits their first tokens."""
        counts, self._prefill_counts = self._prefill_counts, {}
        return counts

    def _rem_of(self, active):
        """Remaining new-token budget per active slot — the ONE place the
        budget rule lives (k_burst sizing, page reservation, and the
        device rem array all derive from it)."""
        return {i: self.slots[i].max_new_tokens - len(self.slots[i].tokens)
                for i in active}

    def _decode_launch_state(self, active):
        """Per-row launch arrays for a decode dispatch, shared by the sync
        and async paths — one assembly point keeps their documented greedy
        bitwise parity true by construction."""
        defaults = dict(greedy=True, temperature=1.0, top_k=0, top_p=1.0)

        def _rp(s):
            return self._req_params.get(s.request_id, defaults) \
                if s.active else defaults

        rem_of = self._rem_of(active)
        act_mask = np.asarray([s.active and i in active
                               for i, s in enumerate(self.slots)], bool)
        return dict(
            rem_of=rem_of,
            act_mask=act_mask,
            lens=np.asarray([s.context_len if s.active else 0
                             for s in self.slots], np.int32),
            all_greedy=all(self.slots[i].greedy for i in active),
            greedy=np.asarray([_rp(s)["greedy"] for s in self.slots],
                              bool),
            temp=np.asarray([_rp(s)["temperature"] for s in self.slots],
                            np.float32),
            tk=np.asarray([_rp(s)["top_k"] for s in self.slots], np.int32),
            tp=np.asarray([_rp(s)["top_p"] for s in self.slots],
                          np.float32),
            rem=np.asarray(
                [max(rem_of.get(i, 0), 0) if act_mask[i] else 0
                 for i in range(self.max_batch)], np.int32),
            eos=np.asarray(
                [e if s.active and
                 (e := self._req_eos(s.request_id)) is not None else -1
                 for s in self.slots], np.int32),
        )

    @staticmethod
    def _buffers_deleted(buffers) -> bool:
        """True when any of the page buffers handed to a failed compiled
        call was actually donated (deleted). Distinguishes a post-
        donation failure (engine must be poisoned) from a pre-donation
        one — argument conversion or trace/compile errors — where the
        pools are intact and the engine can keep serving. Unknowable
        states poison (fail safe)."""
        try:
            return any(b.is_deleted() for b in buffers)
        except Exception:
            return True

    def _poison_if_donated(self, why: str, *page_lists):
        """Post-donation failure: the pools the engine holds are dead
        buffers. Route through the drain->rebuild->re-admit recovery
        (the pools come back as fresh zero pages; in-flight requests
        requeue and re-prefill) — the original exception still
        propagates from the caller, but the NEXT step() serves again.
        Past the recovery budget this poisons, the old fail-fast
        behavior."""
        for pages in page_lists:
            if pages and self._buffers_deleted(pages):
                self._begin_recovery("donated_buffers", why)
                return

    def _poison(self, why: str):
        """Mark the engine unusable: a compiled call raised after its
        donated KV page arguments were already deleted, so the pools the
        engine holds are dead buffers (ADVICE.md round-5)."""
        self._poisoned = why
        self._m.poisoned.set(1.0)
        self._m.errors.inc()  # the error_rate SLO burns on poisons
        _trace.instant("serving.poisoned", why=why)
        _flight.record_event("serving.poisoned", why=why)

    def _check_poisoned(self):
        if self._poisoned:
            raise RuntimeError(
                f"ServingEngine is poisoned ({self._poisoned}): a "
                f"compiled decode call raised after donating the KV page "
                f"pools, so the engine holds deleted buffers. Recreate "
                f"the engine; in-flight requests must be re-submitted.")

    def _quant_weight_delta(self):
        """(algo, bytes) of the model's weight-only quantization: the
        per-forward byte overcount a cost_analysis pass makes when it
        bills the dequantized float weight as traffic. Only layers
        whose shape the fused kernel can actually serve count — a
        quantized linear that fails `quant_matmul.supports` (e.g. an
        n % 128 vocab projection) always dispatches via the XLA path
        where the float weight IS materialized, so its cost_analysis
        bytes are already honest. Zero for unquantized models. Never
        raises."""
        try:
            from ..kernels import quant_matmul as _qm

            try:
                # the dequantized intermediate takes the activations'
                # dtype — the first (float) param's, e.g. the embedding
                float_itemsize = jnp.dtype(next(
                    iter(self.model.parameters()))._data.dtype).itemsize
            except StopIteration:
                float_itemsize = 4
            algo = None
            delta = 0.0
            stack = [self.model]
            while stack:
                layer = stack.pop()
                for child in getattr(layer, "_sub_layers", {}).values():
                    if type(child).__name__ == "WeightOnlyLinear" \
                            and child._algo != "llm.int8":
                        algo = algo or child._algo
                        if _qm._default_blocks(
                                child._in_features,
                                child._out_features,
                                child._weight_dtype,
                                child._group_size) == (None, None):
                            continue  # fused kernel can never serve it
                        n_elems = (child._in_features
                                   * child._out_features)
                        float_bytes = n_elems * float_itemsize
                        int_bytes = int(
                            child.quant_weight._data.nbytes)
                        delta += max(float_bytes - int_bytes, 0)
                    else:
                        stack.append(child)
            return algo, float(delta)
        except Exception:  # noqa: BLE001 — telemetry must never take
            return None, 0.0  # engine construction down

    def _quant_bytes_correction(self):
        """The byte delta to subtract for the CURRENT dispatch mode:
        only when the fused dequant-in-kernel path serves
        (quant_matmul_dispatch's gate). Under the XLA traced dequant the
        float weight IS materialized, so cost_analysis's bytes are
        already honest — subtracting there would misclassify
        memory-bound decode as compute-bound, the opposite dishonesty."""
        from ..kernels import quant_matmul as _qm

        return self._quant_bytes_delta if _qm.fused_requested() else 0.0

    # ------------------------------------------------------------------
    # memory observability (memwatch channel)
    # ------------------------------------------------------------------
    def _record_static_breakdown(self):
        """Publish this engine's static memory budget: param bytes + KV
        page-pool bytes (pages + quant scales) into the
        memwatch_breakdown_bytes gauges. Never raises."""
        try:
            params = sum(int(p._data.nbytes)
                         for p in self.model.parameters())
            kv = sum(int(p.nbytes) for p in self.k_pages + self.v_pages)
            if self.k_scales is not None:
                kv += sum(int(p.nbytes)
                          for p in self.k_scales + self.v_scales)
            _memwatch.record_breakdown(params=params, kv_pages=kv)
        except Exception:  # noqa: BLE001 — telemetry must never take
            pass           # engine construction down

    def _observe_memory(self):
        """Per-step memwatch close-out (FLAGS_memwatch on): KV pool
        occupancy + internal-fragmentation histograms, free-page gauge,
        and one HBM watermark sample. Handles were resolved at engine
        build — zero registry allocations per step."""
        free = len(self._free_pages)
        self._m.kv_free.set(free)
        self._m.kv_occupancy.observe(1.0 - free / self._n_pages_total)
        # fragmentation over UNIQUE pages: a prefix page shared by N
        # slots is one page of capacity holding one page of tokens —
        # the per-slot sum would count it N times and overstate both
        # sides (identical to the old per-slot sums when nothing is
        # shared). Trie-only residents hold full cached pages.
        seen: Dict[int, int] = {}
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            for j, pid in enumerate(
                    self.block_tables[i, :s.n_pages].tolist()):
                filled = min(self.page_size,
                             max(s.context_len - j * self.page_size, 0))
                if filled > seen.get(pid, -1):
                    seen[pid] = filled
        if self._prefix_cache is not None:
            for pid in self._prefix_cache.pages():
                if pid not in seen:
                    seen[pid] = self.page_size
        alloc_tokens = len(seen) * self.page_size
        used_tokens = sum(seen.values())
        self._m.kv_frag.observe(
            1.0 - used_tokens / alloc_tokens if alloc_tokens else 0.0)
        _memwatch.sample()

    def _page_table_report(self) -> str:
        """The page-table half of an OOM forensic dump: per-slot page
        allocation + context, pool state, and internal fragmentation."""
        lines = [
            "== kv page table ==",
            f"pool: {self._n_pages_total} pages x {self.page_size} "
            f"tokens, {len(self._free_pages)} free, dtype "
            f"{jnp.dtype(self.kv_dtype).name}"
            + (", quant int8" if self.kv_cache_quant else ""),
        ]
        for i, s in enumerate(self.slots):
            if not s.active:
                lines.append(f"  slot {i}: (idle)")
                continue
            pages = self.block_tables[i, :s.n_pages].tolist()
            waste = s.n_pages * self.page_size - s.context_len
            lines.append(
                f"  slot {i}: rid {s.request_id}, ctx {s.context_len}, "
                f"{s.n_pages} pages (waste {waste} tok), "
                f"admit_seq {s.admit_seq}, tokens {len(s.tokens)}/"
                f"{s.max_new_tokens}, pages {pages}")
        if self._prefix_cache is not None:
            lines.append(
                f"prefix cache: {len(self._prefix_cache)} pages cached, "
                f"{self._prefix_cache.evictable()} evictable, "
                f"{self._prefix_cache.evictions} evicted")
        lines.append(f"pending queue: {len(self._pending)} request(s)")
        return "\n".join(lines)

    def _begin_recovery(self, cause: str, why: str) -> bool:
        """Self-heal the engine: drain -> rebuild -> re-admit
        (README.md "Fault tolerance") instead of the old permanent
        poison.

        Drain: every active slot requeues at the FRONT of pending with
        its tokens so far (recompute policy, exactly _preempt's), but
        bounded by a per-request retry budget
        (FLAGS_serving_request_retries) so one pathological request
        cannot pin the engine in a crash loop — over-budget requests
        are dropped and counted as UNRECOVERED errors. Rebuild: the KV
        page pools (possibly deleted buffers after a donation failure)
        reallocate fresh, the free list / block tables / slot structs
        reset, and an exponential backoff
        (FLAGS_serving_recovery_backoff_s * 2^(attempt-1)) absorbs
        thundering-herd retries. Re-admit happens on the next step()'s
        _admit(), which re-prefills each requeued request's context.

        Bounded by FLAGS_serving_max_recoveries over the engine's
        lifetime; past that budget the engine poisons (fail fast, the
        pre-recovery behavior). Returns True when the engine recovered
        and the caller may keep serving, False when it poisoned.
        /readyz is 503 while the rebuild runs (self._recovering);
        /healthz reports "degraded" once self._recoveries > 0."""
        from ..framework import config as _config

        budget = int(_config.get_flag("FLAGS_serving_max_recoveries", 3))
        if self._recoveries >= budget:
            self._poison(f"recovery budget exhausted "
                         f"({self._recoveries}/{budget}): {why}")
            return False
        self._recoveries += 1
        self._recovering = True
        try:
            _trace.instant("serving.recovery", cause=cause, why=why)
            _flight.record_event("serving.recovery", cause=cause,
                                 attempt=self._recoveries, why=why)
            retries = int(_config.get_flag(
                "FLAGS_serving_request_retries", 2))
            for idx, s in enumerate(self.slots):
                if not s.active:
                    s.trace_id = -1
                    continue
                rid = s.request_id
                n = self._retry_counts.get(rid, 0) + 1
                if n > retries:
                    # retry budget spent: drop — an UNRECOVERED failure
                    # (the error_rate SLO burns on it), same emission
                    # semantics as abort()
                    self._m.errors.inc()
                    self._m.aborts.inc()
                    self._prompts.pop(rid, None)
                    self._req_params.pop(rid, None)
                    self._retry_counts.pop(rid, None)
                    self._finish_trace(rid, aborted="recovery")
                    _flight.record_event("serving.recovery_drop",
                                         rid=rid, retries=n - 1)
                else:
                    self._retry_counts[rid] = n
                    self._pending.insert(
                        0, (rid, self._prompts[rid], s.max_new_tokens,
                            list(s.tokens)))
                # deactivate by hand: _release_slot would push page ids
                # from a table we are about to wipe onto the free list
                s.active = False
                s.n_pages = 0
                s.prefilling = False
                s._pf_ctx = None
                s.trace_id = -1
            # rebuild: fresh pools — the old lists may hold deleted
            # buffers, and even live ones hold KV for contexts that
            # will re-prefill anyway (mirrors __init__'s allocation)
            L = self.cfg.num_hidden_layers
            kvh = self._kv_layouts[0][0][0]
            n_pages = self._n_pages_total
            if self.kv_cache_quant == "int8":
                self.k_scales, self.v_scales = map(list, zip(*[
                    _pa.alloc_page_scales(n_pages, self.page_size, kvh)
                    for _ in range(L)]))
            self.k_pages, self.v_pages = self._alloc_pools()
            if self._page_sharding is not None:
                self._pin_pages()
            if self._draft_model is not None:
                dcfg = self._draft_model.config
                dkvh = getattr(dcfg, "num_key_value_heads",
                               dcfg.num_attention_heads)
                dhd = dcfg.hidden_size // dcfg.num_attention_heads
                dL = dcfg.num_hidden_layers
                try:
                    d_dtype = next(iter(
                        self._draft_model.parameters()))._data.dtype
                except StopIteration:
                    d_dtype = jnp.float32
                if self.kv_cache_quant == "int8":
                    d_dtype = jnp.int8
                    self._draft_k_scales, self._draft_v_scales = map(
                        list, zip(*[_pa.alloc_page_scales(
                            n_pages, self.page_size, dkvh)
                            for _ in range(dL)]))
                self._draft_k_pages = [
                    jnp.zeros((dkvh, n_pages, self.page_size, dhd),
                              d_dtype) for _ in range(dL)]
                self._draft_v_pages = [
                    jnp.zeros((dkvh, n_pages, self.page_size, dhd),
                              d_dtype) for _ in range(dL)]
            self._free_pages = list(range(n_pages))
            self._page_refs = [0] * n_pages
            if self._prefix_cache is not None:
                # drop the cache wholesale: its nodes name pages of the
                # pools just rebuilt; clear() leaves refs/free alone
                # (both were reset above) and the trie rebinds to the
                # NEW accounting lists
                dropped = self._prefix_cache.clear()
                self._prefix_cache = _pc.PrefixCache(
                    self.page_size, self._page_refs, self._free_pages)
                if self._kv_tiers is not None:
                    # the spill tiers survive recovery on purpose:
                    # their bytes were host-copied at eviction time, so
                    # the rebuilt engine re-admits warm prefixes by
                    # promotion instead of recomputing them
                    self._prefix_cache.attach_tiers(
                        self._kv_tiers, self._gather_page_blob)
                if dropped:
                    self._m.cache_evictions.inc(dropped)
                    _flight.record_event("serving.prefix_cache_drop",
                                         pages=dropped)
            self.block_tables[:] = 0
            self._release_gen += 1
            self._oom_retried = False
            self._m.queue_depth.set(len(self._pending))
            self._m.recoveries.labels(cause).inc()
            backoff = float(_config.get_flag(
                "FLAGS_serving_recovery_backoff_s", 0.5))
            if backoff > 0:
                _time_mod.sleep(backoff * (2 ** (self._recoveries - 1)))
        finally:
            self._recovering = False
        return True

    def _handle_decode_oom(self, exc, where: str) -> bool:
        """RESOURCE_EXHAUSTED in a compiled decode call: write the
        forensic dump (ranked live buffers + the page-table report),
        then degrade gracefully ONCE — preempt the lowest-priority
        (youngest-admitted) slot and tell the caller to retry the
        dispatch. A second OOM, or one that already consumed the
        donated pools, escalates to the drain->rebuild->re-admit
        recovery (_begin_recovery) — and only past the recovery budget
        does the engine poison. Returns True when the caller should
        retry the dispatch (against the surviving slots, or an empty
        batch after a full drain)."""
        path = _memwatch.dump_oom(f"serving_{where}", exc=exc,
                                  extra=self._page_table_report())
        _flight.record_event("serving.oom", where=where, dump=path)
        if any(pages and self._buffers_deleted(pages)
               for pages in (self.k_pages, self.v_pages)):
            return self._begin_recovery(
                "decode_oom",
                f"{where} raised RESOURCE_EXHAUSTED after donating the "
                f"KV pages (forensics: {path})")
        if self._oom_retried:
            return self._begin_recovery(
                "oom_storm",
                f"{where} OOM persisted after a preemption round "
                f"(forensics: {path})")
        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            return self._begin_recovery(
                "decode_oom",
                f"{where} OOM with no active slots (forensics: {path})")
        victim = self.scheduler.select_victim(self, active, "decode_oom")
        self._oom_retried = True
        _flight.record_event("serving.oom_preempt",
                             rid=self.slots[victim].request_id,
                             slot=victim)
        self._preempt(victim)
        return True

    def step(self) -> List[FinishedRequest]:
        """Run one decode step for all active slots; returns requests that
        finished this step."""
        self._check_poisoned()
        self._admit()  # batched prefill of everything admissible
        # chunked-prefill continuation: each prefilling slot advances
        # one chunk per step, INTERLEAVED with the decode dispatch below
        pf = [i for i, s in enumerate(self.slots)
              if s.active and s.prefilling]
        if pf:
            with _trace.phase("serving.prefill_batch",
                              rows_held=self._rows_held()):
                self._prefill_chunk_round(pf)
        # prefilling slots are excluded from decode (their context is
        # partial and they have no last token yet)
        active = [i for i, s in enumerate(self.slots)
                  if s.active and not s.prefilling]
        if not active:
            return []
        # first step for a slot consumes the prefill-time device-side
        # sample; afterwards the decode fn both samples and advances
        tokens = np.zeros((self.max_batch,), np.int64)
        first_done = []
        now = _time_mod.perf_counter()
        # the first tokens' commit (callbacks, a finish on the first
        # token) is an emit phase of its own; a step that has none opens
        # nothing here
        with (_trace.phase("serving.emit", **self._take_prefill_counts())
              if any(self.slots[i].needs_first_sample for i in active)
              else _trace.NOOP_SPAN):
            for i, s in enumerate(self.slots):
                if not s.active or s.prefilling:
                    continue  # mid-chunked-prefill: no last token yet
                if s.needs_first_sample:
                    s.needs_first_sample = False
                    s.tokens.append(s._first_token)
                    rp = self._req_params.get(s.request_id)
                    # popping t_enq makes TTFT one-shot: a request preempted
                    # AFTER its first token re-prefills (needs_first_sample
                    # fires again) but must not record a second "TTFT"; one
                    # preempted BEFORE it still records the true
                    # enqueue-to-first-token time, preemption delay included
                    if rp is not None and "t_enq" in rp:
                        ttft = now - rp.pop("t_enq")
                        rp["ttft_s"] = ttft  # retained for the ledger
                        ex = None
                        if self._traces:
                            tr0 = self._traces.get(s.request_id)
                            if tr0 is not None and \
                                    tr0.trace_id is not None:
                                # OpenMetrics exemplar: this observation's
                                # trace_id, so a TTFT outlier in /metrics
                                # links straight to its distributed trace
                                ex = {"trace_id": f"{tr0.trace_id:x}"}
                        self._m.ttft.observe(ttft, exemplar=ex)
                    if self._traces:
                        tr = self._traces.get(s.request_id)
                        if tr is not None:
                            tr.instant("serving.first_token")
                    self._stream(s.request_id, s._first_token)
                    eos = self._req_eos(s.request_id)
                    if (eos is not None and s.tokens[-1] == eos) or \
                            len(s.tokens) >= s.max_new_tokens:
                        first_done.append(i)
                tokens[i] = s.tokens[-1]
            for i in first_done:
                # request finished on its very first token; never decode it
                active = [j for j in active if j != i]
            finished_early = [self._finish(i) for i in first_done]
        if not active:
            if finished_early:
                self._admit()
            return finished_early
        # burst sizing buckets to {1, decode_burst} — ONE compiled scan
        # length (a per-tail-length K would compile a new program for every
        # distinct remaining budget). Rows that exhaust their budget or hit
        # eos mid-burst deactivate on device, so a partially-useful final
        # burst is correct, just not free; it only occurs while the queue
        # drains. max rem == 1 (every row on its last token) drops to the
        # single-step program.
        #
        # The dispatch runs inside a retry loop: a RESOURCE_EXHAUSTED
        # from the compiled call gets one graceful-degradation round
        # (_handle_decode_oom dumps forensics and preempts the youngest
        # slot) before the engine poisons — the launch state is rebuilt
        # from the surviving slots and the dispatch retried.
        while True:
            # everything from page growth to the compiled call is ONE
            # launch phase; a retry round (`continue`) opens another
            with _trace.phase("serving.decode.launch"):
                rem_of = self._rem_of(active)
                # speculative rounds replace the burst path when eligible
                # (all-greedy batch with more than one token of budget)
                spec_w = self._spec_window(active, rem_of)
                # scan length is the scheduler policy's call (default
                # buckets to {1, decode_burst}); clamp to sizes the engine
                # compiles programs for
                k_burst = int(self.scheduler.burst_k(self, active, rem_of))
                k_burst = self.decode_burst if k_burst > 1 else 1
                # on-demand page growth for the positions this step writes
                # (one per single step, up to min(burst, remaining) for a
                # burst, up to min(window, remaining) for a spec round);
                # pool exhaustion preempts the youngest slot (recompute
                # policy) and retries, so the oldest slots always make
                # progress
                reserve = spec_w if spec_w else k_burst
                while True:
                    stalled = [i for i in active
                               if not self._ensure_pages(
                                   i, min(reserve, rem_of[i]))]
                    if not stalled:
                        break
                    victim = self.scheduler.select_victim(
                        self, stalled, "page_stall")
                    self._preempt(victim)
                    active = [j for j in active if j != victim]
                    if not active:
                        return finished_early
                st = self._decode_launch_state(active)
                if _faults.enabled():
                    # deterministic chaos (faults/chaos.py): rank.kill dies
                    # HARD mid-serve (the kv-fabric drill proves the router
                    # loses zero requests when a worker vanishes); an
                    # injected decode OOM takes the SAME handler as an
                    # organic RESOURCE_EXHAUSTED from the compiled call;
                    # rank.slow sleeps the decode step, turning this rank
                    # into a straggler the anomaly detectors must catch
                    _faults.maybe_kill()
                    _faults.maybe_slow()
                    try:
                        _faults.maybe_decode_oom()
                    except BaseException as e:
                        if _memwatch.is_oom(e) and \
                                self._handle_decode_oom(e, "decode"):
                            active = [i for i in active
                                      if self.slots[i].active]
                            if not active:
                                return finished_early
                            continue
                        raise
                if not spec_w:
                    all_greedy = st["all_greedy"]
                    lens, act_mask = st["lens"], st["act_mask"]
                    greedy, temp, tk, tp_arr = (st["greedy"], st["temp"],
                                                st["tk"], st["tp"])
                    self._key, sk = jax.random.split(self._key)
                    params, buffers = self._cached_params()
                    t0 = _time_mod.perf_counter()
                    tok0 = self._m.tokens.value
                    if self._traces:
                        # the per-request aggregate decode span runs from
                        # the first dispatch that includes the slot to its
                        # finish
                        for i in active:
                            tr = self._traces.get(
                                self.slots[i].request_id)
                            if tr is not None \
                                    and "decode_t0" not in tr.marks:
                                tr.mark("decode_t0", t0)
                    # step-time ledger (one flag read when off): open the
                    # measured dispatch window for this decode step
                    led = _stepledger.begin()
                    burst = k_burst > 1
                    fn = self._get_burst_fn(all_greedy, k_burst) if burst \
                        else self._get_decode_fn(all_greedy)
                    try:
                        # arg prep stays INSIDE the try: the host->device
                        # transfers can themselves raise RESOURCE_EXHAUSTED
                        # near the HBM ceiling, and that must reach the
                        # same forensics + preempt-retry path as the call
                        args = (
                            params, buffers, tuple(self.k_pages),
                            tuple(self.v_pages),
                            tuple(self.k_scales or ()),
                            tuple(self.v_scales or ()),
                            jnp.asarray(tokens),
                            jnp.asarray(self.block_tables),
                            jnp.asarray(lens), jnp.asarray(act_mask))
                        if burst:
                            args += (jnp.asarray(st["rem"]),
                                     jnp.asarray(st["eos"]))
                        args += (jax.random.key_data(sk),
                                 jnp.asarray(greedy), jnp.asarray(temp),
                                 jnp.asarray(tk), jnp.asarray(tp_arr))
                        # the arguments are copied: what follows is the
                        # compiled call's own dispatch
                        _trace.mark("serving.dispatch")
                        if burst:
                            (toks, emits, nk, nv, nks, nvs, *_carry,
                             counts) = fn(*args)
                        else:
                            toks, nk, nv, nks, nvs, counts = fn(*args)
                    except BaseException as e:
                        if _memwatch.is_oom(e) and self._handle_decode_oom(
                                e, "burst_decode" if burst else "decode"):
                            active = [i for i in active
                                      if self.slots[i].active]
                            if not active:
                                return finished_early
                            continue
                        self._poison_if_donated(
                            ("burst decode" if burst else "decode")
                            + " fn raised after donating the KV pages",
                            self.k_pages, self.v_pages)
                        raise
            if spec_w:
                # the speculative round has launch, sync and emit phases
                # of its own
                got = self._dispatch_spec(spec_w, active, st, tokens)
                if got is None:
                    # OOM preemption round: rebuild the launch state
                    # from the surviving slots and retry the dispatch
                    active = [i for i in active if self.slots[i].active]
                    if not active:
                        return finished_early
                    continue
                finished = finished_early + got
                if finished:
                    self._admit()
                return finished
            break
        if led is not None:
            # blocked window + bucket attribution; cost registration
            # lowers on ShapeDtypeStructs (safe post-donation), once per
            # process under the flag
            name = "serving.decode_burst" if burst else "serving.decode_step"
            _stepledger.end(led, name, _time_mod.perf_counter(),
                            out=(nk, nv, toks))
            _stepledger.register_from_lowered(
                name, fn, args, quant=self._quant_algo,
                quant_bytes_delta=self._quant_bytes_correction()
                * (k_burst if burst else 1))
        self.k_pages, self.v_pages = list(nk), list(nv)
        if self.k_scales is not None:
            self.k_scales, self.v_scales = list(nks), list(nvs)
        # intentional sync: the sampled tokens must reach the host to be
        # appended/streamed — the one wait per burst or step, not a stray
        # transfer. Every read inside it blocks: the tokens, a burst's
        # `emits`, and one `int(v)` for each of the program's counts
        with _trace.phase("serving.decode.sync",
                          fetches=(2 if burst else 1) + len(counts)):
            toks = np.asarray(toks)  # tpu-lint: disable=sync-transfer-in-step-loop
            # the program has ended and its tokens are here; what is left
            # of this phase is the reads that follow
            _trace.mark("serving.fetched")
            if burst:
                emits = np.asarray(emits)  # tpu-lint: disable=sync-transfer-in-step-loop
            # the program is done once its tokens are here: no second wait
            counts = {k: int(v) for k, v in counts.items()}
        finished = finished_early
        with _trace.phase("serving.emit", **counts):
            if burst:
                finished.extend(self._replay_burst(toks, emits, active))
            else:
                for i in active:
                    s = self.slots[i]
                    if not s.active:
                        continue  # abort()ed from an on_token callback
                    s.context_len += 1  # the token just fed is now cached
                    s.tokens.append(int(toks[i]))
                    self._stream(s.request_id, s.tokens[-1])
                    if not s.active:
                        continue  # the callback above aborted THIS request
                    # finish at append time (slots at max_new never
                    # re-enter decode; add_request guarantees context_len
                    # stays <= max_seq_len)
                    eos = self._req_eos(s.request_id)
                    if len(s.tokens) >= s.max_new_tokens or (
                            eos is not None and s.tokens[-1] == eos):
                        finished.append(self._finish(i))
        self._step_metrics(t0, len(active), tok0)
        if finished:
            self._admit()
        return finished

    def _step_metrics(self, t0, n_active, tok0):
        """Per-step telemetry close-out: ZERO registry allocations —
        handle attribute reads + float ops only (the overhead guard test
        pins this)."""
        n_tok = self._m.tokens.value - tok0
        with _trace.phase("serving.close", tokens=int(n_tok)):
            t1 = _time_mod.perf_counter()
            dt = t1 - t0
            ex = None
            if self._traces:
                # decode-step exemplar: one traced rider of this batched
                # step (tracing off => self._traces empty => no alloc, the
                # overhead guard's zero-registry-allocation path)
                for s in self.slots:
                    if s.active and s.trace_id != -1:
                        ex = {"trace_id": f"{s.trace_id:x}"}
                        break
            self._m.step_lat.observe(dt, exemplar=ex)
            self._m.token_lat.observe(dt / n_tok if n_tok > 0 else dt,
                                      exemplar=ex)
            self._m.occupancy.set(n_active / self.max_batch)
            self._m.page_util.set(
                1.0 - len(self._free_pages) / self._n_pages_total)
            _flight.record_event("serving.step", active=n_active,
                                 tokens=n_tok, seconds=round(dt, 6))
            _flight.beat_all()
            # memwatch channel (one flag read when off): KV pool
            # occupancy/fragmentation histograms + an HBM watermark sample
            if _memwatch.enabled():
                self._observe_memory()
            # fleet heartbeat (rank shard liveness; also lazily boots the
            # live HTTP plane — fleet.heartbeat is the ONE ensure_server
            # call site) + SLO window snapshot: flag reads only when
            # FLAGS_telemetry_port/_dir are unset (the off-path alloc
            # guard pins zero allocations per step)
            _fleet.heartbeat()
            _slo.tick()

    def _replay_burst(self, toks, emits, active):
        """Token-by-token host replay of one harvested burst: identical
        semantics to K single steps (stream order, finish rules, abort
        from an on_token callback skips the rest of that request's
        burst). toks/emits: [K, B] numpy."""
        finished = []
        for j in range(toks.shape[0]):
            for i in active:
                s = self.slots[i]
                if not s.active or not emits[j, i]:
                    continue
                s.context_len += 1
                s.tokens.append(int(toks[j, i]))
                self._stream(s.request_id, s.tokens[-1])
                if not s.active:
                    continue  # the callback above aborted THIS request
                eos = self._req_eos(s.request_id)
                if len(s.tokens) >= s.max_new_tokens or (
                        eos is not None and s.tokens[-1] == eos):
                    finished.append(self._finish(i))
        return finished

    def _finish(self, slot_idx) -> FinishedRequest:
        s = self.slots[slot_idx]
        self._release_slot(slot_idx)
        self._m.finished.inc()
        if s.spec_proposed > 0:
            self._m.spec_acceptance.observe(
                s.spec_accepted / s.spec_proposed)
        trace_id = self._finish_trace(s.request_id, tokens=len(s.tokens)) \
            if self._traces else None
        _flight.record_event("serving.finish", rid=s.request_id,
                             tokens=len(s.tokens), trace_id=trace_id)
        rp = self._req_params.pop(s.request_id, None)
        retries = self._retry_counts.pop(s.request_id, None)
        # pop with default: an on_token callback may have abort()ed the
        # request between the decode step and this finish
        prompt = self._prompts.pop(s.request_id, None)
        if _reqlog.enabled() and not self._warming:
            # off = this one flag read, no record; warmup's throwaway
            # requests are not accounted (synthetic, no tenant)
            self._account_finish(
                s, rp, retries, trace_id,
                0 if prompt is None else len(prompt))
        return FinishedRequest(
            request_id=s.request_id,
            prompt_ids=prompt if prompt is not None
            else np.zeros((0,), np.int64),
            output_ids=np.asarray(s.tokens, np.int64),
            trace_id=trace_id)

    def _account_finish(self, s, rp, retries, trace_id, prompt_len,
                        outcome="ok"):
        """ONE accounting emission per finished request
        (FLAGS_requestlog): the ledger record plus the per-tenant
        usage/latency families. Called only by _finish — aborts emit
        nothing (vLLM abort semantics), and a detached request is
        accounted by the engine that finishes it, so a disaggregated
        request yields exactly one record fleet-wide."""
        rp = rp or {}
        now = _time_mod.perf_counter()
        tenant = _reqlog.normalize_tenant(rp.get("tenant"))
        n_out = len(s.tokens)
        ttft = rp.get("ttft_s")
        t0 = rp.get("t_start")
        total = max(0.0, now - t0) if t0 is not None else None
        # inter-token latency: decode time amortized over the tokens
        # that followed the first one
        itl = (max(0.0, (total - ttft) / (n_out - 1))
               if total is not None and ttft is not None and n_out > 1
               else None)
        rec = {
            "rid": int(s.request_id),
            "tenant": tenant,
            "outcome": outcome,
            "prompt_tokens": int(prompt_len),
            "output_tokens": int(n_out),
        }
        if trace_id is not None:
            rec["trace_id"] = f"{trace_id:x}"
        if rp.get("queue_s") is not None:
            rec["queue_s"] = round(rp["queue_s"], 6)
        if ttft is not None:
            rec["ttft_s"] = round(ttft, 6)
        if itl is not None:
            rec["itl_s"] = round(itl, 6)
        if total is not None:
            rec["total_s"] = round(total, 6)
        if rp.get("prefix_hit_ratio") is not None:
            rec["prefix_hit_ratio"] = rp["prefix_hit_ratio"]
        if rp.get("tier_promoted"):
            rec["kv_tier_promoted"] = int(rp["tier_promoted"])
        if s.spec_proposed > 0:
            rec["spec_acceptance"] = round(
                s.spec_accepted / s.spec_proposed, 4)
        if retries:
            rec["retries"] = int(retries)
        recov0 = rp.get("recov0")
        if recov0 is not None and self._recoveries > recov0:
            rec["recoveries_touched"] = int(
                self._recoveries - recov0)
        if rp.get("attached"):
            rec["attached"] = True
        _reqlog.record(rec)
        cells = self._tenant_cells.get(tenant)
        if cells is None:
            m = self._m
            cells = (m.usage_tokens.labels(tenant, "prompt"),
                     m.usage_tokens.labels(tenant, "output"),
                     m.tenant_ttft.labels(tenant),
                     m.tenant_total.labels(tenant))
            self._tenant_cells[tenant] = cells
        if prompt_len:
            cells[0].inc(prompt_len)
        if n_out:
            cells[1].inc(n_out)
        if ttft is not None:
            cells[2].observe(ttft)
        if total is not None:
            cells[3].observe(total)

    def has_work(self) -> bool:
        return bool(self._pending) or any(s.active for s in self.slots)

    # ------------------------------------------------------------------
    # disaggregated prefill/decode: KV handoff between engines
    # ------------------------------------------------------------------
    def admit_pending(self):
        """Run one admission round (batched prefill of everything
        admissible) WITHOUT decoding — the disaggregated prefill pool's
        step: the router prefills here, then detach_request() carries
        the paged KV to a decode-pool engine. Requests routed through
        the chunk/continuation path (prefix-cache hit, or chunked
        prefill on) run their rounds to completion here — a handoff
        needs the full context and its first-token sample."""
        self._check_poisoned()
        self._admit()
        while True:
            pf = [i for i, s in enumerate(self.slots)
                  if s.active and s.prefilling]
            if not pf:
                break
            before = sum(self.slots[i]._pf_chunks_done for i in pf)
            self._prefill_chunk_round(pf)
            after = sum(self.slots[i]._pf_chunks_done
                        for i in pf if self.slots[i].active)
            if after <= before:  # OOM drained/preempted: no progress
                break

    def _no_mixed_layout_handoff(self):
        if self._mixed_layout:
            raise NotImplementedError(
                "KV hand-off packs (k, v) pages of every position: a mixed "
                "layout (a latent page pool, window layers' rings) has no "
                "hand-off format yet (ROADMAP R9 and R10)")

    def detach_request(self, request_id: int) -> "KVHandoff":
        """Extract a prefilled request from this engine: gather its KV
        pages to the host, free the slot, and return a KVHandoff that
        attach_request() on a decode-pool engine accepts. Must be
        called between steps (never while an async pipeline is in
        flight — the pages gathered here must not have bursts pending
        against them). The uncommitted prefill-time sample rides the
        handoff, so the first token is committed exactly once, by the
        attaching engine."""
        self._no_mixed_layout_handoff()
        self._check_poisoned()
        slot_idx = next((i for i, s in enumerate(self.slots)
                         if s.active and s.request_id == request_id),
                        None)
        if slot_idx is None:
            raise KeyError(
                f"request {request_id} is not active on this engine "
                f"(pending requests must be admitted/prefilled first)")
        s = self.slots[slot_idx]
        if s.prefilling:
            raise RuntimeError(
                f"request {request_id} is mid chunked-prefill "
                f"({s._pf_chunks_done}/{s._pf_n_chunks} chunks done, "
                f"{s.context_len}/{len(s._pf_ctx)} context tokens "
                f"written); drive admit_pending()/step() until the "
                f"final chunk completes, then detach (a partial "
                f"context has no first-token sample to hand off)")
        # copy-or-pin: the KV gathers below HOST-COPY every page —
        # including prefix pages shared with the trie or other slots —
        # BEFORE _release_slot decrefs them, so the handoff owns its
        # data outright and shared pages are neither freed twice nor
        # mutated under the copy
        page_idx = self.block_tables[slot_idx, :s.n_pages].copy()
        k = [np.asarray(kp[:, page_idx]) for kp in self.k_pages]
        v = [np.asarray(vp[:, page_idx]) for vp in self.v_pages]
        if self.k_scales is not None:
            ks = [np.asarray(sc[:, page_idx]) for sc in self.k_scales]
            vs = [np.asarray(sc[:, page_idx]) for sc in self.v_scales]
        else:
            ks = vs = None
        rp = dict(self._req_params.get(s.request_id, {}))
        rp.pop("t_enq", None)  # TTFT belongs to the prefill engine's
        # clock only when the first token committed there; the router
        # observes routed TTFT end to end instead
        # capture the trace identity BEFORE _finish_trace pops it: the
        # decode-side attach joins this id, so the handoff is one hop
        # of one distributed timeline, not two unrelated traces
        tr = self._traces.get(s.request_id)
        handoff = KVHandoff(
            prompt_ids=self._prompts.get(
                s.request_id, np.zeros((0,), np.int64)),
            tokens=list(s.tokens),
            context_len=s.context_len,
            max_new_tokens=s.max_new_tokens,
            needs_first_sample=s.needs_first_sample,
            first_token=s._first_token,
            req_params=rp,
            page_size=self.page_size,
            kv_cache_quant=self.kv_cache_quant,
            k=k, v=v, k_scales=ks, v_scales=vs,
            trace_ctx=_trace.inject(tr) if tr is not None else None)
        self._release_slot(slot_idx)
        self._prompts.pop(s.request_id, None)
        self._req_params.pop(s.request_id, None)
        self._retry_counts.pop(s.request_id, None)
        if self._traces:
            self._finish_trace(s.request_id, detached=True)
        _flight.record_event("serving.detach", rid=s.request_id,
                             ctx=s.context_len, pages=len(page_idx))
        return handoff

    def attach_request(self, handoff: "KVHandoff") -> int:
        """Adopt a detached request: allocate a slot + pages, scatter
        the handoff's KV into this engine's pools, and resume decoding
        from its context. Returns the request's NEW id on this engine.
        Must be called between steps. The engines must agree on
        page_size, KV quantization, and model geometry (the page
        shapes are checked)."""
        self._check_poisoned()
        t_attach0 = _time_mod.perf_counter()
        self._no_mixed_layout_handoff()
        if handoff.page_size != self.page_size:
            raise ValueError(
                f"page_size mismatch: handoff {handoff.page_size} vs "
                f"engine {self.page_size}")
        if handoff.kv_cache_quant != self.kv_cache_quant:
            raise ValueError(
                f"kv_cache_quant mismatch: handoff "
                f"{handoff.kv_cache_quant!r} vs engine "
                f"{self.kv_cache_quant!r}")
        if len(handoff.k) != len(self.k_pages) or (
                handoff.k and handoff.k[0].shape[0] !=
                self.k_pages[0].shape[0]) or (
                handoff.k and handoff.k[0].shape[2:] !=
                self.k_pages[0].shape[2:]):
            raise ValueError(
                "model geometry mismatch between the detaching and "
                "attaching engines' KV page pools")
        n_pages = handoff.k[0].shape[1] if handoff.k else 0
        if handoff.context_len + max(
                0, handoff.max_new_tokens - len(handoff.tokens)) \
                > self.max_seq_len:
            raise ValueError(
                f"handoff needs up to "
                f"{handoff.context_len + handoff.max_new_tokens} "
                f"positions; engine max_seq_len={self.max_seq_len}")
        slot_idx = next((i for i, s in enumerate(self.slots)
                         if not s.active), None)
        if slot_idx is None:
            raise RuntimeError("attach_request: no free slot")
        if len(self._free_pages) < n_pages:
            self._reclaim_pages(n_pages - len(self._free_pages))
        if len(self._free_pages) < n_pages:
            raise RuntimeError(
                f"attach_request: needs {n_pages} pages, "
                f"{len(self._free_pages)} free")
        # fresh EXCLUSIVE pages: the handoff's KV scatters into them, so
        # they must not alias trie-cached pages (no trie insert either —
        # the attaching engine never saw the token stream page-aligned)
        dst = np.asarray([self._alloc_page()
                          for _ in range(n_pages)], np.int32)
        dd = jnp.asarray(dst)
        for li in range(len(self.k_pages)):
            self.k_pages[li] = self.k_pages[li].at[:, dd].set(
                jnp.asarray(handoff.k[li], self.k_pages[li].dtype))
            self.v_pages[li] = self.v_pages[li].at[:, dd].set(
                jnp.asarray(handoff.v[li], self.v_pages[li].dtype))
            if self.k_scales is not None:
                self.k_scales[li] = self.k_scales[li].at[:, dd].set(
                    jnp.asarray(handoff.k_scales[li]))
                self.v_scales[li] = self.v_scales[li].at[:, dd].set(
                    jnp.asarray(handoff.v_scales[li]))
        if self._page_sharding is not None:
            self._pin_pages()
        rid = self._next_rid
        self._next_rid += 1
        ids = np.asarray(handoff.prompt_ids).reshape(-1).astype(np.int64)
        self._prompts[rid] = ids
        rp = dict(handoff.req_params)
        rp.setdefault("greedy", True)
        rp.setdefault("temperature", float(self.temperature))
        rp.setdefault("top_k", int(self.top_k))
        rp.setdefault("top_p", float(self.top_p))
        rp.setdefault("eos", self.eos_token_id)
        rp.setdefault("on_token", None)
        # accounting identity: the handoff's tenant wins (one tenant
        # across the disaggregated hop); a handoff that predates the
        # accounting plane falls back to the X-PT-Tenant header parked
        # on this thread, then "default". The timing watermarks restart
        # on THIS engine's clock — perf_counter does not travel between
        # processes — so total_s covers the decode side of the hop.
        tn = rp.get("tenant")
        rp["tenant"] = _reqlog.normalize_tenant(
            tn if tn is not None else _reqlog.pending_tenant())
        rp["t_start"] = _time_mod.perf_counter()
        rp["recov0"] = self._recoveries
        rp["attached"] = True
        self._req_params[rid] = rp
        self.block_tables[slot_idx, :] = 0
        self.block_tables[slot_idx, :n_pages] = dst
        s = self.slots[slot_idx]
        s.request_id = rid
        s.tokens = list(handoff.tokens)
        s.prompt_len = len(ids)
        s.context_len = handoff.context_len
        s.max_new_tokens = handoff.max_new_tokens
        s.n_pages = n_pages
        s.greedy = bool(rp["greedy"])
        s.admit_seq = self._admit_seq
        self._admit_seq += 1
        s.needs_first_sample = handoff.needs_first_sample
        s._first_token = handoff.first_token
        s.spec_proposed = 0
        s.spec_accepted = 0
        s.prefilling = False
        s._pf_ctx = None
        s._pf_chunks_done = 0
        s.active = True
        trace_id = None
        if _trace.enabled():
            # adopt the handoff's trace identity (the prefill engine's
            # detach injected it) — this engine's decode continues the
            # SAME distributed timeline; without one, start_trace falls
            # back to the thread context / local sampling as usual
            ctx = _trace.parse_context(handoff.trace_ctx) \
                if handoff.trace_ctx else None
            tr = _trace.start_trace("serving.request", own_track=True,
                                    parent=ctx, rid=rid, attached=True,
                                    ctx_len=s.context_len)
            if tr.trace_id is not None:
                self._traces[rid] = tr
                trace_id = tr.trace_id
                # the KV scatter + slot re-admission IS this hop's
                # handoff cost — record it with explicit endpoints
                tr.emit("serving.attach", t_attach0,
                        _time_mod.perf_counter(), rid=rid,
                        pages=n_pages)
        _flight.record_event("serving.attach", rid=rid,
                             ctx=s.context_len, pages=n_pages,
                             trace_id=trace_id)
        return rid

    def _async_ok(self) -> bool:
        """Pipelined decode is only entered in the steady pure-decode
        state: no admissible queue (admission reuses slots whose pages an
        in-flight burst may still write), no prefill-time samples pending,
        and at least one row with >1 tokens of budget (single-tail rows
        take the classic single-step program)."""
        if self.async_depth <= 0 or self.decode_burst <= 1 or self._pending:
            return False
        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            return False
        if any(self.slots[i].needs_first_sample or
               self.slots[i].prefilling for i in active):
            return False
        return max(self._rem_of(active).values()) > 1

    def _decode_async(self, max_bursts):
        """Dispatch up to `async_depth` bursts ahead of the harvest point.

        Deliberately NOT instrumented by the step-time ledger: its
        whole point is keeping multiple bursts in flight, and the
        ledger's block_until_ready window would serialize exactly that
        pipeline. Measure decode attribution on the sync paths
        (async_depth=0) — the compiled programs are identical.

        The compiled burst returns its scalar carry (token/lens/active/
        budget/key) as device arrays; each next dispatch consumes them as
        futures, so the chain runs back-to-back on device while the host
        replays older bursts' tokens. Page growth is reserved
        CONSERVATIVELY before each dispatch (host lens lag the device by
        the in-flight count, so reservation covers (inflight+1) bursts);
        any finish/abort during replay releases pages, so the pipeline
        drains before the next dispatch could reallocate them. Returns
        (finished, bursts_dispatched)."""
        from collections import deque

        k = self.decode_burst
        active = [i for i, s in enumerate(self.slots) if s.active]
        st = self._decode_launch_state(active)
        rem_of = st["rem_of"]
        n_bursts = min(int(max_bursts), -(-max(rem_of.values()) // k))
        if n_bursts <= 0:
            return [], 0
        if self._traces:
            t_disp0 = _time_mod.perf_counter()
            for i in active:
                tr = self._traces.get(self.slots[i].request_id)
                if tr is not None and "decode_t0" not in tr.marks:
                    tr.mark("decode_t0", t_disp0)
        params, buffers = self._cached_params()
        fn = self._get_burst_fn(st["all_greedy"], k)
        tokens = np.zeros((self.max_batch,), np.int64)
        for i in active:
            tokens[i] = self.slots[i].tokens[-1]
        # the max context each row can ever reach in this phase — the
        # page-reservation cap (sync step() caps at min(burst, rem) the
        # same way; without it a nearly-done row beside a long-running one
        # would reserve past its budget and overrun its block-table row)
        final_ctx = {i: self.slots[i].context_len + rem_of[i]
                     for i in active}
        self._key, sk = jax.random.split(self._key)
        greedy, temp = jnp.asarray(st["greedy"]), jnp.asarray(st["temp"])
        tk, tp_arr = jnp.asarray(st["tk"]), jnp.asarray(st["tp"])
        eos_arr = jnp.asarray(st["eos"])
        carry = (jnp.asarray(tokens), jnp.asarray(st["lens"]),
                 jnp.asarray(st["act_mask"]), jnp.asarray(st["rem"]),
                 jax.random.key_data(sk))
        pages = (tuple(self.k_pages), tuple(self.v_pages),
                 tuple(self.k_scales or ()), tuple(self.v_scales or ()))
        # recovery sentinel: if a failure inside this pipeline drains and
        # rebuilds the engine (_begin_recovery via _poison_if_donated),
        # the finally below must NOT re-point the rebuilt pools at the
        # stale (deleted) `pages` tuple
        recov0 = self._recoveries
        inflight = deque()
        finished = []
        dispatched = 0
        stop = False

        def _reserve():
            # cover every in-flight burst plus the one about to dispatch,
            # capped at the row's final context
            for i in active:
                s = self.slots[i]
                if not s.active:
                    continue
                steps = min(k * (len(inflight) + 1),
                            final_ctx[i] - s.context_len)
                if steps > 0 and not self._ensure_pages(i, steps):
                    return False
            return True

        # Inside this loop self.k_pages/v_pages still name buffers the
        # compiled call donated (deleted); the finally re-points them at
        # the live `pages` tuple so an exception mid-pipeline (or from an
        # on_token callback) cannot leave the engine holding freed arrays.
        # Callbacks must NOT re-enter the engine (step()/run()/cache
        # reads) during async decode — the live cache is in `pages`, not
        # on the engine, until the drain completes.
        try:
            while (dispatched < n_bursts and not stop) or inflight:
                if dispatched < n_bursts and not stop:
                    with _trace.phase("serving.decode.launch"):
                        reserved = _reserve()
                        if reserved:
                            try:
                                (toks, emits, nk, nv, nks, nvs,
                                 tok_f, ln_f, act_f, rm_f, key_f,
                                 _counts) = fn(
                                    params, buffers, *pages, carry[0],
                                    jnp.asarray(self.block_tables), carry[1],
                                    carry[2], carry[3], eos_arr, carry[4],
                                    greedy, temp, tk, tp_arr)
                            except BaseException as e:
                                # on a post-donation failure `pages` names
                                # deleted buffers and the finally below
                                # re-points the engine at them — poison so
                                # step()/run() fail fast (ADVICE.md round-5);
                                # pre-donation failures keep the engine live.
                                # An OOM still gets its forensic dump here;
                                # the graceful preemption round belongs to
                                # the classic step() the caller falls back
                                # to.
                                if _memwatch.is_oom(e):
                                    path = _memwatch.dump_oom(
                                        "serving_async_decode", exc=e,
                                        extra=self._page_table_report())
                                    _flight.record_event(
                                        "serving.oom", where="async_decode",
                                        dump=path)
                                self._poison_if_donated(
                                    "async burst decode fn raised after "
                                    "donating the KV pages",
                                    pages[0], pages[1])
                                raise
                            pages = (nk, nv, nks, nvs)
                            carry = (tok_f, ln_f, act_f, rm_f, key_f)
                            inflight.append(
                                (toks, emits, _time_mod.perf_counter()))
                            dispatched += 1
                    if not reserved:
                        # page-pool pressure: drain, then let the classic
                        # step() run its preemption policy
                        stop = True
                if inflight and (stop or len(inflight) > self.async_depth
                                 or dispatched >= n_bursts):
                    # step latency measured from the burst's DISPATCH:
                    # np.asarray below blocks on the device result, so
                    # the observation covers compute + pipeline queueing
                    # + replay (bursts overlap, so individual spans do
                    # too — honest per-burst completion latency)
                    toks, emits, t_disp = inflight.popleft()
                    gen0 = self._release_gen
                    tok0 = self._m.tokens.value
                    with _trace.phase("serving.decode.sync"):
                        toks, emits = np.asarray(toks), np.asarray(emits)
                    with _trace.phase("serving.emit"):
                        finished.extend(
                            self._replay_burst(toks, emits, active))
                    self._step_metrics(t_disp, len(active), tok0)
                    if self._release_gen != gen0:
                        # pages were freed (finish OR a callback abort):
                        # the remaining in-flight bursts still write to
                        # them via their stale carry, so drain before any
                        # dispatch could hand those pages to another
                        # request
                        stop = True
        finally:
            if self._recoveries == recov0:
                self.k_pages, self.v_pages = list(pages[0]), list(pages[1])
                if self.k_scales is not None:
                    self.k_scales, self.v_scales = (list(pages[2]),
                                                    list(pages[3]))
        if finished:
            self._admit()
        return finished, dispatched

    def run(self, max_steps=10_000) -> List[FinishedRequest]:
        self._check_poisoned()
        out = []
        steps = 0
        while self.has_work() and steps < max_steps:
            if self._async_ok():
                got, n = self._decode_async(max_steps - steps)
                if n > 0:
                    out.extend(got)
                    steps += n
                    continue
                # nothing could be dispatched (page pressure on entry):
                # fall through to the classic step, which preempts
            out.extend(self.step())
            steps += 1
        return out
