"""Pluggable scheduling policy for the serving engine.

``ServingEngine`` owns the *mechanism* of continuous batching — paged
KV, compiled prefill/decode programs, recompute preemption, recovery —
while the six *decisions* that shape latency and throughput live here
behind ``SchedulerPolicy``:

  1. admission order   — which pending request enters a free slot next
  2. preemption victim — which active slot to evict on page exhaustion
                         or a decode RESOURCE_EXHAUSTED
  3. prefill packing   — the (batch, token) bucket a group of admitted
                         prompts compiles/pads into
  4. burst sizing      — the scan length of this decode dispatch
  5. chunk budgeting   — the token width of this step's chunked-prefill
                         continuation round (FLAGS_prefill_chunk)
  6. promotion budget  — how many spilled prefix chunks one admission
                         may pull back from the host/disk KV tiers

``FifoSchedulerPolicy`` (the default, FLAGS_scheduler_policy="fifo")
reproduces the pre-extraction engine bit-identically: strict
head-of-line FIFO admission, youngest-admitted victim (vLLM's
recompute policy), next-pow2 batch buckets with page-multiple token
buckets, and {1, decode_burst} burst bucketing. The golden-trace test
(tests/test_scheduler_policy.py) pins this equivalence against token
streams captured from the engine before the extraction.

``SloAwareSchedulerPolicy`` trades strict fairness for tail latency:
while the fast TTFT burn-rate alert fires it admits the shortest
pending prompt first (head-of-line blocking is exactly what burns the
TTFT budget), and it preempts the slot with the MOST remaining budget
(evicting a nearly-finished request throws away latency already
spent; evicting the one with the most work left wastes the smallest
completed fraction).

Policies observe the engine read-only through the hook arguments; all
mutation (page pops, slot writes, requeues) stays in the engine.
"""
from __future__ import annotations

import time as _time_mod
from typing import Dict, List, Optional, Sequence, Tuple

from ..framework import config as _cfg

# An engine whose sequences span at most this many pages has a prefill
# program a (batch bucket, page multiple): at most PAGE_BUCKETS_MAX x
# (log2(max_batch) + 1) of them. Beyond it the count of programs would grow
# with max_seq_len / page_size (36 x 4 for 9,216 tokens at pages of 256 and
# 8 slots, each the whole unrolled depth to compile), and a batch padded to
# its longest prompt would mostly multiply padding where prompts differ by
# that many pages: such an engine pads a prompt to the next power of two of
# its pages and prefills a prompt a round, log2(pages) + 2 programs at most.
PAGE_BUCKETS_MAX = 16


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class SchedulerPolicy:
    """Base policy: the six decision hooks, default = FIFO engine
    behavior. Subclass and override; register with
    ``register_policy``. Hooks must not mutate the engine."""

    name = "base"

    # -- admission ----------------------------------------------------
    def select_admission(self, engine) -> Optional[int]:
        """Index into ``engine._pending`` of the next request to admit
        into a free slot, or None to END this admission round (the
        engine stops looking — returning None with admissible work
        behind a too-big head request is head-of-line blocking, which
        is the FIFO contract). Only called when a free slot exists.
        The engine re-checks the page fit before committing."""
        entry = engine._pending[0]
        return 0 if self._fits(engine, entry) else None

    @staticmethod
    def _fits(engine, entry) -> bool:
        """Admission takes only the context's pages (on-demand growth
        covers decode) — same arithmetic as the engine's commit path.
        Counts prefix-cache evictable pages as available (the engine
        reclaims them at commit); falls back to the raw free list for
        engines without the accounting (test doubles)."""
        _rid, ids, _max_new, prior = entry
        ctx_len = len(ids) + len(prior)
        need = -(-ctx_len // engine.page_size)
        avail = engine._avail_pages() \
            if hasattr(engine, "_avail_pages") \
            else len(engine._free_pages)
        return avail >= need

    # -- preemption ---------------------------------------------------
    def select_victim(self, engine, candidates: Sequence[int],
                      where: str) -> int:
        """Slot index (from ``candidates``, never empty) to evict.
        where="page_stall": the pool ran dry growing this step's
        allocations; where="decode_oom": a compiled decode call raised
        RESOURCE_EXHAUSTED. Default: youngest admitted (max admit_seq)
        — the recompute policy; the oldest slots always progress."""
        return max(candidates, key=lambda i: engine.slots[i].admit_seq)

    # -- prefill packing ----------------------------------------------
    def prefill_bucket(self, engine,
                       new: Sequence[Tuple[int, Sequence[int]]]
                       ) -> Tuple[int, int]:
        """(batch_bucket, token_bucket) for one batched prefill of
        ``new`` = [(slot_idx, context_ids), ...]. One compiled program
        exists per bucket pair, so the policy trades padding FLOPs
        against compile-cache pressure. ``batch_bucket`` may be less than
        ``len(new)``: the engine then prefills the first ``batch_bucket``
        of ``new`` in this round and asks again for the rest. Default, for
        an engine whose sequences span at most PAGE_BUCKETS_MAX pages:
        batch to the next power of two capped at max_batch, tokens to the
        next page multiple of the longest prompt. For a longer engine: a
        prompt a round, its tokens to the next power of two of its pages,
        capped at max_seq_len."""
        page = engine.page_size
        if engine.max_seq_len // page <= PAGE_BUCKETS_MAX:
            nb = min(_pow2_at_least(len(new)), engine.max_batch)
            longest = max(len(ids) for _si, ids in new)
            return nb, -(-longest // page) * page
        pages = _pow2_at_least(-(-len(new[0][1]) // page))
        return 1, min(pages * page, engine.max_seq_len)

    # -- burst sizing -------------------------------------------------
    def burst_k(self, engine, active: Sequence[int],
                rem_of: Dict[int, int]) -> int:
        """Decode-scan length for this dispatch. Must return a value
        the engine has a program for — the default buckets to
        {1, decode_burst}: the full burst while any row has > 1 token
        of budget, the single-step program when every row is on its
        last token (a per-tail-length K would compile a program per
        distinct remaining budget)."""
        if engine.decode_burst > 1 and max(rem_of.values()) > 1:
            return engine.decode_burst
        return 1

    # -- chunk budgeting ----------------------------------------------
    def prefill_chunk_budget(self, engine,
                             prefilling: Sequence[int]) -> int:
        """Token width of this step's chunked-prefill continuation
        round (``prefilling`` = the slot indices mid-prefill). The
        engine page-aligns and clamps the return to
        [page_size, engine.prefill_chunk]; one compiled program exists
        per distinct width, so a policy varying it trades suffix
        latency against compile-cache pressure. Default: the
        configured budget."""
        return engine.prefill_chunk

    # -- tier promotion budgeting -------------------------------------
    def promotion_budget(self, engine, n_candidates: int) -> int:
        """How many spilled prefix chunks (pages) this admission may
        promote from the host/disk KV tiers back into HBM
        (``n_candidates`` = the contiguous spilled run extending the
        resident match). Promotion competes with live decode for free
        pages and host bandwidth; a policy may cap it to keep admission
        latency bounded. Default: take everything the tiers hold — a
        promoted page is a page admission does not have to prefill."""
        return n_candidates


class FifoSchedulerPolicy(SchedulerPolicy):
    """The default: inherits every base hook unchanged. Exists as a
    named registry entry so configs can say what they mean."""

    name = "fifo"


class SloAwareSchedulerPolicy(SchedulerPolicy):
    """TTFT-burn-aware variant (FLAGS_scheduler_policy="slo").

    Admission: while the fast TTFT burn alert fires, pick the
    shortest *admissible* pending prompt (SJF) instead of blocking on
    the head — shortest-first is the queue-wait-minimizing order when
    the budget is already burning. Otherwise plain FIFO.

    Victim: the active slot with the most remaining token budget
    (ties broken youngest), bounding the wasted completed fraction.

    ``firing_fn`` is injectable for tests; the default reads the
    process SLO engine with a small TTL so the hot admission path
    doesn't re-evaluate burn windows every step.
    """

    name = "slo"
    _TTL_S = 0.5

    def __init__(self, firing_fn=None, clock=None):
        from ..observability import slo as _slo

        self._firing_fn = firing_fn or _slo.firing
        self._clock = clock or _time_mod.monotonic
        self._cached: Tuple[float, bool] = (-1e18, False)

    def _ttft_burning(self) -> bool:
        now = self._clock()
        t, val = self._cached
        if now - t < self._TTL_S:
            return val
        try:
            val = any(name.startswith("ttft") for name in self._firing_fn())
        except Exception:
            val = False  # a broken SLO plane must not stop admission
        self._cached = (now, val)
        return val

    def select_admission(self, engine) -> Optional[int]:
        if not self._ttft_burning():
            return super().select_admission(engine)
        best = None
        best_len = None
        for idx, entry in enumerate(engine._pending):
            if not self._fits(engine, entry):
                continue
            _rid, ids, _mn, prior = entry
            ctx_len = len(ids) + len(prior)
            if best is None or ctx_len < best_len:
                best, best_len = idx, ctx_len
        return best

    def select_victim(self, engine, candidates: Sequence[int],
                      where: str) -> int:
        def _key(i):
            s = engine.slots[i]
            rem = s.max_new_tokens - len(s.tokens)
            return (rem, s.admit_seq)

        return max(candidates, key=_key)

    def prefill_chunk_budget(self, engine,
                             prefilling: Sequence[int]) -> int:
        """Halve the chunk width (floor one page) while the TTFT burn
        alert fires: smaller chunks yield the interleaved decode rounds
        more often, trading suffix-prefill latency for the in-flight
        requests' ITL exactly when the latency budget is burning."""
        if self._ttft_burning():
            return max(engine.page_size, engine.prefill_chunk // 2)
        return engine.prefill_chunk

    def promotion_budget(self, engine, n_candidates: int) -> int:
        """Halve the promotion pull (floor one chunk) while the TTFT
        burn alert fires: promotion's host->HBM scatter sits on the
        admission path, and under burn a partially promoted prefix
        (remainder prefilled) beats a stalled admission queue."""
        if self._ttft_burning():
            return max(1, n_candidates // 2)
        return n_candidates


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_POLICIES: Dict[str, type] = {}


def register_policy(cls) -> type:
    """Register a SchedulerPolicy subclass under its ``name``."""
    _POLICIES[cls.name] = cls
    return cls


register_policy(FifoSchedulerPolicy)
register_policy(SloAwareSchedulerPolicy)


def available_policies() -> List[str]:
    return sorted(_POLICIES)


def resolve_policy(policy=None) -> SchedulerPolicy:
    """The engine's constructor-time resolution: an instance passes
    through, a name looks up the registry, None reads
    FLAGS_scheduler_policy."""
    if isinstance(policy, SchedulerPolicy):
        return policy
    name = policy if policy is not None else \
        _cfg.get_flag("FLAGS_scheduler_policy", "fifo")
    cls = _POLICIES.get(name)
    if cls is None:
        raise ValueError(
            f"unknown scheduler policy {name!r}; available: "
            f"{available_policies()}")
    return cls()
