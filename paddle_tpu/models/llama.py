"""LLaMA-family causal LM — the flagship model (BASELINE.json configs 3/4:
GPT-3 1.3B TP=4 and LLaMA-2-13B TP×PP×sharding).

Reference parity: the PaddleNLP LLaMA trainer runs on the reference's fused
stack (FusedMultiTransformer / flash_attn / fused_rope / rms_norm — SURVEY.md
§2.1 "Fused transformer ops") over Fleet HybridParallel (mp_layers.py TP,
sequence_parallel_utils SP). This model composes the same pieces from this
framework: VocabParallelEmbedding / ColumnParallelLinear / RowParallelLinear
(GSPMD tp specs), RMSNorm, fused rope, SDPA->flash-attention, with
activations dp/sp-sharded. Degrees of parallelism come from the ambient mesh;
at mesh=None everything runs dense single-chip.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp

from .. import nn
from .causal_lm import CausalLMBase
from ..distributed.fleet.layers.mpu import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.sharding_utils import shard_tensor
from ..nn import functional as F
from ..nn.functional.rope import apply_rope, rope_tables
from ..observability.tracing import scope
from ..tensor import Tensor, _apply_op, as_array


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_recompute: bool = False
    # run the token stream in the zigzag context-parallel layout: the
    # caller permutes inputs+labels ONCE (distributed.zigzag_reorder) and
    # attention uses the balanced zigzag ring with zero per-layer
    # relayout gathers; RoPE follows the original token positions
    cp_zigzag_stream: bool = False
    # compile the decoder stack as ONE lax.scan over weight-stacked layers
    # instead of L unrolled copies: the jitted program shrinks ~L-fold
    # (MaxText-style compile-time scaling; XLA re-traces one homogeneous
    # body). Opt-in: the unrolled form lets XLA specialize per layer and
    # is fine at small L. Ignored by the pipeline path (pp stages stack
    # their layer blocks already) and by pure-eager execution (the
    # autograd tape needs per-op dispatch).
    scan_layers: bool = False
    # fuse the lm_head matmul into a chunked cross entropy: the [tokens,
    # vocab] logits are never materialized (peak memory / chunks), the
    # backward recomputes each chunk (jax.checkpoint). 0 = dense CE.
    fused_ce_chunks: int = 0
    dtype: str = "float32"

    @staticmethod
    def llama2_7b():
        return LlamaConfig(hidden_size=4096, intermediate_size=11008,
                           num_hidden_layers=32, num_attention_heads=32)

    @staticmethod
    def llama2_13b():
        return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                           num_hidden_layers=40, num_attention_heads=40,
                           num_key_value_heads=40)

    @staticmethod
    def gpt3_1p3b():
        return LlamaConfig(vocab_size=50304, hidden_size=2048,
                           intermediate_size=8192, num_hidden_layers=24,
                           num_attention_heads=16,
                           max_position_embeddings=2048)

    @staticmethod
    def tiny(vocab=256, hidden=64, layers=2, heads=4, seq=128):
        return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                           intermediate_size=hidden * 4,
                           num_hidden_layers=layers,
                           num_attention_heads=heads,
                           num_key_value_heads=heads,
                           max_position_embeddings=seq)


class LlamaMLP(nn.Layer):
    """gate/up column-parallel, down row-parallel (megatron split)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, has_bias=False,
            gather_output=False)
        self.up_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, has_bias=False,
            gather_output=False)
        self.down_proj = RowParallelLinear(
            config.intermediate_size, config.hidden_size, has_bias=False,
            input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        if self.num_heads % self.num_kv_heads != 0:
            raise ValueError(
                f"num_attention_heads ({self.num_heads}) must be divisible "
                f"by num_key_value_heads ({self.num_kv_heads})"
            )
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.hidden_size = config.hidden_size
        self.rope_theta = config.rope_theta
        self.cp_zigzag_stream = getattr(config, "cp_zigzag_stream", False)
        self.q_proj = ColumnParallelLinear(
            config.hidden_size, self.num_heads * self.head_dim,
            has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(
            config.hidden_size, self.num_kv_heads * self.head_dim,
            has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(
            config.hidden_size, self.num_kv_heads * self.head_dim,
            has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, config.hidden_size,
            has_bias=False, input_is_parallel=True)

    def forward(self, hidden_states, attn_mask=None, position_offset=0,
                kv_cache=None):
        from ..ops.manipulation import reshape

        b, s = hidden_states.shape[0], hidden_states.shape[1]
        q = reshape(self.q_proj(hidden_states),
                    [b, s, self.num_heads, self.head_dim])
        k = reshape(self.k_proj(hidden_states),
                    [b, s, self.num_kv_heads, self.head_dim])
        v = reshape(self.v_proj(hidden_states),
                    [b, s, self.num_kv_heads, self.head_dim])
        # heads are tp-sharded
        q = shard_tensor(q, "dp", None, "tp", None)
        k = shard_tensor(k, "dp", None, "tp", None)
        v = shard_tensor(v, "dp", None, "tp", None)

        cos, sin = rope_tables(s, self.head_dim, base=self.rope_theta,
                               dtype=as_array(q).dtype,
                               position_offset=position_offset)
        zigzag_live = False
        if self.cp_zigzag_stream:
            # zigzag stream legality, checked ONCE up front: the layout
            # is only expressible on the pure-cp training attention path.
            # Every other path (padding masks, attention inside a pp
            # pipeline stage, dense/paged kv-cache decode) applies
            # contiguous-order RoPE/causal masks that would silently
            # corrupt a permuted stream — raise instead.
            from ..distributed import context_parallel as _cp
            from ..distributed.sharding_utils import in_manual_region

            zigzag_live = _cp.context_parallel_enabled()
            if zigzag_live and (attn_mask is not None or kv_cache is not None
                                or in_manual_region()):
                raise NotImplementedError(
                    "cp_zigzag_stream supports only the pure cp "
                    "attention path (no padding attn_mask, no kv_cache "
                    "decode, no pp pipeline stage); use the contiguous "
                    "layout (cp_zigzag_stream=False) for this config")
            if zigzag_live:
                # rotary phases follow the ORIGINAL token positions of
                # the permuted slots (static gather, fuses)
                zpos = _cp.zigzag_positions(s)
                cos, sin = cos[jnp.asarray(zpos)], sin[jnp.asarray(zpos)]

        def rope_fn(qq, kk):
            return apply_rope(qq, cos, sin), apply_rope(kk, cos, sin)

        q, k = _apply_op(rope_fn, q, k, _name="fused_rope")
        if kv_cache is not None:
            return self._cached_attention(q, k, v, kv_cache,
                                          position_offset, b, s)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            from ..ops.manipulation import repeat_interleave

            k = repeat_interleave(k, rep, axis=2)
            v = repeat_interleave(v, rep, axis=2)
        if attn_mask is not None:
            # fold the causal mask into the user mask (padding masks arrive
            # as [b,1,1,s] bool/additive per the reference convention; the
            # model stays causal either way)
            ma = as_array(attn_mask)
            causal = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None]
            if ma.dtype == jnp.bool_:
                combined = Tensor(jnp.logical_and(
                    jnp.broadcast_to(ma, ma.shape[:2] + (s, s)), causal))
            else:
                neg = jnp.finfo(ma.dtype).min
                combined = Tensor(
                    ma + jnp.where(causal, 0.0, neg).astype(ma.dtype))
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=combined, is_causal=False,
                training=self.training)
        else:
            from ..distributed import context_parallel as _cp
            from ..distributed.sharding_utils import in_manual_region

            if _cp.context_parallel_enabled() and not in_manual_region():
                if zigzag_live:
                    # stream already in zigzag layout: balanced ring, no
                    # per-layer relayout gathers
                    def ring_fn(qq, kk, vv):
                        return _cp.zigzag_stream_attention(qq, kk, vv)
                else:
                    # contiguous stream; FLAGS_cp_ring_balance='zigzag'
                    # opts into per-call relayout balancing (opt-in
                    # until the gather cost is chip-measured)
                    from ..framework import config as _config

                    bal = _config.get_flag("FLAGS_cp_ring_balance", None)

                    def ring_fn(qq, kk, vv):
                        return _cp.ring_attention(qq, kk, vv, causal=True,
                                                  balance=bal)

                out = _apply_op(ring_fn, q, k, v, _name="ring_attention")
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, training=self.training)
        out = reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)

    def forward_paged(self, hidden_states, paged_cache, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None):
        """Decode over a paged KV cache (serving path, SURVEY.md §7
        phase 10). hidden_states: [b, s, hidden] — s == 1 is the classic
        single-token decode step; s > 1 is a speculative-verify WINDOW
        (all s tokens' K/V scatter at positions context_lens..+s-1, each
        position attends its own causal prefix). paged_cache:
        (k_pages, v_pages) [kv_heads, n_pages, page_size, d];
        context_lens[b]: tokens already in the cache for that slot (the
        new tokens land there); active[b]=False rows skip the cache write
        (retired serving slots with stale block tables); limit_lens[b]:
        window positions at/beyond it write nothing (budget overhang).
        Returns (out [b, s, hidden], new_cache)."""
        from ..ops.manipulation import reshape
        from .paged_step import paged_attention_step

        b, s = hidden_states.shape[0], hidden_states.shape[1]
        q = reshape(self.q_proj(hidden_states),
                    [b, s, self.num_heads, self.head_dim])
        k = reshape(self.k_proj(hidden_states),
                    [b, s, self.num_kv_heads, self.head_dim])
        v = reshape(self.v_proj(hidden_states),
                    [b, s, self.num_kv_heads, self.head_dim])
        theta = self.rope_theta
        head_dim = self.head_dim

        def rotate(qq, kk, lens):
            # per-slot rope at positions lens[b]..lens[b]+s-1 (shared
            # tables, rope.py — a [b] offset yields [b, s, d/2] tables)
            cos, sin = rope_tables(qq.shape[1], head_dim, base=theta,
                                   dtype=qq.dtype, position_offset=lens)
            return apply_rope(qq, cos, sin), apply_rope(kk, cos, sin)

        out, new_cache = paged_attention_step(
            q, k, v, paged_cache, block_tables, context_lens,
            active=active, mesh=mesh, kv_heads=self.num_kv_heads,
            rotate=rotate, limit_lens=limit_lens)
        return self.o_proj(out), new_cache

    def _cached_attention(self, q, k, v, kv_cache, cur_len, b, s):
        """Incremental decode/prefill over a dense preallocated KV cache
        (SURVEY.md §7 phase 10; paged-cache serving path lives in
        paddle_tpu.inference). kv_cache: (k_cache, v_cache) arrays of shape
        [b, max_len, num_kv_heads, head_dim]; cur_len (traced ok) tokens are
        already present; the s new tokens land at cur_len..cur_len+s-1."""
        import jax.numpy as _jnp
        from jax import lax as _lax

        from ..ops.manipulation import reshape

        rep = self.num_heads // self.num_kv_heads

        def attend(qq, kk, vv, kc, vc):
            cur = _jnp.asarray(cur_len, dtype=_jnp.int32)
            z = _jnp.zeros((), _jnp.int32)
            with scope("kv_write"):
                kc2 = _lax.dynamic_update_slice(
                    kc, kk.astype(kc.dtype), (z, cur, z, z))
                vc2 = _lax.dynamic_update_slice(
                    vc, vv.astype(vc.dtype), (z, cur, z, z))
            kr, vr = kc2, vc2
            if rep != 1:
                kr = _jnp.repeat(kr, rep, axis=2)
                vr = _jnp.repeat(vr, rep, axis=2)
            scale = 1.0 / math.sqrt(self.head_dim)
            scores = _jnp.einsum(
                "bshd,bThd->bhsT", qq.astype(_jnp.float32),
                kr.astype(_jnp.float32)) * scale
            S = kr.shape[1]
            q_pos = cur + _jnp.arange(s)[:, None]
            k_pos = _jnp.arange(S)[None, :]
            mask = k_pos <= q_pos  # [s, S]
            scores = _jnp.where(mask[None, None], scores,
                                _jnp.float32(-1e30))
            p = _jnp.exp(scores - scores.max(axis=-1, keepdims=True))
            p = p / p.sum(axis=-1, keepdims=True)
            out = _jnp.einsum("bhsT,bThd->bshd", p,
                              vr.astype(_jnp.float32))
            return out.astype(qq.dtype), kc2, vc2

        k_cache, v_cache = kv_cache
        out, new_k, new_v = _apply_op(
            attend, q, k, v, Tensor(as_array(k_cache)),
            Tensor(as_array(v_cache)), _name="cached_attention")
        out = reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(out), (new_k, new_v)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        self.use_recompute = config.use_recompute

    # `attn` and `mlp` each take their norm and their residual add (the
    # same split as GPTDecoderLayer)

    def _inner(self, hidden_states, attn_mask=None):
        with scope("attn"):
            residual = hidden_states
            h = self.input_layernorm(hidden_states)
            h = self.self_attn(h, attn_mask)
            h = residual + h
        return self._mlp_block(h)

    def _mlp_block(self, h):
        with scope("mlp"):
            return h + self.mlp(self.post_attention_layernorm(h))

    def forward(self, hidden_states, attn_mask=None):
        if self.use_recompute and self.training:
            from ..distributed.fleet.utils.recompute import recompute

            return recompute(self._inner, hidden_states, attn_mask)
        return self._inner(hidden_states, attn_mask)

    def forward_cached(self, hidden_states, kv_cache, cur_len):
        """Decode/prefill step writing into a dense KV cache; returns
        (hidden, new_kv_cache)."""
        with scope("attn"):
            residual = hidden_states
            h = self.input_layernorm(hidden_states)
            h, new_cache = self.self_attn(h, position_offset=cur_len,
                                          kv_cache=kv_cache)
            h = residual + h
        return self._mlp_block(h), new_cache

    def forward_paged(self, hidden_states, paged_cache, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None):
        with scope("attn"):
            residual = hidden_states
            h = self.input_layernorm(hidden_states)
            h, new_cache = self.self_attn.forward_paged(
                h, paged_cache, block_tables, context_lens, active=active,
                mesh=mesh, limit_lens=limit_lens)
            h = residual + h
        return self._mlp_block(h), new_cache


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        h = self._embed(input_ids)
        h = shard_tensor(h, "dp", ("sp", "sep"), None)
        if self._use_scan_layers():
            h = self._forward_scan(h, attn_mask)
        else:
            for layer in self.layers:
                h = layer(h, attn_mask)
        return self._final_norm(h)

    def _embed(self, input_ids):
        with scope("embed"):
            return self.embed_tokens(input_ids)

    def _final_norm(self, h):
        with scope("head"):
            return self.norm(h)

    def _use_scan_layers(self):
        from .scan_stack import use_scan_layers
        return use_scan_layers(self.config, self.layers)

    def _forward_scan(self, h, attn_mask=None):
        """ONE lax.scan over the weight-stacked decoder layers — see
        models.scan_stack (shared with the GPT family)."""
        from .scan_stack import forward_scan
        return forward_scan(self.layers, h,
                            call=lambda mod, x: mod(x, attn_mask))

    def forward_cached(self, input_ids, caches, cur_len):
        """caches: list of per-layer (k_cache, v_cache). Returns
        (hidden, new_caches)."""
        h = self._embed(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            h, nc = layer.forward_cached(h, cache, cur_len)
            new_caches.append(nc)
        return self._final_norm(h), new_caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None, max_layers=None):
        """max_layers: run only the first N decoder layers (the
        LayerSkip-style shallow-exit draft path of self-speculative
        decoding) — `paged_caches` then carries N entries and the final
        norm still applies, so the lm head sees a normed early exit."""
        h = self._embed(input_ids)
        layers = self.layers if max_layers is None \
            else list(self.layers)[:max_layers]
        new_caches = []
        for layer, cache in zip(layers, paged_caches):
            h, nc = layer.forward_paged(h, cache, block_tables,
                                        context_lens, active=active,
                                        mesh=mesh, limit_lens=limit_lens)
            new_caches.append(nc)
        return self._final_norm(h), new_caches


class LlamaForCausalLM(CausalLMBase):
    """Causal LM head; `compute_loss(logits-free)` keeps the vocab-parallel
    CE fused with the lm_head matmul under GSPMD."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            # tied head reuses the [vocab, hidden] embedding weight via a
            # transposed matmul (reference: SharedLayerDesc tied embeddings)
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def forward(self, input_ids, attn_mask=None):
        return self._head(self.llama(input_ids, attn_mask))

    def forward_cached(self, input_ids, caches, cur_len):
        h, new_caches = self.llama.forward_cached(input_ids, caches,
                                                  cur_len)
        return self._head(h), new_caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None, max_layers=None):
        h, new_caches = self.llama.forward_paged(
            input_ids, paged_caches, block_tables, context_lens,
            active=active, mesh=mesh, limit_lens=limit_lens,
            max_layers=max_layers)
        return self._head(h), new_caches

    def _backbone_embed_weight(self):
        return self.llama.embed_tokens.weight

    # ------------------------------------------------------------------
    # pipeline decomposition (SURVEY.md §7 phase 8): embed / homogeneous
    # decoder stack / head. The decoder layers are the pipelined stages
    # (stacked, pp-sharded); embed+head run GSPMD on every pp rank (cheap,
    # and it keeps the stages homogeneous — the SPMD-pipelining contract).
    # ------------------------------------------------------------------
    def pp_embed(self, input_ids):
        h = self.llama._embed(input_ids)
        return shard_tensor(h, "dp", ("sp", "sep"), None)

    def pp_layers(self):
        return list(self.llama.layers)

    def pp_head(self, hidden):
        return self._head(self.llama._final_norm(hidden))

