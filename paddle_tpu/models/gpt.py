"""GPT-2/3-family causal LM (BASELINE.json config 3: GPT-3 1.3B TP=4).

Reference parity: the PaddleNLP GPT trainer over the reference's fused
stack and Fleet HybridParallel. Architecture differences from the LLaMA
flagship, faithful to GPT: LEARNED position embeddings (no rope),
LayerNorm (not RMSNorm), a fused column-parallel QKV projection WITH
bias, a 4x GELU MLP, and a final LayerNorm before the (optionally tied)
head. Shares the same pipeline/serving contracts as LlamaForCausalLM
(pp_embed/pp_layers/pp_head, forward_cached + generate), so
build_train_step and the generation utilities work unchanged.
"""
from __future__ import annotations

from dataclasses import dataclass

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
from ..observability.tracing import scope
from ..tensor import Tensor, as_array


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    intermediate_size: int = 0  # 0 -> 4*hidden (GPT convention)
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    use_recompute: bool = False
    # one lax.scan over weight-stacked layers instead of L unrolled copies
    # (models.scan_stack; same contract as LlamaConfig.scan_layers)
    scan_layers: bool = False
    # chunked fused head+CE (same contract as LlamaConfig.fused_ce_chunks)
    fused_ce_chunks: int = 0
    dtype: str = "float32"

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size

    @staticmethod
    def gpt2_small():
        return GPTConfig(hidden_size=768, num_hidden_layers=12,
                         num_attention_heads=12)

    @staticmethod
    def gpt3_1p3b():
        return GPTConfig(hidden_size=2048, num_hidden_layers=24,
                         num_attention_heads=16,
                         max_position_embeddings=2048)

    @staticmethod
    def tiny(vocab=128, hidden=32, layers=2, heads=2, seq=32):
        return GPTConfig(vocab_size=vocab, hidden_size=hidden,
                         num_hidden_layers=layers,
                         num_attention_heads=heads,
                         max_position_embeddings=seq)


class GPTAttention(nn.Layer):
    """Fused-QKV causal self-attention (reference: the fused_attention /
    FusedMultiHeadAttention configuration GPT trains with)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.qkv_proj = ColumnParallelLinear(
            config.hidden_size, 3 * config.hidden_size, has_bias=True,
            gather_output=False)
        self.out_proj = RowParallelLinear(
            config.hidden_size, config.hidden_size, has_bias=True,
            input_is_parallel=True)

    def _split_qkv(self, qkv, b, s):
        from ..ops.manipulation import reshape

        # [b, s, 3H] -> 3 x [b, s, heads, d] with HEAD-MAJOR columns: head
        # h owns the contiguous column block [3*d*h, 3*d*(h+1)), so tp
        # shards of the fused projection align exactly with the head
        # sharding below — no resharding collective inside the layer.
        # (A [3, heads] ordering would make each tp shard straddle
        # q/k/v blocks and force an all-to-all per layer.)
        qkv = reshape(qkv, [b, s, self.num_heads, 3, self.head_dim])
        q = qkv[:, :, :, 0]
        k = qkv[:, :, :, 1]
        v = qkv[:, :, :, 2]
        q = shard_tensor(q, "dp", None, "tp", None)
        k = shard_tensor(k, "dp", None, "tp", None)
        v = shard_tensor(v, "dp", None, "tp", None)
        return q, k, v

    def forward(self, hidden_states, attn_mask=None):
        from ..ops.manipulation import reshape

        b, s = hidden_states.shape[0], hidden_states.shape[1]
        q, k, v = self._split_qkv(self.qkv_proj(hidden_states), b, s)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=True,
            training=self.training)
        out = reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.out_proj(out)

    def forward_cached(self, hidden_states, kv_cache, cur_len):
        # intentionally parallel to LlamaAttention._cached_attention
        # (llama.py): the llama path additionally handles GQA head repeat
        # and rope'd keys, so the shared core is only the cache write +
        # length mask — kept separate; sync changes across both sites
        from ..ops.manipulation import reshape

        b, s = hidden_states.shape[0], hidden_states.shape[1]
        q, k, v = self._split_qkv(self.qkv_proj(hidden_states), b, s)
        ck, cv = kv_cache

        def upd(c, new):
            import jax

            cl = jnp.asarray(cur_len._data if hasattr(cur_len, "_data")
                             else cur_len, jnp.int32)
            zero = jnp.zeros((), jnp.int32)
            return jax.lax.dynamic_update_slice(
                c, as_array(new).astype(c.dtype), (zero, cl, zero, zero))

        with scope("kv_write"):
            nk, nv = upd(ck, k), upd(cv, v)
        # causal against positions < cur_len + s
        total = nk.shape[1]
        pos_q = cur_len + jnp.arange(s)[:, None]
        pos_k = jnp.arange(total)[None, :]
        mask = Tensor((pos_k <= pos_q)[None, None])
        out = F.scaled_dot_product_attention(
            q, Tensor(nk), Tensor(nv), attn_mask=mask, is_causal=False,
            training=False)
        out = reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.out_proj(out), (nk, nv)

    def forward_paged(self, hidden_states, paged_cache, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None):
        """Decode over a paged KV cache: the GPT serving path
        (reference: fused_multi_transformer GPT configs); s > 1 is the
        speculative-verify window. Positions are learned embeddings
        applied at the model level, so unlike LLaMA there is no
        per-step rotation — the shared `paged_attention_step` runs with
        rotate=None."""
        from .paged_step import paged_attention_step

        b, s = hidden_states.shape[0], hidden_states.shape[1]
        q, k, v = self._split_qkv(self.qkv_proj(hidden_states), b, s)
        out, new_cache = paged_attention_step(
            q, k, v, paged_cache, block_tables, context_lens,
            active=active, mesh=mesh, kv_heads=self.num_heads,
            limit_lens=limit_lens)
        return self.out_proj(out), new_cache


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, has_bias=True,
            gather_output=False)
        self.fc_out = RowParallelLinear(
            config.intermediate_size, config.hidden_size, has_bias=True,
            input_is_parallel=True)

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.use_recompute = config.use_recompute

    # `attn` and `mlp` each take their LayerNorm and their residual add, so
    # that a layer's operations all stand under one of the two names

    def _inner(self, hidden_states, attn_mask=None):
        with scope("attn"):
            h = hidden_states + self.attn(self.ln_1(hidden_states),
                                          attn_mask)
        with scope("mlp"):
            return h + self.mlp(self.ln_2(h))

    def forward(self, hidden_states, attn_mask=None):
        if self.use_recompute and self.training:
            from ..distributed.fleet.utils.recompute import recompute

            return recompute(self._inner, hidden_states, attn_mask)
        return self._inner(hidden_states, attn_mask)

    def forward_cached(self, hidden_states, kv_cache, cur_len):
        with scope("attn"):
            a, new_cache = self.attn.forward_cached(
                self.ln_1(hidden_states), kv_cache, cur_len)
            h = hidden_states + a
        with scope("mlp"):
            return h + self.mlp(self.ln_2(h)), new_cache

    def forward_paged(self, hidden_states, paged_cache, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None):
        with scope("attn"):
            a, new_cache = self.attn.forward_paged(
                self.ln_1(hidden_states), paged_cache, block_tables,
                context_lens, active=active, mesh=mesh,
                limit_lens=limit_lens)
            h = hidden_states + a
        with scope("mlp"):
            return h + self.mlp(self.ln_2(h)), new_cache


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.embed_positions = nn.Embedding(config.max_position_embeddings,
                                            config.hidden_size)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)

    def _embed(self, input_ids, position_offset=0):
        s = input_ids.shape[1]
        max_pos = self.config.max_position_embeddings
        # learned positions end at max_position_embeddings: overflow would
        # silently clamp to the last row (JAX gather semantics), so fail
        # loudly wherever the overflow is statically knowable
        if s > max_pos:
            raise ValueError(
                f"sequence length {s} exceeds max_position_embeddings "
                f"{max_pos}")
        if isinstance(position_offset, int) and position_offset + s > max_pos:
            raise ValueError(
                f"position {position_offset + s} exceeds "
                f"max_position_embeddings {max_pos} (shorten the prompt "
                "or max_new_tokens, or raise max_position_embeddings)")
        # static-size arange + (possibly traced) offset: position_offset is
        # a tracer inside the jitted decode loop
        off = as_array(position_offset) if hasattr(position_offset, "_data") \
            else position_offset
        with scope("embed"):
            pos = Tensor((jnp.arange(s, dtype=jnp.int64) + off)[None])
            h = self.embed_tokens(input_ids) + self.embed_positions(pos)
            return shard_tensor(h, "dp", ("sp", "sep"), None)

    def forward(self, input_ids, attn_mask=None):
        from .scan_stack import forward_scan, use_scan_layers

        h = self._embed(input_ids)
        if use_scan_layers(self.config, self.layers):
            h = forward_scan(self.layers, h,
                             call=lambda mod, x: mod(x, attn_mask))
        else:
            for layer in self.layers:
                h = layer(h, attn_mask)
        return self._final_norm(h)

    def _final_norm(self, h):
        with scope("head"):
            return self.ln_f(h)

    def forward_cached(self, input_ids, caches, cur_len):
        h = self._embed(input_ids, position_offset=cur_len)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            h, nc = layer.forward_cached(h, cache, cur_len)
            new_caches.append(nc)
        return self._final_norm(h), new_caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None, max_layers=None):
        # per-ROW learned positions: slot b's window tokens sit at
        # context_lens[b]..+s-1 (unlike forward_cached's shared scalar
        # offset); max_layers = shallow-exit draft (ln_f still applies)
        s = input_ids.shape[1]
        with scope("embed"):
            pos = Tensor(as_array(context_lens).astype(jnp.int64)[:, None]
                         + jnp.arange(s, dtype=jnp.int64)[None, :])
            h = self.embed_tokens(input_ids) + self.embed_positions(pos)
        layers = self.layers if max_layers is None \
            else list(self.layers)[:max_layers]
        new_caches = []
        for layer, cache in zip(layers, paged_caches):
            h, nc = layer.forward_paged(h, cache, block_tables,
                                        context_lens, active=active,
                                        mesh=mesh, limit_lens=limit_lens)
            new_caches.append(nc)
        return self._final_norm(h), new_caches


class GPTForCausalLM(CausalLMBase):
    """GPT causal LM with the same trainer/serving contracts as the LLaMA
    flagship (pp_embed/pp_layers/pp_head, forward_cached, generate)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def forward(self, input_ids, attn_mask=None):
        return self._head(self.gpt(input_ids, attn_mask))

    def forward_cached(self, input_ids, caches, cur_len):
        h, new_caches = self.gpt.forward_cached(input_ids, caches, cur_len)
        return self._head(h), new_caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, mesh=None,
                      limit_lens=None, max_layers=None):
        h, new_caches = self.gpt.forward_paged(
            input_ids, paged_caches, block_tables, context_lens,
            active=active, mesh=mesh, limit_lens=limit_lens,
            max_layers=max_layers)
        return self._head(h), new_caches

    def _backbone_embed_weight(self):
        return self.gpt.embed_tokens.weight

    # pipeline decomposition: same contract as LlamaForCausalLM
    def pp_embed(self, input_ids):
        return self.gpt._embed(input_ids)

    def pp_layers(self):
        return list(self.gpt.layers)

    def pp_head(self, hidden):
        return self._head(self.gpt._final_norm(hidden))
