"""Shared causal-LM head/generation contract for the flagship model
families (LLaMA, GPT): tied/untied vocab head, vocab-parallel loss,
dense KV-cache allocation and the generate() entry — one implementation
so the two models cannot drift."""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from .. import nn
from ..observability.tracing import scope


class StatePool(NamedTuple):
    """A pool of a layer that keeps a state of fixed size a sequence (a
    state-space layer's conv tail or scan state) in place of K/V rows:
    `shape` a sequence, `dtype` its own or None for the cache's."""
    shape: tuple
    dtype: str = None

    def zeros(self, batch, default_dtype):
        """`batch` zero states, in `default_dtype` where the pool names
        none."""
        return jnp.zeros((batch,) + tuple(self.shape),
                         self.dtype or default_dtype)


class CausalLMBase(nn.Layer):
    """Subclass contract: set `self.config`, `self.lm_head` (None for a
    tied head), `self.loss_fn`, and implement `_backbone_embed_weight()`
    returning the [vocab, hidden] embedding parameter; expose
    `forward_cached(input_ids, caches, cur_len)`."""

    def _kv_heads(self):
        cfg = self.config
        return getattr(cfg, "num_key_value_heads",
                       cfg.num_attention_heads)

    def kv_cache_layouts(self):
        """(((heads, width), ...), ...): a layer an entry, the pools that
        layer's cache is made of, in the order its cache tuple holds them.
        Keys and values of every kv head in every layer for full attention;
        a model whose layers cache something else (a latent in one pool, K
        and V of a head count or of widths of the layer's own) or keep only
        a window of some layers' positions (`kv_cache_windows`) states it
        by overriding these, and the dense caches below and the serving
        engine's page pools follow, layer by layer. A layer that keeps no
        K/V at all answers `StatePool`s: a state a sequence, whatever its
        length, which the engine keeps a slot apiece."""
        cfg = self.config
        kv = (self._kv_heads(), cfg.hidden_size // cfg.num_attention_heads)
        return ((kv, kv),) * cfg.num_hidden_layers

    def kv_cache_windows(self):
        """How many positions each layer keeps, a layer an entry: None for
        every position, else the window a later step can still see (the
        serving engine gives such a layer a ring of pages a slot and no
        pages from the allocator)."""
        return (None,) * self.config.num_hidden_layers

    def init_kv_caches(self, batch_size, max_length, dtype=None):
        """Dense per-layer caches for incremental decoding: one `[batch,
        max_length, heads, width]` array per pool of the layer's layout
        ((k, v) for full attention), a zero `[batch, *shape]` state per
        `StatePool`."""
        dt = dtype or jnp.float32

        def empty(pool):
            if isinstance(pool, StatePool):
                return pool.zeros(batch_size, dt)
            heads, width = pool
            return jnp.zeros((batch_size, max_length, heads, width), dt)

        return [tuple(empty(pool) for pool in layout)
                for layout in self.kv_cache_layouts()]

    def forward_prefill(self, input_ids, caches, true_lens):
        """A serving prefill: prompts [batch, s] padded past `true_lens`
        [batch] through `forward_cached` from position 0. Returns (the
        next-token logits [batch, vocab] at each prompt's last position, an
        array, and the filled caches). A model whose vocabulary makes the
        logits of every position too large to hold takes the head at those
        positions alone by overriding this."""
        from ..tensor import as_array

        logits, caches = self.forward_cached(input_ids, caches, 0)
        rows = jnp.arange(int(input_ids.shape[0]))
        return as_array(logits)[rows, true_lens - 1, :], caches

    def generate(self, input_ids, max_length=None, max_new_tokens=None,
                 decode_strategy="greedy_search", temperature=1.0,
                 top_k=0, top_p=1.0, eos_token_id=None, pad_token_id=0,
                 seed=None):
        from .generation import generate as _generate

        return _generate(self, input_ids, max_length=max_length,
                         max_new_tokens=max_new_tokens,
                         decode_strategy=decode_strategy,
                         temperature=temperature, top_k=top_k, top_p=top_p,
                         eos_token_id=eos_token_id,
                         pad_token_id=pad_token_id, seed=seed)

    @scope("head")
    def _head(self, h):
        if self.lm_head is None:
            # tied head reuses the [vocab, hidden] embedding weight via a
            # transposed matmul (reference: SharedLayerDesc tied embeddings)
            from ..ops.linalg import matmul

            return matmul(h, self._backbone_embed_weight(),
                          transpose_y=True)
        return self.lm_head(h)

    @scope("head")
    def compute_loss(self, logits, labels):
        from ..ops.reduction import mean

        return mean(self.loss_fn(logits, labels))

    def forward_hidden(self, input_ids, attn_mask=None):
        """Backbone output (final-norm'd hidden states) WITHOUT the vocab
        head — the input to `compute_loss_hidden`'s fused head+CE."""
        return self._backbone()(input_ids, attn_mask)

    def _backbone(self):
        for name in ("llama", "gpt", "model"):
            if hasattr(self, name):
                return getattr(self, name)
        raise NotImplementedError("subclass must expose its backbone")

    @scope("head")
    def compute_loss_hidden(self, hidden, labels, chunks=None):
        """Fused chunked lm-head + cross entropy: the [tokens, vocab]
        logits tensor is NEVER materialized.

        The reference's c_softmax_with_cross_entropy consumes dense
        logits, so its peak memory carries batch*seq*vocab floats (the
        allocation that capped the row-0 bench at batch 32 — f32 logits
        at batch 64 x 1024 x 32k are 8.4 GB). Here the token axis is
        split into `chunks` slices scanned through a `jax.checkpoint`ed
        (head-matmul -> logsumexp -> label-pick) body: peak memory drops
        chunks-fold to one [tokens/chunks, vocab] slice (recomputed for
        the backward), trading ~one extra head matmul per chunk —
        negligible against the 6x backbone flops. The label pick is the
        select-reduce of nn/functional/loss.py:_pick_class, so the same
        code partitions under a tp-sharded vocab (GSPMD inserts the
        max/sum psums exactly as the reference kernel does explicitly).
        """
        import jax

        from ..tensor import _apply_op

        cfg = self.config
        if chunks is None:
            chunks = int(getattr(cfg, "fused_ce_chunks", 0)) or 8
        head_w = self._backbone_embed_weight() if self.lm_head is None \
            else self.lm_head.weight
        tied = self.lm_head is None  # [vocab, hidden] when tied
        ignore_index = getattr(self.loss_fn, "ignore_index", -100)

        def f(h, y, w):
            n = h.shape[0] * h.shape[1]
            hf = h.reshape(n, h.shape[2])
            yf = y.reshape(n)
            c = chunks
            while n % c:  # shapes are static: plain python is fine
                c -= 1
            hc = hf.reshape(c, n // c, -1)
            yc = yf.reshape(c, n // c)

            def body(carry, xs):
                hs, ys = xs
                logits = jax.lax.dot_general(
                    hs, w, (((1,), (1,) if tied else (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                logz = jax.nn.logsumexp(logits, axis=-1)
                valid = ys != ignore_index
                safe = jnp.where(valid, ys, 0)
                # select-reduce, not take_along_axis (SPMD-safe pick)
                classes = jax.lax.broadcasted_iota(
                    jnp.int32, logits.shape, 1)
                picked = jnp.sum(jnp.where(
                    classes == safe[:, None], logits, 0.0), axis=1)
                nll = jnp.where(valid, logz - picked, 0.0)
                return carry + jnp.sum(nll).astype(jnp.float32), None

            total, _ = jax.lax.scan(
                jax.checkpoint(body), jnp.float32(0.0), (hc, yc))
            # parity contract: compute_loss = mean(loss_fn(...)) averages
            # over ALL tokens (ignored rows contribute 0 to the sum but
            # stay in the denominator) — match it exactly
            return total / jnp.float32(n)

        return _apply_op(f, hidden, labels, head_w,
                         _name="fused_lm_head_ce")
