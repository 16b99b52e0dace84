"""Autoregressive generation — jitted prefill + while_loop decode.

Reference parity: the reference serves decoders through
fused_multi_transformer_op's incremental decode (SURVEY.md §2.1 "Fused
transformer ops" — "the serving engine") driven by PaddleNLP's
`model.generate(decode_strategy=greedy_search|sampling, top_k, top_p, ...)`.

TPU-native design: the ENTIRE generation — prefill, sampling, cache update,
the token loop — is one compiled XLA program: prefill traces once, the
decode step traces once inside `lax.while_loop` (no per-token dispatch, no
host round-trips; the XLA equivalent of the reference's CUDA-graph decode
capture). Sampling uses explicit jax.random keys.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import tape as _tape
from ..framework import random as _random
from ..observability.tracing import scope
from ..tensor import Tensor, as_array


@scope("head/sample")
def sample_logits(logits, key, decode_strategy="sampling", temperature=1.0,
                  top_k=0, top_p=1.0):
    """Sample next tokens from [b, vocab] logits. Returns (tokens [b] i32,
    logprobs [b] f32)."""
    logits = logits.astype(jnp.float32)
    if decode_strategy == "greedy_search":
        tok = jnp.argmax(logits, axis=-1)
        lp = jax.nn.log_softmax(logits, axis=-1)
        return tok.astype(jnp.int32), jnp.take_along_axis(
            lp, tok[:, None], axis=-1)[:, 0]
    if temperature != 1.0:
        logits = logits / jnp.float32(max(temperature, 1e-6))
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
        logits = jnp.where(logits < kth, jnp.float32(-1e30), logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose prefix (exclusive) mass is < top_p; the argmax
        # is ALWAYS kept (top_p <= 0 would otherwise mask everything and
        # degrade to uniform sampling)
        keep_sorted = ((cum - probs) < jnp.float32(top_p)) | (
            jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1) == 0)
        # threshold = smallest kept logit
        thresh = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.float32(np.inf)),
            axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, jnp.float32(-1e30), logits)
    tok = jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return tok, jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]


@scope("head/sample")
def sample_logits_per_row(logits, key, greedy, temperature, top_k, top_p):
    """Vectorized per-ROW sampling from [b, vocab] logits — each request
    carries its own decode params (the serving engine's per-request
    sampling; reference: PaddleNLP generate kwargs per call).

    greedy: [b] bool — argmax rows; temperature/top_k/top_p: [b] arrays
    (top_k == 0 disables the k filter for that row; top_p == 1.0 disables
    the nucleus filter). Returns (tokens [b] i32, logprobs [b] f32)."""
    logits = logits.astype(jnp.float32)
    lp_plain = jax.nn.log_softmax(logits, axis=-1)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature.astype(jnp.float32), 1e-6)[:, None]
    f = logits / temp
    sorted_desc = jnp.sort(f, axis=-1)[:, ::-1]
    # per-row top-k threshold: the (k-1)-th largest; k==0 -> keep all
    kk = jnp.clip(top_k.astype(jnp.int32), 0, f.shape[-1])
    kth = jnp.take_along_axis(
        sorted_desc, jnp.maximum(kk - 1, 0)[:, None], axis=-1)
    f = jnp.where((kk[:, None] > 0) & (f < kth), jnp.float32(-1e30), f)
    # per-row nucleus on the top-k-FILTERED distribution (the scalar
    # sampler applies its filters sequentially — same semantics here);
    # the argmax is ALWAYS kept so top_p <= 0 means argmax-only
    sorted_f = jnp.sort(f, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = ((cum - probs) < top_p.astype(jnp.float32)[:, None]) | (
        jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1) == 0)
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_f, jnp.float32(np.inf)),
        axis=-1, keepdims=True)
    f = jnp.where((top_p[:, None] < 1.0) & (f < thresh),
                  jnp.float32(-1e30), f)
    sampled_tok = jax.random.categorical(key, f, axis=-1).astype(jnp.int32)

    tok = jnp.where(greedy, greedy_tok, sampled_tok)
    lp_f = jax.nn.log_softmax(f, axis=-1)
    lp = jnp.where(
        greedy,
        jnp.take_along_axis(lp_plain, greedy_tok[:, None], axis=-1)[:, 0],
        jnp.take_along_axis(lp_f, sampled_tok[:, None], axis=-1)[:, 0])
    return tok, lp


def _build_generate_fn(model, batch, prompt_len, total_len, decode_strategy,
                       temperature, top_k, top_p, eos_token_id,
                       pad_token_id):
    """One compiled program: (params, buffers, seed, ids) ->
    (tokens [b, total_len], scores [b])."""
    from ..jit.api import _LayerScope

    n_new = total_len - prompt_len
    eos = eos_token_id

    def pure_gen(params, buffers, seed, ids):
        with _tape.no_grad(), _LayerScope(model, params, buffers):
            caches = model.init_kv_caches(batch, total_len)
            logits, caches = model.forward_cached(Tensor(ids), caches, 0)
            last = as_array(logits)[:, -1, :]
            caches = tuple(tuple(as_array(a) for a in c) for c in caches)
            tokens = jnp.concatenate(
                [ids.astype(jnp.int64),
                 jnp.full((batch, n_new), pad_token_id, dtype=jnp.int64)],
                axis=1)
            key = jax.random.wrap_key_data(seed)
            done = jnp.zeros((batch,), dtype=bool)
            scores = jnp.zeros((batch,), dtype=jnp.float32)
            cur = jnp.asarray(prompt_len, dtype=jnp.int32)

            def cond(state):
                cur, tokens, last, done, scores, key, caches = state
                return jnp.logical_and(cur < total_len,
                                       jnp.logical_not(jnp.all(done)))

            def body(state):
                cur, tokens, last, done, scores, key, caches = state
                key, sk = jax.random.split(key)
                tok, lp = sample_logits(last, sk, decode_strategy,
                                        temperature, top_k, top_p)
                tok = jnp.where(done, jnp.int32(pad_token_id), tok)
                scores = scores + jnp.where(done, 0.0, lp)
                tokens = jax.lax.dynamic_update_slice(
                    tokens, tok[:, None].astype(jnp.int64),
                    (jnp.zeros((), jnp.int32), cur))
                if eos is not None:
                    done = jnp.logical_or(done, tok == eos)

                # nothing left to predict after writing the final slot —
                # skip the last forward entirely
                def advance(operand):
                    tok, caches, cur, last = operand
                    logits2, caches2 = model.forward_cached(
                        Tensor(tok[:, None].astype(ids.dtype)),
                        [tuple(c) for c in caches], cur)
                    return (as_array(logits2)[:, -1, :], tuple(
                        tuple(as_array(a) for a in c) for c in caches2))

                def hold(operand):
                    tok, caches, cur, last = operand
                    return (last, caches)

                last2, caches2 = jax.lax.cond(
                    cur + 1 < total_len, advance, hold,
                    (tok, caches, cur, last))
                return (cur + 1, tokens, last2, done, scores, key, caches2)

            state = (cur, tokens, last, done, scores, key, caches)
            state = jax.lax.while_loop(cond, body, state)
            cur, tokens, last, done, scores, key, caches = state
            return tokens, scores

    return jax.jit(pure_gen)


def generate(model, input_ids, max_length=None, max_new_tokens=None,
             decode_strategy="greedy_search", temperature=1.0, top_k=0,
             top_p=1.0, eos_token_id=None, pad_token_id=0, seed=None):
    """PaddleNLP-style generate. Returns (new_tokens [b, n_new] Tensor,
    scores [b] Tensor). The whole loop is one XLA program, cached per
    (shape, strategy) signature on the model."""
    ids = as_array(input_ids)
    if ids.ndim == 1:
        ids = ids[None, :]
    batch, prompt_len = int(ids.shape[0]), int(ids.shape[1])
    if max_new_tokens is None:
        # PaddleNLP semantics: max_length counts GENERATED tokens
        max_new_tokens = max_length if max_length is not None else 20
    if int(max_new_tokens) < 1:
        raise ValueError(
            f"max_new_tokens/max_length must be >= 1, got {max_new_tokens}")
    total_len = prompt_len + int(max_new_tokens)

    sig = (batch, prompt_len, total_len, decode_strategy, float(temperature),
           int(top_k), float(top_p), eos_token_id, pad_token_id)
    cache = getattr(model, "_generate_cache", None)
    if cache is None:
        cache = model._generate_cache = {}
    fn = cache.get(sig)
    if fn is None:
        fn = cache[sig] = _build_generate_fn(
            model, batch, prompt_len, total_len, decode_strategy,
            temperature, top_k, top_p, eos_token_id, pad_token_id)

    if seed is not None:
        key = jax.random.PRNGKey(seed)
    else:
        key = _random.next_key()
    params = model.parameters_pytree()
    buffers = model.buffers_pytree()
    tokens, scores = fn(params, buffers, jax.random.key_data(key), ids)
    new = tokens[:, prompt_len:]
    return Tensor(new), Tensor(scores)
