"""Plain GPT-2/3 reference: forward, loss, gradients and AdamW in float32.

Straightforward `jax.numpy`, matrix products at `Precision.HIGHEST` (on a
TPU a float32 product otherwise runs as one bf16 pass), no KV cache, no
batching, no kernels, and no import from the program. It follows
arXiv:2005.14165 §2.1 / GPT-2: learned positions, pre-LayerNorm blocks, a
fused QKV projection with bias, causal softmax attention scaled by
1/sqrt(head), a 4x tanh-GELU MLP, a final LayerNorm and a head tied to the
token embedding. One noted layout fact, shared with the program because
the weights are handed to both: the fused QKV columns are HEAD-MAJOR
([head, (q,k,v), head_dim]).

It runs one sequence at a time and layer by layer (each block is its own
small jitted program; the backward pass is a hand-chained `jax.vjp` per
block that recomputes the block's forward), so it fits on the chip beside
nothing but its own float32 weights.

`mode` selects the arithmetic of the Linear layers' products and is how the
CONTROL is computed ("How correct is decided", step 2): "f32" is the
reference; "bf16" rounds both operands to bfloat16 (the step below a
float32 configuration); "int8" and "fp8" quantise both operands (weights
per output column, activations per row; symmetric int8, or float8_e4m3fn
scaled to its range) the way a W8A8 deployment would, with a
straight-through gradient (the step below a bfloat16 configuration).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32

LAYER_LEAVES = (
    "ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight", "attn.qkv_proj.bias",
    "attn.out_proj.weight", "attn.out_proj.bias", "ln_2.weight", "ln_2.bias",
    "mlp.fc_in.weight", "mlp.fc_in.bias", "mlp.fc_out.weight",
    "mlp.fc_out.bias")
EMBED, POS = "gpt.embed_tokens.weight", "gpt.embed_positions.weight"
LNF_W, LNF_B = "gpt.ln_f.weight", "gpt.ln_f.bias"


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _fake_quant(x, axis, mode):
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    if mode == "int8":
        scale = amax / 127.0
        q = jnp.round(x / scale) * scale
    elif mode == "fp8":
        scale = amax / 448.0  # float8_e4m3fn's largest finite value
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    else:
        raise ValueError(f"unknown reference mode {mode!r}")
    # straight-through: the rounding has no gradient of its own
    return x + jax.lax.stop_gradient(q - x)


def linear(x, w, b, mode):
    """x [s, k] @ w [k, n] + b, in the arithmetic `mode` names."""
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=F32) + b
    if mode != "f32":
        x = _fake_quant(x, -1, mode)
        w = _fake_quant(w, 0, mode)
    return jnp.matmul(x, w, precision=HIGHEST) + b


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, heads, eps, mode):
    """One pre-LN transformer block on one sequence, x [s, h] float32; `p`
    holds the block's twelve leaves under LAYER_LEAVES' names."""
    p = {k: v.astype(F32) for k, v in p.items()}
    s, h = x.shape
    d = h // heads
    a = layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps)
    qkv = linear(a, p["attn.qkv_proj.weight"], p["attn.qkv_proj.bias"], mode)
    # [s, (head, q|k|v, d)] -> q, k, v of [head, s, d]: the head leads, so
    # the two products below are plain batched matrix products
    qkv = qkv.reshape(s, heads, 3, d).transpose(2, 1, 0, 3)
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST) \
        / math.sqrt(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hqk,hkd->hqd", probs, v, precision=HIGHEST)
    ctx = ctx.transpose(1, 0, 2)
    x = x + linear(ctx.reshape(s, h), p["attn.out_proj.weight"],
                   p["attn.out_proj.bias"], mode)
    m = layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps)
    m = gelu_tanh(linear(m, p["mlp.fc_in.weight"], p["mlp.fc_in.bias"], mode))
    return x + linear(m, p["mlp.fc_out.weight"], p["mlp.fc_out.bias"], mode)


def embed(ids, tok, pos):
    return tok.astype(F32)[ids] + pos.astype(F32)[:ids.shape[0]]


def head_logits(x, gain, bias, tok, eps):
    """Final LayerNorm and the tied head: x [n, h] -> logits [n, vocab]."""
    y = layer_norm(x, gain.astype(F32), bias.astype(F32), eps)
    return jnp.matmul(y, tok.astype(F32).T, precision=HIGHEST)


def head_loss(x, gain, bias, tok, labels, eps):
    """Mean cross entropy of one sequence's next-token logits."""
    logits = head_logits(x, gain, bias, tok, eps)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


# ---------------------------------------------------------------------------
# jitted pieces (one program per piece and shape, reused by every layer)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(heads: int, eps: float, mode: str):
    fwd = jax.jit(lambda x, p: block(x, p, heads, eps, mode))

    def bwd(x, p, dy, acc, scale):
        """(dL/dx, acc + scale * dL/dp) of one block, recomputing its
        forward. `acc` is donated: the running gradient is updated in
        place."""
        _, vjp = jax.vjp(lambda x_, p_: block(x_, p_, heads, eps, mode), x, p)
        dx, dp = vjp(dy)
        return dx, {k: acc[k] + scale * dp[k].astype(F32) for k in acc}

    def head_bwd(x, gain, bias, tok, labels):
        return jax.value_and_grad(head_loss, argnums=(0, 1, 2, 3))(
            x, gain, bias, tok, labels, eps)

    return {
        "fwd": fwd,
        "bwd": jax.jit(bwd, donate_argnums=(3,)),
        "embed": jax.jit(embed),
        "logits": jax.jit(lambda x, g, b, t: head_logits(x, g, b, t, eps)),
        "head_bwd": jax.jit(head_bwd),
    }


def layer_params(weights: dict, i: int) -> dict:
    return {k: weights[f"gpt.layers.{i}.{k}"] for k in LAYER_LEAVES}


# ---------------------------------------------------------------------------
# serving: logits of chosen positions of one sequence
# ---------------------------------------------------------------------------


def logits_at(weights: dict, cfg: dict, ids, positions, mode="f32"):
    """Next-token logits [len(positions), vocab] (float32, on the device)
    of the sequence `ids` at `positions`, by one full forward pass."""
    prog = _programs(cfg["num_attention_heads"],
                     float(cfg["layer_norm_epsilon"]), mode)
    x = prog["embed"](jnp.asarray(ids, jnp.int32), weights[EMBED],
                      weights[POS])
    for i in range(cfg["num_hidden_layers"]):
        x = prog["fwd"](x, layer_params(weights, i))
    return prog["logits"](x[jnp.asarray(positions, jnp.int32)],
                          weights[LNF_W], weights[LNF_B], weights[EMBED])


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW
# ---------------------------------------------------------------------------


def loss_and_grads(params: dict, cfg: dict, inputs, labels, mode="f32",
                   rows=None):
    """Mean next-token cross entropy over the rows of one batch and its
    gradient for every leaf of `params` (float32 master weights).
    `inputs`, `labels`: [rows, seq] host integers. `rows` restricts the
    batch to those rows, the mean taken over them alone (None: all) — the
    "half of the batch left out" fault is planted with it."""
    prog = _programs(cfg["num_attention_heads"],
                     float(cfg["layer_norm_epsilon"]), mode)
    layers = cfg["num_hidden_layers"]
    rows = list(range(len(inputs))) if rows is None else list(rows)
    scale = jnp.asarray(1.0 / len(rows), F32)
    grads = {k: jnp.zeros(v.shape, F32) for k, v in params.items()}
    total = 0.0
    for r in rows:
        ids = jnp.asarray(np.asarray(inputs[r]), jnp.int32)
        lab = jnp.asarray(np.asarray(labels[r]), jnp.int32)
        xs = [prog["embed"](ids, params[EMBED], params[POS])]
        for i in range(layers):
            xs.append(prog["fwd"](xs[-1], layer_params(params, i)))
        loss, (dx, d_gain, d_bias, d_tok) = prog["head_bwd"](
            xs.pop(), params[LNF_W], params[LNF_B], params[EMBED], lab)
        total += float(loss) / len(rows)
        grads[LNF_W] += scale * d_gain
        grads[LNF_B] += scale * d_bias
        grads[EMBED] += scale * d_tok
        for i in reversed(range(layers)):
            acc = {k: grads.pop(f"gpt.layers.{i}.{k}") for k in LAYER_LEAVES}
            dx, acc = prog["bwd"](xs.pop(), layer_params(params, i), dx, acc,
                                  scale)
            grads.update({f"gpt.layers.{i}.{k}": v for k, v in acc.items()})
        grads[EMBED] = grads[EMBED].at[ids].add(scale * dx)
        grads[POS] = grads[POS].at[:ids.shape[0]].add(scale * dx)
    return total, grads


@functools.partial(jax.jit, donate_argnums=(0, 2, 3),
                   static_argnames=("lr", "beta1", "beta2", "eps", "decay"))
def _adamw_leaf(p, g, m, v, step, *, lr, beta1, beta2, eps, decay):
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    m_hat = m / (1 - beta1 ** step)
    v_hat = v / (1 - beta2 ** step)
    p = p * (1 - lr * decay) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return p, m, v


def adamw_step(params, grads, state, hyper):
    """AdamW with decoupled weight decay (Loshchilov & Hutter), every leaf
    decayed, bias-corrected moments: updates `params` and `state` in place
    (their buffers are donated) and returns them."""
    state["step"] += 1
    step = jnp.asarray(state["step"], F32)
    for k in params:
        params[k], state["m"][k], state["v"][k] = _adamw_leaf(
            params[k], grads[k], state["m"][k], state["v"][k], step,
            lr=hyper["learning_rate"], beta1=hyper["beta1"],
            beta2=hyper["beta2"], eps=hyper["epsilon"],
            decay=hyper["weight_decay"])
    return params, state


def adamw_init(params):
    return {"step": 0,
            "m": {k: jnp.zeros(v.shape, F32) for k, v in params.items()},
            "v": {k: jnp.zeros(v.shape, F32) for k, v in params.items()}}


def leaf_norms(tree: dict) -> dict:
    """name -> L2 norm of the leaf, as host floats."""
    norms = _leaf_norms(tree)
    return {k: float(v) for k, v in norms.items()}


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}
