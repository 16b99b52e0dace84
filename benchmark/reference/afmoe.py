"""Plain reference for the `afmoe` family (Trinity-Mini): the forward pass
in float32.

Straightforward `jax.numpy`, matrix products at `Precision.HIGHEST`, no
cache, no batching, no kernels, and no import from the program. It runs
one sequence at a time and layer by layer, each layer's leaves upcast
inside its own small jitted program, so that it fits on the chip beside
the bf16 weights it is handed (9.97 GB; 19.9 GB in float32). Attention
goes a block of queries at a time (float32 scores of 32 heads x 9,216 x
9,216 would be 10.9 GB), each block against every key, masked.

The equations (`N(x; g) = x / sqrt(mean(x^2) + eps) * g`, no biases;
https://huggingface.co/arcee-ai/Trinity-Mini config.json, `model_type`
afmoe, and its published `modeling_afmoe.py`):

    embed    h0 = E[ids] * sqrt(hidden)            (mup_enabled)
    block    x' = x + N(Attn(N(x; g_in)); g_post_attn)
             y  = x' + N(FFN(N(x'; g_pre_mlp)); g_post_mlp)
    attn     q = x W_q -> heads x d; k = x W_k, v = x W_v -> kv heads x d
             q = N(q; g_qn), k = N(k; g_kn) over the d of each head
             sliding_attention layers only: rope on q and k (theta, all d
             dims, rotate-half: dimension i with i + d/2), and position i
             sees j only if 0 <= i - j < sliding_window
             full_attention layers: causal, no position encoding at all
             query head h reads kv head h // (heads / kv heads)
             scores = q . k / sqrt(d), softmax
             o = (concat_h(P_h v_h) * sigmoid(x W_gate)) W_o
    dense    W_down(silu(x W_gate) * x W_up)
    experts  s = sigmoid(x W_r); S = the top_k largest of s + b (the
             router's expert_bias, for the pick alone);
             w_e = route_scale * s_e / (sum_{j in S} s_j + 1e-20)
             y = sum_{e in S & H} w_e E_e(x) + E_shared(x)

`H` is the share this chip holds, experts `first .. first + held - 1`: the
router keeps every output, its picks and its denominator over all of them;
what the absent experts would add is left out, here as in the program.

`mode` selects the arithmetic of every product with a weight matrix (the
projections, the gate, the FFNs, the router, the head), as
`reference/gpt.py`'s `linear` defines it: "f32" is the reference, "bf16"
the program's own stated precision, "fp8" / "int8" the step below it (the
CONTROL). The attention's own two products stay float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt import F32, HIGHEST, linear

ATTN = ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj", "q_norm",
        "k_norm")
NORMS = ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
         "post_mlp_layernorm")
FFN = ("gate_proj", "up_proj", "down_proj")
ROUTED = ("mlp.experts.gate.weight", "mlp.experts.gate.expert_bias",
          "mlp.experts.w_gate", "mlp.experts.w_up", "mlp.experts.w_down")
EMBED, NORM, HEAD = ("model.embed_tokens.weight", "model.norm.weight",
                     "lm_head.weight")
# queries of one block of the attention: [heads, block, keys] float32 scores
QUERY_BLOCK = 512


def static_of(cfg: dict) -> tuple:
    """The sizes the arithmetic needs, hashable: one set of programs per
    configuration."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "sliding_window", "num_experts_per_tok", "route_scale",
            "route_norm", "rms_norm_eps", "rope_theta", "hidden_size",
            "mup_enabled")
    held = cfg["num_experts"]
    return tuple((k, cfg[k]) for k in keys) + (
        ("first", cfg.get("ep_rank", 0) * held), ("held", held))


def layer_leaves(cfg: dict, i: int) -> tuple:
    """Leaf names of layer `i`, without the `model.layers.<i>.` prefix."""
    names = [f"{n}.weight" for n in NORMS] \
        + [f"self_attn.{n}.weight" for n in ATTN]
    if i < cfg["num_dense_layers"]:
        return tuple(names + [f"mlp.{n}.weight" for n in FFN])
    return tuple(names + list(ROUTED)
                 + [f"mlp.shared_experts.{n}.weight" for n in FFN])


def layer_params(weights: dict, cfg: dict, i: int) -> dict:
    return {k: weights[f"model.layers.{i}.{k}"]
            for k in layer_leaves(cfg, i)}


# ---------------------------------------------------------------------------
# arithmetic, on one sequence: x [s, hidden]
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rope(x, theta):
    """x [s, heads, d] at positions 0 .. s - 1: dimension i rotates with
    i + d/2 by the angle position / theta^(2i/d)."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def gated_ffn(x, w_gate, w_up, w_down, mode):
    return linear(jax.nn.silu(linear(x, w_gate, 0.0, mode))
                  * linear(x, w_up, 0.0, mode), w_down, 0.0, mode)


def attend(q, k, v, window):
    """q [s, heads, d], k / v [s, kv heads, d] -> [s, heads x d]: causal
    softmax attention, and with `window` nothing further back than
    window - 1 positions; QUERY_BLOCK queries at a time."""
    s, h, d = q.shape
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    key_pos = jnp.arange(s)[None, :]

    def block(args):
        qb, q_pos = args            # [block, heads, d], [block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            / math.sqrt(d)
        seen = key_pos <= q_pos[:, None]
        if window is not None:
            seen = seen & (q_pos[:, None] - key_pos < window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    if s <= QUERY_BLOCK:
        return block((q, jnp.arange(s))).reshape(s, h * d)
    pad = -s % QUERY_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, h, d)
    # a padded query sits past the end and sees every key: finite, unread
    pos = jnp.arange(s + pad).reshape(-1, QUERY_BLOCK)
    return jax.lax.map(block, (qp, pos)).reshape(-1, h * d)[:s]


def gated_attention(x, p, st, window, mode):
    s = x.shape[0]
    h, kv, d = (st["num_attention_heads"], st["num_key_value_heads"],
                st["head_dim"])
    w = lambda n: p[f"self_attn.{n}.weight"]  # noqa: E731
    q = rms_norm(linear(x, w("q_proj"), 0.0, mode).reshape(s, h, d),
                 w("q_norm"), st["rms_norm_eps"])
    k = rms_norm(linear(x, w("k_proj"), 0.0, mode).reshape(s, kv, d),
                 w("k_norm"), st["rms_norm_eps"])
    v = linear(x, w("v_proj"), 0.0, mode).reshape(s, kv, d)
    if window is not None:
        q, k = rope(q, st["rope_theta"]), rope(k, st["rope_theta"])
    ctx = attend(q, k, v, window)
    gate = jax.nn.sigmoid(linear(x, w("gate_proj"), 0.0, mode))
    return linear(ctx * gate, w("o_proj"), 0.0, mode)


def route(x, w_router, bias, st, mode):
    """(picks [s, top_k], weights [s, top_k]) over ALL the router's
    experts: the bias moves the pick and nothing else."""
    scores = jax.nn.sigmoid(linear(x, w_router, 0.0, mode))
    _, picks = jax.lax.top_k(scores + bias, st["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, picks, axis=-1)
    if st["route_norm"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return picks, top * st["route_scale"]


def routed_share(x, picks, weights, w_gate, w_up, w_down, first, mode):
    """sum over the experts `first .. first + len(w_gate) - 1` of w_e
    E_e(x), for the tokens that picked them."""
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        y = y + w_e[:, None] * gated_ffn(x, w_gate[e], w_up[e], w_down[e],
                                         mode)
    return y


def block(x, p, st, window, mode):
    """One layer on one sequence -> (y, picks or None)."""
    p = {k: v.astype(F32) for k, v in p.items()}
    eps = st["rms_norm_eps"]
    a = gated_attention(rms_norm(x, p["input_layernorm.weight"], eps), p,
                        st, window, mode)
    x = x + rms_norm(a, p["post_attention_layernorm.weight"], eps)
    m = rms_norm(x, p["pre_mlp_layernorm.weight"], eps)
    picks = None
    if "mlp.experts.gate.weight" in p:
        picks, weights = route(m, p["mlp.experts.gate.weight"],
                               p["mlp.experts.gate.expert_bias"], st, mode)
        m = routed_share(m, picks, weights, p["mlp.experts.w_gate"],
                         p["mlp.experts.w_up"], p["mlp.experts.w_down"],
                         st["first"], mode) \
            + gated_ffn(m, *(p[f"mlp.shared_experts.{n}.weight"]
                             for n in FFN), mode)
    else:
        m = gated_ffn(m, *(p[f"mlp.{n}.weight"] for n in FFN), mode)
    return x + rms_norm(m, p["post_mlp_layernorm.weight"], eps), picks


@functools.lru_cache(maxsize=None)
def _programs(static: tuple, mode: str):
    st = dict(static)
    scale = math.sqrt(st["hidden_size"]) if st["mup_enabled"] else 1.0
    return {
        "window": jax.jit(lambda x, p: block(x, p, st, st["sliding_window"],
                                             mode)),
        "full": jax.jit(lambda x, p: block(x, p, st, None, mode)),
        "embed": jax.jit(lambda ids, tok: tok.astype(F32)[ids] * scale),
        "logits": jax.jit(lambda x, gain, head: linear(
            rms_norm(x, gain.astype(F32), st["rms_norm_eps"]),
            head.astype(F32), 0.0, mode)),
    }


def logits_at(weights: dict, cfg: dict, ids, positions, mode="f32",
              picks=None):
    """Next-token logits [len(positions), vocab] (float32, on the device)
    of the sequence `ids` at `positions`, by one full forward pass.
    `picks`, a list, receives each expert layer's [len(positions), top_k]
    picks at those positions."""
    prog = _programs(static_of(cfg), mode)
    at = jnp.asarray(positions, jnp.int32)
    x = prog["embed"](jnp.asarray(ids, jnp.int32), weights[EMBED])
    for i, kind in enumerate(cfg["layer_types"]):
        x, chosen = prog["window" if kind == "sliding_attention"
                         else "full"](x, layer_params(weights, cfg, i))
        if picks is not None and chosen is not None:
            picks.append(chosen[at])
    return prog["logits"](x[at], weights[NORM], weights[HEAD])
