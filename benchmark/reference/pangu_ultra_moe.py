"""Plain reference for the `pangu_ultra_moe` family (openPangu-Ultra-MoE):
the forward pass in float32.

Straightforward `jax.numpy`, matrix products at `Precision.HIGHEST`, no
cache, no batching, no kernels, attention in DECOMPRESSED form (K and V of
every head rebuilt from the latent), and no import from the program. It
runs one sequence at a time and layer by layer, each layer's leaves
upcast inside its own small jitted program, so that it fits on the chip
beside the bf16 weights it is handed (9.84 GB; 19.7 GB in float32).

The equations (`N(x; g) = x / sqrt(mean(x^2) + eps) * g`, no biases;
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B
config.json, `model_type` pangu_ultra_moe, the DeepSeek-V3 layer shapes
with `sandwich_norm`):

    block    x' = x + N(Attn(N(x; g_in)); g_post_attn)
             y  = x' + N(FFN(N(x'; g_pre_mlp)); g_post_mlp)
    latent   c_q = N(x W_qa; g_qa); q = c_q W_qb -> heads x (nope | rope)
             [c_kv | k_r] = x W_kva; c = N(c_kv; g_kva)
             [k_nope | v] = c W_kvb -> heads x (nope | v)
             scores_h = (q_nope,h . k_nope,h + rope(q_rope,h) . rope(k_r))
                        / sqrt(nope + rope), causal, softmax
             o = concat_h(P_h v_h) W_o
    dense    W_down(silu(x W_gate) * x W_up)
    experts  s = sigmoid(x W_r); S = the top_k largest;
             w_e = scale * s_e / (sum_{j in S} s_j + 1e-20)
             y = sum_{e in S & H} w_e E_e(x) + E_shared(x)

`H` is the share this chip holds, experts `first .. first + held - 1`: the
router keeps every output, its picks and its denominator over all of them;
what the absent experts would add is left out, here as in the program.

Departures and assumptions, each also under `assumed` in the
configuration's file: no expert groups and no score-correction bias (the
config has neither key); rope pairs dimension i with i + rope/2
(rotate-half), theta as published, no scaling; the multi-token-prediction
module is left off.

`mode` selects the arithmetic of every product with a weight matrix (the
projections, the FFNs, the router, the head), as `reference/gpt.py`'s
`linear` defines it: "f32" is the reference, "bf16" the program's own
stated precision, "fp8" / "int8" the step below it (the CONTROL). The
attention's own two products stay float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt import F32, HIGHEST, linear

ATTN = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
        "kv_a_layernorm", "kv_b_proj", "o_proj")
FFN = ("gate_proj", "up_proj", "down_proj")
EMBED, NORM, HEAD = ("model.embed_tokens.weight", "model.norm.weight",
                     "lm_head.weight")


def static_of(cfg: dict) -> tuple:
    """The sizes the arithmetic needs, hashable: one set of programs per
    configuration."""
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "sandwich_norm")
    held = cfg["n_routed_experts"]
    return tuple((k, cfg[k]) for k in keys) + (
        ("first", cfg.get("ep_rank", 0) * held), ("held", held))


def layer_leaves(cfg: dict, i: int) -> tuple:
    """Leaf names of layer `i`, without the `model.layers.<i>.` prefix."""
    names = ["input_layernorm.weight", "pre_mlp_layernorm.weight"]
    if cfg["sandwich_norm"]:
        names += ["post_attention_layernorm.weight",
                  "post_mlp_layernorm.weight"]
    names += [f"self_attn.{n}.weight" for n in ATTN]
    if i < cfg["first_k_dense_replace"]:
        return tuple(names + [f"mlp.{n}.weight" for n in FFN])
    return tuple(names + ["mlp.experts.gate.weight", "mlp.experts.w_gate",
                          "mlp.experts.w_up", "mlp.experts.w_down"]
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


def latent_attention(x, p, st, mode):
    s = x.shape[0]
    h, rank = st["num_attention_heads"], st["kv_lora_rank"]
    nope, rp, vd = (st["qk_nope_head_dim"], st["qk_rope_head_dim"],
                    st["v_head_dim"])
    w = lambda n: p[f"self_attn.{n}.weight"]  # noqa: E731
    c_q = rms_norm(linear(x, w("q_a_proj"), 0.0, mode), w("q_a_layernorm"),
                   st["rms_norm_eps"])
    q = linear(c_q, w("q_b_proj"), 0.0, mode).reshape(s, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], st["rope_theta"])
    kv = linear(x, w("kv_a_proj_with_mqa"), 0.0, mode)
    c = rms_norm(kv[:, :rank], w("kv_a_layernorm"), st["rms_norm_eps"])
    k_r = rope(kv[:, None, rank:], st["rope_theta"])[:, 0]
    kvb = linear(c, w("kv_b_proj"), 0.0, mode).reshape(s, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=HIGHEST)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r, precision=HIGHEST)) \
        / math.sqrt(nope + rp)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    return linear(ctx.reshape(s, h * vd), w("o_proj"), 0.0, mode)


def route(x, w_router, st, mode):
    """(picks [s, top_k], weights [s, top_k]) over ALL the router's
    experts."""
    scores = jax.nn.sigmoid(linear(x, w_router, 0.0, mode))
    top, picks = jax.lax.top_k(scores, st["num_experts_per_tok"])
    if st["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return picks, top * st["routed_scaling_factor"]


def routed_share(x, picks, weights, w_gate, w_up, w_down, first, mode):
    """sum over the experts `first .. first + len(w_gate) - 1` of w_e
    E_e(x), for the tokens that picked them."""
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        y = y + w_e[:, None] * gated_ffn(x, w_gate[e], w_up[e], w_down[e],
                                         mode)
    return y


def block(x, p, st, mode):
    """One layer on one sequence -> (y, picks or None)."""
    p = {k: v.astype(F32) for k, v in p.items()}
    eps, sandwich = st["rms_norm_eps"], st["sandwich_norm"]
    a = latent_attention(rms_norm(x, p["input_layernorm.weight"], eps), p,
                         st, mode)
    if sandwich:
        a = rms_norm(a, p["post_attention_layernorm.weight"], eps)
    x = x + a
    m = rms_norm(x, p["pre_mlp_layernorm.weight"], eps)
    picks = None
    if "mlp.experts.gate.weight" in p:
        picks, weights = route(m, p["mlp.experts.gate.weight"], st, mode)
        m = routed_share(m, picks, weights, p["mlp.experts.w_gate"],
                         p["mlp.experts.w_up"], p["mlp.experts.w_down"],
                         st["first"], mode) \
            + gated_ffn(m, *(p[f"mlp.shared_experts.{n}.weight"]
                             for n in FFN), mode)
    else:
        m = gated_ffn(m, *(p[f"mlp.{n}.weight"] for n in FFN), mode)
    if sandwich:
        m = rms_norm(m, p["post_mlp_layernorm.weight"], eps)
    return x + m, picks


@functools.lru_cache(maxsize=None)
def _programs(static: tuple, mode: str):
    st = dict(static)
    return {
        "block": jax.jit(lambda x, p: block(x, p, st, mode)),
        "embed": jax.jit(lambda ids, tok: tok.astype(F32)[ids]),
        "logits": jax.jit(lambda x, gain, head: linear(
            rms_norm(x, gain.astype(F32), st["rms_norm_eps"]),
            head.astype(F32), 0.0, mode)),
    }


def logits_at(weights: dict, cfg: dict, ids, positions, mode="f32",
              picks=None):
    """Next-token logits [len(positions), vocab] (float32, on the device)
    of the sequence `ids` at `positions`, by one full forward pass over the
    held vocabulary rows. `picks`, a list, receives each expert layer's
    [len(positions), top_k] picks at those positions."""
    prog = _programs(static_of(cfg), mode)
    at = jnp.asarray(positions, jnp.int32)
    x = prog["embed"](jnp.asarray(ids, jnp.int32), weights[EMBED])
    for i in range(cfg["num_hidden_layers"]):
        x, chosen = prog["block"](x, layer_params(weights, cfg, i))
        if picks is not None and chosen is not None:
            picks.append(chosen[at])
    return prog["logits"](x[at], weights[NORM], weights[HEAD])
