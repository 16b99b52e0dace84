"""Plain reference for the `mimo_v2` family (MiMo-V2.5): the forward pass
in float32.

Straightforward `jax.numpy`, matrix products at `Precision.HIGHEST`, no
cache, no batching, no kernels, and no import from the program. It runs
one sequence at a time and layer by layer, each layer's leaves upcast
inside its own small jitted program, so that it fits on the chip beside
the bf16 weights it is handed (10.84 GB). Attention goes a block of
queries at a time (float32 scores of 64 heads x 17,408 x 17,408 would be
78 GB), each block against every key, masked; the dense FFN a block of
tokens at a time (17,408 x 16,384 float32 twice over would be 2.3 GB); the
held experts one at a time, each upcast as it comes. Compiled for a
described v5e at 17,408 tokens a layer's program holds at most 3.4 GB of
temporaries beside its arguments (AOT, PR 33).

The equations (`N(x; g) = x / sqrt(mean(x^2) + eps) * g`, no biases;
https://huggingface.co/XiaomiMiMo/MiMo-V2.5 config.json, `model_type`
mimo_v2; `hybrid_layer_pattern[i]` 0 = full, 1 = window;
`moe_layer_freq[i]` 0 = dense FFN, 1 = experts):

    block    x' = x + Attn_i(N(x; g_in));  y = x' + FFN_i(N(x'; g_post))
    attn     q = x W_q -> heads x d_k;  k = x W_k -> kv_i heads x d_k;
             v = value_scale (x W_v) -> kv_i heads x d_v
             kv_i = num_key_value_heads in a full layer,
             swa_num_key_value_heads in a window layer
             rope (rotate-half: dimension i with i + r/2) on the FIRST r =
             int(d_k x partial_rotary_factor) dims of each head of q and
             k, base rope_theta in a full layer and swa_rope_theta in a
             window layer; the other d_k - r dims carry no position
             s_hj = q_h . k_g(h),j / sqrt(d_k),  g(h) = h // (heads / kv_i)
             full: j <= i.  window: 0 <= i - j < sliding_window
             window layers (add_swa_attention_sink_bias): a learned b_h,
             P_hj = exp(s_hj) / (sum_j' exp(s_hj') + exp(b_h)): one more
             column of the softmax, which has no value
             o = concat_h(sum_j P_hj v_g(h),j) W_o
    dense    W_down(silu(x W_gate) * x W_up)
    experts  s = sigmoid(x W_r); S = the top_k largest of s + c (the
             router's e_score_correction_bias, for the pick alone; n_group
             1, topk_group 1: no group limit);
             w_e = s_e / (sum_{j in S} s_j + 1e-20)   (norm_topk_prob;
             routed_scaling_factor null = 1)
             y = sum_{e in S & H} w_e E_e(x);  no shared expert
    head     logits = N(h; g_f) W_head, untied

`H` is the share this chip holds, experts `first .. first + held - 1`: the
router keeps every output, its picks and its denominator over all of them;
what the absent experts would add is left out, here as in the program.

Taken from the published modelling code and not from a config key (the
configuration file lists them under `assumed`): the sink as a column of
the softmax that is dropped after it; the value scale on v, before the
product with P; no norm on q or k; norms before the sublayers only;
`attention_chunk_size` is read by nothing on this path. No equation was
left to a guess: none of them is underdetermined by those statements.

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

ATTN = ("q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight")
SINK = "attention_sink_bias"
NORMS = ("input_layernorm", "pre_mlp_layernorm")
FFN = ("gate_proj", "up_proj", "down_proj")
ROUTED = ("mlp.experts.gate.weight", "mlp.experts.gate.expert_bias",
          "mlp.experts.w_gate", "mlp.experts.w_up", "mlp.experts.w_down")
EXPERTS = ROUTED[2:]
EMBED, NORM, HEAD = ("model.embed_tokens.weight", "model.norm.weight",
                     "lm_head.weight")
# queries of one block of the attention: [heads, block, keys] float32 scores
QUERY_BLOCK = 128
# tokens of one block of the dense FFN
TOKEN_BLOCK = 4096


def kind_static(cfg: dict, window: bool) -> tuple:
    """What one kind of layer's attention is built from, hashable."""
    pre = "swa_" if window else ""
    d_k = cfg[pre + "head_dim"]
    return (("heads", cfg[pre + "num_attention_heads"]),
            ("kv_heads", cfg[pre + "num_key_value_heads"]),
            ("key_dim", d_k), ("value_dim", cfg[pre + "v_head_dim"]),
            ("rope_dims", int(d_k * cfg["partial_rotary_factor"]) // 2 * 2),
            ("rope_theta", cfg["swa_rope_theta" if window
                               else "rope_theta"]),
            ("window", cfg["sliding_window"] if window else None),
            ("sink", bool(cfg["add_swa_attention_sink_bias" if window
                              else "add_full_attention_sink_bias"])))


def static_of(cfg: dict) -> tuple:
    """The sizes the arithmetic needs, hashable: one set of programs per
    configuration."""
    held = cfg["n_routed_experts"]
    scale = cfg["routed_scaling_factor"]
    return (("full", kind_static(cfg, False)),
            ("window", kind_static(cfg, True)),
            ("value_scale", cfg["attention_value_scale"]),
            ("num_experts_per_tok", cfg["num_experts_per_tok"]),
            ("norm_topk_prob", cfg["norm_topk_prob"]),
            ("route_scale", 1.0 if scale is None else scale),
            ("eps", cfg["layernorm_epsilon"]),
            ("first", cfg.get("ep_rank", 0) * held), ("held", held))


def layer_leaves(cfg: dict, i: int) -> tuple:
    """Leaf names of layer `i`, without the `model.layers.<i>.` prefix."""
    window = bool(cfg["hybrid_layer_pattern"][i])
    names = [f"{n}.weight" for n in NORMS] \
        + [f"self_attn.{n}" for n in ATTN]
    if dict(kind_static(cfg, window))["sink"]:
        names.append(f"self_attn.{SINK}")
    if cfg["moe_layer_freq"][i]:
        return tuple(names + list(ROUTED))
    return tuple(names + [f"mlp.{n}.weight" for n in FFN])


def layer_params(weights: dict, cfg: dict, i: int) -> dict:
    return {k: weights[f"model.layers.{i}.{k}"]
            for k in layer_leaves(cfg, i)}


# ---------------------------------------------------------------------------
# arithmetic, on one sequence: x [s, hidden]
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rope(x, theta, r):
    """x [s, heads, d] at positions 0 .. s - 1: of the first `r` dims,
    dimension i rotates with i + r/2 by the angle position / theta^(2i/r);
    the dims from r on are left as they are."""
    s = x.shape[0]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def gated_ffn(x, w_gate, w_up, w_down, mode):
    return linear(jax.nn.silu(linear(x, w_gate, 0.0, mode))
                  * linear(x, w_up, 0.0, mode), w_down, 0.0, mode)


def over_tokens(fn, x):
    """fn over x [s, .] TOKEN_BLOCK tokens at a time."""
    block, s = TOKEN_BLOCK, x.shape[0]
    if s <= block:
        return fn(x)
    pad = -s % block
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    return jax.lax.map(fn, xp).reshape(s + pad, -1)[:s]


def attend(q, k, v, window=None, sink=None):
    """q [s, heads, d_k], k [s, kv heads, d_k], v [s, kv heads, d_v] ->
    [s, heads x d_v]: causal softmax attention, with `window` nothing
    further back than window - 1 positions, with `sink` [heads] one more
    column of the softmax that carries no value; QUERY_BLOCK queries at a
    time."""
    s, h, d = q.shape
    kv, d_v = k.shape[1], v.shape[2]
    key_pos = jnp.arange(s)[None, :]
    # query head n reads kv head n // (h / kv): [s, kv, group, d]
    q = q.reshape(s, kv, h // kv, d)

    def block(args):
        qb, q_pos = args            # [block, kv, group, d_k], [block]
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST) \
            / math.sqrt(d)
        seen = key_pos <= q_pos[:, None]
        if window is not None:
            seen = seen & (q_pos[:, None] - key_pos < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        if sink is not None:
            column = jnp.broadcast_to(
                sink.reshape(kv, h // kv, 1, 1), scores.shape[:3] + (1,))
            scores = jnp.concatenate([scores, column], axis=-1)
        probs = jax.nn.softmax(scores, -1)[..., :s]
        return jnp.einsum("kgqt,tkd->qkgd", probs, v, precision=HIGHEST)

    if s <= QUERY_BLOCK:
        return block((q, jnp.arange(s))).reshape(s, h * d_v)
    pad = -s % QUERY_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, kv, h // kv, d)
    # a padded query sits past the end and sees every key: finite, unread
    pos = jnp.arange(s + pad).reshape(-1, QUERY_BLOCK)
    return jax.lax.map(block, (qp, pos)).reshape(-1, h * d_v)[:s]


def attention(x, p, a, value_scale, mode):
    """One layer's attention on x [s, hidden] (normed); `a` the kind's
    sizes (`kind_static`, as a dict)."""
    s = x.shape[0]
    w = lambda n: p[f"self_attn.{n}"]  # noqa: E731
    q = linear(x, w("q_proj.weight"), 0.0, mode).reshape(
        s, a["heads"], a["key_dim"])
    k = linear(x, w("k_proj.weight"), 0.0, mode).reshape(
        s, a["kv_heads"], a["key_dim"])
    v = value_scale * linear(x, w("v_proj.weight"), 0.0, mode).reshape(
        s, a["kv_heads"], a["value_dim"])
    q = rope(q, a["rope_theta"], a["rope_dims"])
    k = rope(k, a["rope_theta"], a["rope_dims"])
    ctx = attend(q, k, v, a["window"], w(SINK) if a["sink"] else None)
    return linear(ctx, w("o_proj.weight"), 0.0, mode)


def route(x, w_router, bias, st, mode):
    """(picks [s, top_k], weights [s, top_k]) over ALL the router's
    experts: the bias moves the pick and nothing else."""
    scores = jax.nn.sigmoid(linear(x, w_router, 0.0, mode))
    _, picks = jax.lax.top_k(scores + bias, st["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, picks, axis=-1)
    if st["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return picks, top * st["route_scale"]


def routed_share(x, picks, weights, w_gate, w_up, w_down, first, mode):
    """sum over the experts `first .. first + len(w_gate) - 1` of w_e
    E_e(x), for the tokens that picked them; one expert at a time, its
    three matrices upcast as it comes (16 experts of 4,096 x 2,048 in
    float32 at once would be 1.6 GB)."""
    def add(y, expert):
        e, wg, wu, wd = expert
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), -1)
        return y + w_e[:, None] * gated_ffn(
            x, wg.astype(F32), wu.astype(F32), wd.astype(F32), mode), None

    return jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))[0]


def block(x, p, st, kind, mode):
    """One layer of `kind` ("full" / "window") on one sequence -> (y,
    picks or None)."""
    p = {k: v if k in EXPERTS else v.astype(F32) for k, v in p.items()}
    x = x + attention(rms_norm(x, p["input_layernorm.weight"], st["eps"]),
                      p, dict(st[kind]), st["value_scale"], mode)
    m = rms_norm(x, p["pre_mlp_layernorm.weight"], st["eps"])
    picks = None
    if "mlp.experts.gate.weight" in p:
        picks, weights = route(m, p["mlp.experts.gate.weight"],
                               p["mlp.experts.gate.expert_bias"], st, mode)
        m = routed_share(m, picks, weights, p["mlp.experts.w_gate"],
                         p["mlp.experts.w_up"], p["mlp.experts.w_down"],
                         st["first"], mode)
    else:
        m = over_tokens(lambda t: gated_ffn(
            t, *(p[f"mlp.{n}.weight"] for n in FFN), mode), m)
    return x + m, picks


@functools.lru_cache(maxsize=None)
def _programs(static: tuple, mode: str):
    st = dict(static)
    return {
        "window": jax.jit(lambda x, p: block(x, p, st, "window", mode)),
        "full": jax.jit(lambda x, p: block(x, p, st, "full", mode)),
        "embed": jax.jit(lambda ids, tok: tok.astype(F32)[ids]),
        "logits": jax.jit(lambda x, gain, head: linear(
            rms_norm(x, gain.astype(F32), st["eps"]), head.astype(F32),
            0.0, mode)),
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
    for i, window in enumerate(cfg["hybrid_layer_pattern"]):
        x, chosen = prog["window" if window else "full"](
            x, layer_params(weights, cfg, i))
        if picks is not None and chosen is not None:
            picks.append(chosen[at])
    return prog["logits"](x[at], weights[NORM], weights[HEAD])
