"""Plain reference for the `motif` family (Motif-3-Beta): the forward pass
in float32.

Straightforward `jax.numpy`, matrix products at `Precision.HIGHEST`, no
cache, no batching, no kernels, attention with K and V decompressed from
the latent rows, and no import from the program. It runs one sequence at
a time and layer by layer, each layer's leaves upcast inside its own
small jitted program (the held experts one at a time, each upcast as it
comes), so that it fits on the chip beside the bf16 weights it is handed
(11.3 GB). Attention goes a block of queries at a time, each block against
every key, masked.

The equations (`N(x; g) = x / sqrt(mean(x^2) + eps) * g`, no biases but
PolyNorm's and mHC's; https://huggingface.co/Motif-Technologies/Motif-3-
Beta config.json, `model_type` Motif):

    streams  X [n, C] a token (n = mhc_expansion_rate): the embedding's
             row copied into all n; logits = N(sum_i X_i; g_f) W_head
    mHC      around each sublayer F (attention, FFN), with its own phi
             [nC, 2n + n^2], alpha [3], b [2n + n^2]:
             x~ = vec X / sqrt(mean(vec X^2) + eps);  h = x~ phi
             H_pre = sigmoid(alpha_0 h[:n] + b[:n])
             H_post = 2 sigmoid(alpha_1 h[n:2n] + b[n:2n])
             H_res = Sinkhorn(alpha_2 mat(h[2n:]) + b[2n:]): exp, then
             rows and columns normalised in turn, mhc_sinkhorn_iters times
             X' = clip(H_res X + H_post^T F(H_pre X), +-hidden_clamp)
             F(x) = Attn(N(x; g_in)) or FFN(N(x; g_pre_mlp))
    GDLA     c_q = N(x W_qa; g_qa); q = c_q W_qb -> H heads x (nope | rope)
             [c | k_r] = x W_kva; c = N(c; g_kva)
             [k_nope | v] = c W_kvb -> K kv heads x (nope | v)
             rope(q_rope), rope(k_r): one rope key for every head
             query head h reads kv head h // G (G = H / K); in each group
             the first G - 1 heads are signal, the last noise
             A_h = softmax_j((q_h . k_{h//G,j}) / sqrt(nope + rope)) v,
             j <= i, and i - j < sliding_window in a window layer
             lambda = sigmoid(x W_lambda)  [K (G - 1)]
             o_{g,k} = A_{gG+k} - lambda_{g(G-1)+k} A_{gG+G-1}
             out = (concat o * sigmoid(x W_gate)) W_o
    layers   layer i (published index first_layer + i) is full where
             (first_layer + i) % sliding_window_period == period - 1,
             window otherwise; dense where first_layer + i <
             n_dense_first_layers
    rope     rotate-half (dimension i with i + r/2); a full layer's
             frequencies take DeepSeek-V3's YaRN ramp (factor,
             original_max_position_embeddings, beta_fast, beta_slow of
             rope_scaling; no mscale on cos / sin), a window layer's are
             plain at swa_rope_theta
    FFN      W_down(PolyNorm(x W_gate) * x W_up) for the dense FFN, the
             shared expert and every routed expert, each with its own
             PolyNorm: s (w_0 M(u^3) + w_1 M(u^2) + w_2 M(u)) + clip(b,
             +-c), M(t) = t / sqrt(mean(t^2) + eps_p) over the FFN's width
    experts  s = sigmoid(x W_r); S = the top_k largest;
             w_e = route_scale s_e / (sum_{j in S} s_j + 1e-20)
             y = sum_{e in S & H} w_e E_e(x) + E_shared(x)

`H` is the share this chip holds, experts `first .. first + held - 1`: the
router keeps every output, its picks and its denominator over all of them;
what the absent experts would add is left out, here as in the program.

Every reading the published config leaves open is the configuration
file's `assumed` entry: diff_v2 as a lambda a token and signal head from a
sigmoid, with no per-head norm; the head order; mHC as published (the
config says "modified"); the copy into the streams and their sum before
the head; PolyNorm's scale and clamp placement and its eps; the window
layers' positions; YaRN on the full layers alone; the MTP layer left off.

`mode` selects the arithmetic of every product with a weight matrix (the
projections, mHC's phi, the FFNs, the router, the head), as
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

ATTN = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
        "kv_a_layernorm", "kv_b_proj", "o_proj", "lambda_proj", "gate_proj")
FFN = ("gate_proj", "up_proj", "down_proj")
HC = ("phi", "alpha", "bias")
EXPERTS = ("mlp.experts.w_gate", "mlp.experts.w_up", "mlp.experts.w_down",
           "mlp.experts.act_weight", "mlp.experts.act_bias")
EMBED, NORM, HEAD = ("model.embed_tokens.weight", "model.norm.weight",
                     "lm_head.weight")
# queries of one block of the attention: [heads, block, keys] float32 scores
QUERY_BLOCK = 128
# tokens of one block of the dense FFN
TOKEN_BLOCK = 4096


def static_of(cfg: dict) -> tuple:
    """The sizes the arithmetic needs, hashable: one set of programs per
    configuration."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
            "sliding_window", "experts_top_k", "route_norm", "route_scale",
            "rms_norm_eps", "rope_theta", "swa_rope_theta",
            "mhc_expansion_rate", "mhc_sinkhorn_iters",
            "polynorm_output_scale", "polynorm_bias_clamp", "polynorm_eps",
            "hidden_clamp")
    r = cfg["rope_scaling"]
    held = cfg["num_experts"]
    return tuple((k, cfg[k]) for k in keys) + (
        ("yarn", (r["factor"], r["original_max_position_embeddings"],
                  r["beta_fast"], r["beta_slow"])),
        ("first", cfg.get("ep_rank", 0) * held), ("held", held))


def is_window(cfg: dict, i: int) -> bool:
    period = cfg["sliding_window_period"]
    return (cfg["first_layer"] + i) % period != period - 1


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["first_layer"] + i < cfg["n_dense_first_layers"]


def layer_leaves(cfg: dict, i: int) -> tuple:
    """Leaf names of layer `i`, without the `model.layers.<i>.` prefix."""
    names = ["input_layernorm.weight", "pre_mlp_layernorm.weight"] \
        + [f"self_attn.{n}.weight" for n in ATTN] \
        + [f"{s}_hc.{n}" for s in ("attn", "mlp") for n in HC]
    if is_dense(cfg, i):
        return tuple(names + [f"mlp.{n}.weight" for n in FFN]
                     + ["mlp.act_fn.weight", "mlp.act_fn.bias"])
    return tuple(names + list(EXPERTS) + ["mlp.experts.gate.weight"]
                 + [f"mlp.shared_experts.{n}.weight" for n in FFN]
                 + ["mlp.shared_experts.act_fn.weight",
                    "mlp.shared_experts.act_fn.bias"])


def layer_params(weights: dict, cfg: dict, i: int) -> dict:
    return {k: weights[f"model.layers.{i}.{k}"]
            for k in layer_leaves(cfg, i)}


# ---------------------------------------------------------------------------
# arithmetic, on one sequence: x [s, hidden]
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def frequencies(r, theta, yarn=None):
    """The r / 2 rotation frequencies of a rope of `r` dims; with `yarn`
    (factor, original length, beta_fast, beta_slow) DeepSeek-V3's ramp:
    pair i is interpolated (divided by the factor) by a share that rises
    linearly from 0 at the correction dim of beta_fast rotations to 1 at
    that of beta_slow."""
    freq = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    if yarn is None:
        return freq
    factor, original, fast, slow = yarn

    def correction(rotations):
        return r * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), r - 1)
    if high == low:
        high += 0.001
    share = jnp.clip((jnp.arange(r // 2, dtype=F32) - low) / (high - low),
                     0.0, 1.0)
    return (freq / factor) * share + freq * (1.0 - share)


def rope(x, freq):
    """x [s, heads, r] at positions 0 .. s - 1: dimension i rotates with
    i + r/2 by the angle position x freq[i]."""
    s, _, r = x.shape
    angle = jnp.arange(s, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def poly_norm(u, w, b, st):
    def m(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                 + st["polynorm_eps"])

    clamp = st["polynorm_bias_clamp"]
    return st["polynorm_output_scale"] * (
        w[0] * m(u ** 3) + w[1] * m(u ** 2) + w[2] * m(u)) \
        + jnp.clip(b[0], -clamp, clamp)


def ffn(x, w_gate, w_up, w_down, act_w, act_b, st, mode):
    return linear(poly_norm(linear(x, w_gate, 0.0, mode), act_w, act_b, st)
                  * linear(x, w_up, 0.0, mode), w_down, 0.0, mode)


def over_tokens(fn, x):
    """fn over x [s, .] TOKEN_BLOCK tokens at a time."""
    block, s = TOKEN_BLOCK, x.shape[0]
    if s <= block:
        return fn(x)
    pad = -s % block
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    return jax.lax.map(fn, xp).reshape(s + pad, -1)[:s]


def attend(q, k, v, window=None):
    """q [s, heads, d], k [s, kv heads, d], v [s, kv heads, d_v] -> [s,
    heads, d_v]: causal softmax attention, query head h on kv head h // (h
    / kv), with `window` nothing further back than window - 1 positions;
    QUERY_BLOCK queries at a time."""
    s, h, d = q.shape
    kv = k.shape[1]
    key_pos = jnp.arange(s)[None, :]
    q = q.reshape(s, kv, h // kv, d)

    def block(args):
        qb, q_pos = args
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST) \
            / math.sqrt(d)
        seen = key_pos <= q_pos[:, None]
        if window is not None:
            seen = seen & (q_pos[:, None] - key_pos < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               -1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v, precision=HIGHEST)

    if s <= QUERY_BLOCK:
        return block((q, jnp.arange(s))).reshape(s, h, -1)
    pad = -s % QUERY_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, kv, h // kv, d)
    # a padded query sits past the end and sees every key: finite, unread
    pos = jnp.arange(s + pad).reshape(-1, QUERY_BLOCK)
    out = jax.lax.map(block, (qp, pos))
    return out.reshape(s + pad, h, -1)[:s]


def gdla(x, p, st, window, mode):
    """One layer's attention on x [s, hidden] (normed)."""
    s = x.shape[0]
    h, kvh = st["num_attention_heads"], st["num_key_value_heads"]
    r, rank, vd = st["qk_rope_head_dim"], st["kv_lora_rank"], \
        st["v_head_dim"]
    nope = st["head_dim"] - r
    eps = st["rms_norm_eps"]
    w = lambda n: p[f"self_attn.{n}.weight"]  # noqa: E731
    freq = frequencies(r, st["swa_rope_theta"]) if window \
        else frequencies(r, st["rope_theta"], st["yarn"])
    c_q = rms_norm(linear(x, w("q_a_proj"), 0.0, mode), w("q_a_layernorm"),
                   eps)
    q = linear(c_q, w("q_b_proj"), 0.0, mode).reshape(s, h, nope + r)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], freq)], -1)
    kv = linear(x, w("kv_a_proj_with_mqa"), 0.0, mode)
    c = rms_norm(kv[:, :rank], w("kv_a_layernorm"), eps)
    k_r = rope(kv[:, None, rank:], freq)
    kvb = linear(c, w("kv_b_proj"), 0.0, mode).reshape(s, kvh, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_r, (s, kvh, r))], -1)
    a = attend(q, k, kvb[..., nope:],
               st["sliding_window"] if window else None)
    g = h // kvh
    a = a.reshape(s, kvh, g, vd)
    lam = jax.nn.sigmoid(linear(x, w("lambda_proj"), 0.0, mode)).reshape(
        s, kvh, g - 1, 1)
    o = (a[:, :, :g - 1] - lam * a[:, :, g - 1:]).reshape(s, -1)
    gate = jax.nn.sigmoid(linear(x, w("gate_proj"), 0.0, mode))
    return linear(o * gate, w("o_proj"), 0.0, mode)


def sinkhorn(logits, iters):
    m = jnp.exp(logits - logits.max(axis=(-2, -1), keepdims=True))

    def body(_, m):
        m = m / m.sum(-1, keepdims=True)
        return m / m.sum(-2, keepdims=True)

    return jax.lax.fori_loop(0, iters, body, m)


def hyper(streams, sublayer, phi, alpha, bias, st, mode):
    """streams [s, n, C] around `sublayer(x [s, C]) -> [s, C]`."""
    s, n, c = streams.shape
    flat = streams.reshape(s, n * c)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + st["rms_norm_eps"])
    h = linear(flat, phi, 0.0, mode)
    pre = jax.nn.sigmoid(alpha[0] * h[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * h[:, n:2 * n] + bias[n:2 * n])
    res = sinkhorn((alpha[2] * h[:, 2 * n:] + bias[2 * n:]).reshape(s, n, n),
                   st["mhc_sinkhorn_iters"])
    y = sublayer(jnp.einsum("sn,snc->sc", pre, streams, precision=HIGHEST))
    out = jnp.einsum("snm,smc->snc", res, streams, precision=HIGHEST) \
        + post[:, :, None] * y[:, None, :]
    return jnp.clip(out, -st["hidden_clamp"], st["hidden_clamp"])


def route(x, w_router, st, mode):
    """(picks [s, top_k], weights [s, top_k]) over ALL the router's
    experts."""
    scores = jax.nn.sigmoid(linear(x, w_router, 0.0, mode))
    top, picks = jax.lax.top_k(scores, st["experts_top_k"])
    if st["route_norm"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return picks, top * st["route_scale"]


def routed_share(x, picks, weights, experts, st, mode):
    """sum over the experts `first .. first + held - 1` of w_e E_e(x), for
    the tokens that picked them; one expert at a time, upcast as it comes.
    `experts` = (w_gate, w_up, w_down, act_weight, act_bias) stacks."""
    def add(y, expert):
        e, *leaves = expert
        w_e = jnp.sum(jnp.where(picks == st["first"] + e, weights, 0.0), -1)
        return y + w_e[:, None] * ffn(
            x, *(leaf.astype(F32) for leaf in leaves), st, mode), None

    return jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(experts[0].shape[0]),) + tuple(experts))[0]


def block(streams, p, st, window, mode):
    """One layer on one sequence's streams [s, n, C] -> (streams, picks or
    None)."""
    p = {k: v if k in EXPERTS else v.astype(F32) for k, v in p.items()}
    eps = st["rms_norm_eps"]
    hc = lambda s: [p[f"{s}_hc.{n}"] for n in HC]  # noqa: E731
    streams = hyper(streams, lambda x: gdla(
        rms_norm(x, p["input_layernorm.weight"], eps), p, st, window, mode),
        *hc("attn"), st, mode)
    chosen = []

    def mlp(x):
        m = rms_norm(x, p["pre_mlp_layernorm.weight"], eps)
        if "mlp.experts.gate.weight" not in p:
            return over_tokens(lambda t: ffn(
                t, *(p[f"mlp.{n}.weight"] for n in FFN),
                p["mlp.act_fn.weight"], p["mlp.act_fn.bias"], st, mode), m)
        picks, weights = route(m, p["mlp.experts.gate.weight"], st, mode)
        chosen.append(picks)
        shared = "mlp.shared_experts."
        return routed_share(m, picks, weights, [p[n] for n in EXPERTS], st,
                            mode) \
            + ffn(m, *(p[f"{shared}{n}.weight"] for n in FFN),
                  p[shared + "act_fn.weight"], p[shared + "act_fn.bias"],
                  st, mode)

    streams = hyper(streams, mlp, *hc("mlp"), st, mode)
    return streams, chosen[0] if chosen else None


@functools.lru_cache(maxsize=None)
def _programs(static: tuple, mode: str):
    st = dict(static)
    return {
        "window": jax.jit(lambda x, p: block(x, p, st, True, mode)),
        "full": jax.jit(lambda x, p: block(x, p, st, False, mode)),
        "embed": jax.jit(lambda ids, tok: jnp.repeat(
            tok.astype(F32)[ids][:, None], st["mhc_expansion_rate"], 1)),
        "logits": jax.jit(lambda x, gain, head: linear(
            rms_norm(x.sum(1), gain.astype(F32), st["rms_norm_eps"]),
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
        x, chosen = prog["window" if is_window(cfg, i) else "full"](
            x, layer_params(weights, cfg, i))
        if picks is not None and chosen is not None:
            picks.append(chosen[at])
    return prog["logits"](x[at], weights[NORM], weights[HEAD])
