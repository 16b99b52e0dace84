"""Plain reference for the `jamba` family (AI21-Jamba2-3B): the forward pass
in float32.

Straightforward `jax.numpy`, matrix products at `Precision.HIGHEST`, no
cache, no batching, no kernels, and no import from the program. It runs
one sequence at a time and layer by layer, each layer's leaves upcast
inside its own small jitted program, so that it fits on the chip beside the
bf16 weights it is handed (6.06 GB). The scan is sequential, a token a step
(`lax.scan`, unrolled `SCAN_UNROLL` steps at a time) over a [d_inner,
d_state] float32 state that starts at zero; attention goes a block of
queries at a time and the dense FFN a block of tokens at a time
(`reference/mimo_v2.py`'s own blocks). At the cell's longest request
(17,408 positions) one forward is 26 scans of 17,408 steps and 28 layers'
products at the highest precision; the check of a run (seven requests,
each read in float32 and in bf16 arithmetic) took 32-37 s on a v5e with
its programs compiled and 57-61 s from an empty compile cache (PERF.md
section 6). `states_at` gives the scan's states after chosen tokens, in
one more forward, for `drivers/serve_state.py`'s comparison of the states
a slot holds.

The equations (`N(x; g) = x / sqrt(mean(x^2) + eps) * g`;
https://huggingface.co/ai21labs/AI21-Jamba2-3B config.json, `model_type`
jamba):

    layer i  attention if i % attn_layer_period == attn_layer_offset,
             else Mamba; every layer's FFN the dense SwiGLU (num_experts 1)
    block    h = x + Mixer_i(N(x; g_in));  y = h + FFN(N(h; g_post))
    FFN      W_down(silu(x W_gate) * (x W_up))
    attn     q = x W_q -> heads x d;  k = x W_k, v = x W_v -> kv heads x d
             (d = hidden / heads); no position encoding;
             causal softmax(q_h k_g(h)^T / sqrt(d)) v_g(h), g(h) = h //
             (heads / kv heads);  o = concat_h W_o
    Mamba    [u | z] = x W_in (d_inner E each, no bias)
             u'_t = silu(b_c + sum_{k=0..K-1} w_c[k] * u_{t-K+1+k}),
             u_{<0} = 0 (a depthwise causal conv of d_conv K taps)
             [d | B | C] = u' W_x (dt_rank R, d_state N, N; no bias)
             d = N(d; g_dt), B = N(B; g_B), C = N(C; g_C)
             D_t = softplus(d W_dt + b_dt);  A = -exp(A_log) [E, N]
             h_t = exp(D_t[:, None] A) * h_{t-1} + (D_t u'_t)[:, None]
             B_t[None, :],  h_{-1} = 0  (h [E, N])
             y_t = h_t C_t + Dskip * u'_t
             out = (y * silu(z)) W_out
    head     logits = N(h; g_f) E_emb^T, tied

Taken from the published modelling code or Mamba's initialisation and not
from a config key (the configuration file lists them under `assumed`): no
position encoding; the layer order from the two keys; the dense MLP where
`num_experts` is 1; the three norms in the mixer, each with a gain; the
state from zero at a sequence's start. No equation was left to a guess.

`mode` selects the arithmetic of every product with a weight matrix (the
projections, the FFNs, the head), as `reference/gpt.py`'s `linear`
defines it: "f32" is the reference, "bf16" the program's own stated
precision, "fp8" / "int8" the step below it (the CONTROL). The conv, the
scan and the attention's own two products stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt import F32, linear
from benchmark.reference.mimo_v2 import attend, gated_ffn, over_tokens

ATTN = ("q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight")
MAMBA = ("conv_weight", "conv_bias", "dt_bias", "A_log", "D",
         "in_proj.weight", "x_proj.weight", "dt_layernorm.weight",
         "b_layernorm.weight", "c_layernorm.weight", "dt_proj.weight",
         "out_proj.weight")
NORMS = ("input_layernorm.weight", "pre_mlp_layernorm.weight")
FFN = ("gate_proj", "up_proj", "down_proj")
EMBED, NORM = "model.embed_tokens.weight", "model.norm.weight"
# scan steps one iteration of the reference's loop takes
SCAN_UNROLL = 16


def static_of(cfg: dict) -> tuple:
    """The sizes the arithmetic needs, hashable: one set of programs per
    configuration."""
    return (("heads", cfg["num_attention_heads"]),
            ("kv_heads", cfg["num_key_value_heads"]),
            ("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"]),
            ("d_inner", cfg["mamba_expand"] * cfg["hidden_size"]),
            ("d_state", cfg["mamba_d_state"]),
            ("dt_rank", cfg["mamba_dt_rank"]),
            ("eps", cfg["rms_norm_eps"]))


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def layer_params(weights: dict, cfg: dict, i: int) -> dict:
    mixer = ATTN if is_attention(cfg, i) else MAMBA
    names = NORMS + tuple("self_attn." + n for n in mixer) \
        + tuple(f"mlp.{n}.weight" for n in FFN)
    return {k: weights[f"model.layers.{i}.{k}"] for k in names}


# ---------------------------------------------------------------------------
# arithmetic, on one sequence: x [s, hidden]
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def attention(x, p, st, mode):
    s = x.shape[0]
    w = lambda n: p["self_attn." + n]  # noqa: E731
    h, kv, d = st["heads"], st["kv_heads"], st["head_dim"]
    q = linear(x, w("q_proj.weight"), 0.0, mode).reshape(s, h, d)
    k = linear(x, w("k_proj.weight"), 0.0, mode).reshape(s, kv, d)
    v = linear(x, w("v_proj.weight"), 0.0, mode).reshape(s, kv, d)
    return linear(attend(q, k, v), w("o_proj.weight"), 0.0, mode)


def scan(u, dt, a, b, c, ends=None):
    """h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t, y_t = h_t C_t, from
    h_{-1} = 0, a token a step: u, dt [s, E], a [E, N], b, c [s, N] -> y
    [s, E]; with `ends` [k] also the state after each of those many tokens,
    [k, E, N]."""
    k = 0 if ends is None else ends.shape[0]

    def step(carry, xs):
        h, kept = carry
        t, u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t
        if k:
            kept = jnp.where((ends == t + 1)[:, None, None], h, kept)
        return (h, kept), jnp.sum(h * c_t, axis=-1)

    start = (jnp.zeros(a.shape, F32), jnp.zeros((k,) + a.shape, F32))
    (_, kept), y = jax.lax.scan(step, start,
                                (jnp.arange(u.shape[0]), u, dt, b, c),
                                unroll=SCAN_UNROLL)
    return y if ends is None else (y, kept)


def mamba(x, p, st, mode, ends=None):
    s = x.shape[0]
    w = lambda n: p["self_attn." + n]  # noqa: E731
    e, n, r, eps = st["d_inner"], st["d_state"], st["dt_rank"], st["eps"]
    xz = linear(x, w("in_proj.weight"), 0.0, mode)
    u, z = xz[:, :e], xz[:, e:]
    taps = w("conv_weight")                      # [K, E]
    k = taps.shape[0]
    padded = jnp.pad(u, ((k - 1, 0), (0, 0)))
    u = jax.nn.silu(w("conv_bias") + sum(
        taps[i] * padded[i:i + s] for i in range(k)))
    dbc = linear(u, w("x_proj.weight"), 0.0, mode)
    d = rms_norm(dbc[:, :r], w("dt_layernorm.weight"), eps)
    b = rms_norm(dbc[:, r:r + n], w("b_layernorm.weight"), eps)
    c = rms_norm(dbc[:, r + n:], w("c_layernorm.weight"), eps)
    dt = jax.nn.softplus(linear(d, w("dt_proj.weight"), 0.0, mode)
                         + w("dt_bias"))
    a = -jnp.exp(w("A_log"))
    y = scan(u, dt, a, b, c, ends)
    y, kept = y if ends is not None else (y, None)
    out = linear((y + w("D") * u) * jax.nn.silu(z), w("out_proj.weight"),
                 0.0, mode)
    return out if ends is None else (out, kept)


def block(x, p, st, kind, mode, ends=None):
    """One layer of `kind` ("attention" / "mamba") on one sequence; with
    `ends`, a Mamba layer also hands back its scan's states after each of
    those many tokens."""
    p = {k: v.astype(F32) for k, v in p.items()}
    normed = rms_norm(x, p[NORMS[0]], st["eps"])
    if kind == "attention":
        x, kept = x + attention(normed, p, st, mode), None
    else:
        out = mamba(normed, p, st, mode, ends)
        out, kept = out if ends is not None else (out, None)
        x = x + out
    m = rms_norm(x, p[NORMS[1]], st["eps"])
    x = x + over_tokens(lambda t: gated_ffn(
        t, *(p[f"mlp.{n}.weight"] for n in FFN), mode), m)
    return x if ends is None else (x, kept)


@functools.lru_cache(maxsize=None)
def _programs(static: tuple, mode: str):
    st = dict(static)
    return {
        "attention": jax.jit(lambda x, p: block(x, p, st, "attention",
                                                mode)),
        "mamba": jax.jit(lambda x, p: block(x, p, st, "mamba", mode)),
        "mamba_states": jax.jit(lambda x, p, ends: block(
            x, p, st, "mamba", mode, ends)),
        "embed": jax.jit(lambda ids, tok: tok.astype(F32)[ids]),
        "logits": jax.jit(lambda x, gain, emb: linear(
            rms_norm(x, gain.astype(F32), st["eps"]), emb.astype(F32).T,
            0.0, mode)),
    }


def logits_at(weights: dict, cfg: dict, ids, positions, mode="f32",
              picks=None):
    """Next-token logits [len(positions), vocab] (float32, on the device)
    of the sequence `ids` at `positions`, by one full forward pass. The
    family routes no token, so `picks` receives nothing."""
    prog = _programs(static_of(cfg), mode)
    at = jnp.asarray(positions, jnp.int32)
    x = prog["embed"](jnp.asarray(ids, jnp.int32), weights[EMBED])
    for i in range(cfg["num_hidden_layers"]):
        kind = "attention" if is_attention(cfg, i) else "mamba"
        x = prog[kind](x, layer_params(weights, cfg, i))
    return prog["logits"](x[at], weights[NORM], weights[EMBED])


def states_at(weights: dict, cfg: dict, ids, ends, mode="f32") -> dict:
    """{Mamba layer index: its scan's state [len(ends), d_state, d_inner]
    after each of `ends` tokens of the sequence `ids`} (float32, numpy), by
    one full forward pass: the program's layout of a state, [N, E]."""
    prog = _programs(static_of(cfg), mode)
    ends = jnp.asarray(ends, jnp.int32)
    x = prog["embed"](jnp.asarray(ids, jnp.int32), weights[EMBED])
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        if is_attention(cfg, i):
            x = prog["attention"](x, layer_params(weights, cfg, i))
        else:
            x, kept = prog["mamba_states"](x, layer_params(weights, cfg, i),
                                           ends)
            out[i] = np.swapaxes(np.asarray(kept), 1, 2)
    return out
