"""The `jamba` family (AI21-Jamba2-3B: Mamba-1 mixers beside grouped-query
attention layers with no position encoding, a dense SwiGLU FFN in every
layer, norms before the sublayers, a tied head) as the benchmark reaches
it.

Three things, all from the configuration file's keys, as
`families/mimo_v2.py` gives them for its family:

- the WEIGHT TABLE: every leaf of `JambaForCausalLM.named_parameters()` by
  name, made on the device from the run's seed in the served dtype, a leaf
  a program. Matrices, the embedding, the conv's taps and its bias
  N(0, 0.02), norm gains 1 + N(0, 0.02) so that a dropped gain shows; as
  Mamba's published initialisation sets them, `A_log` = log(1 .. d_state)
  in every channel, `D` = 1 and `dt_bias` such that softplus(dt_bias) is
  log-uniform in [0.001, 0.1] (drawn from the seed), so that the state
  carries something over many tokens. The program and the plain reference
  (`benchmark/reference/jamba.py`) are both handed this table;
- the BUILD through the program's public entry points
  (`models.JambaForCausalLM`, `inference.ServingEngine`), and where a
  slot's scan states lie in the engine's pools (`drivers/serve_state.py`
  reads them back);
- what the ALGORITHM needs, from shapes alone: operations per token and per
  attended pair (the matrix products and the attention; the conv's and
  the scan's element-wise work, under 1 % of a token's, is left out), the
  bytes a decode step must move and the least bytes a prompt's scan must
  move.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as _weights

BF16 = 2  # bytes
F32 = 4
STD = 0.02
DT_MIN, DT_MAX = 0.001, 0.1


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def is_attention(cfg, i) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def attention_layers(cfg) -> int:
    return sum(is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))


def mamba_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - attention_layers(cfg)


def d_inner(cfg) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _attn_shapes(cfg):
    hid, d = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (("q_proj.weight", (hid, h * d)), ("k_proj.weight", (hid, kv * d)),
            ("v_proj.weight", (hid, kv * d)), ("o_proj.weight", (h * d, hid)))


def _mamba_shapes(cfg):
    """In `named_parameters()` order: the mixer's own leaves, then its
    sublayers'."""
    hid, e, n = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"]
    r = cfg["mamba_dt_rank"]
    return (("conv_weight", (cfg["mamba_d_conv"], e)), ("conv_bias", (e,)),
            ("dt_bias", (e,)), ("A_log", (e, n)), ("D", (e,)),
            ("in_proj.weight", (hid, 2 * e)),
            ("x_proj.weight", (e, r + 2 * n)),
            ("dt_layernorm.weight", (r,)), ("b_layernorm.weight", (n,)),
            ("c_layernorm.weight", (n,)), ("dt_proj.weight", (r, e)),
            ("out_proj.weight", (e, hid)))


def _ffn_shapes(cfg):
    hid, f = cfg["hidden_size"], cfg["intermediate_size"]
    return (("gate_proj.weight", (hid, f)), ("up_proj.weight", (hid, f)),
            ("down_proj.weight", (f, hid)))


def leaf_specs(cfg) -> tuple:
    """((name, shape), ...) in `named_parameters()` order."""
    hid = cfg["hidden_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], hid))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hid,)))
        mixer = _attn_shapes(cfg) if is_attention(cfg, i) \
            else _mamba_shapes(cfg)
        out += [(p + "self_attn." + n, s) for n, s in mixer]
        out.append((p + "pre_mlp_layernorm.weight", (hid,)))
        out += [(p + "mlp." + n, s) for n, s in _ffn_shapes(cfg)]
    out.append(("model.norm.weight", (hid,)))
    return tuple(out)


def param_count(cfg) -> int:
    return sum(math.prod(shape) for _, shape in leaf_specs(cfg))


def weight_bytes(cfg, itemsize=BF16) -> int:
    return itemsize * param_count(cfg)


def state_bytes_per_seq(cfg, itemsize=BF16) -> int:
    """What a sequence's state holds over the Mamba layers, whatever its
    length: the scan's [d_state, d_inner] in float32 and the conv's last
    d_conv - 1 inputs in the served type, a layer."""
    e = d_inner(cfg)
    return mamba_layers(cfg) * (F32 * cfg["mamba_d_state"] * e
                                + itemsize * (cfg["mamba_d_conv"] - 1) * e)


def cache_bytes_per_token(cfg, itemsize=BF16) -> int:
    """K and V of one token in every attention layer."""
    return attention_layers(cfg) * 2 * itemsize \
        * cfg["num_key_value_heads"] * head_dim(cfg)


def pool_bytes(cfg, engine_cfg, itemsize=BF16) -> dict:
    """What the engine's pools hold, by `kv_pool_bytes()`'s keys: every
    slot's pages in the attention layers, a state a slot in the Mamba
    layers."""
    slots = engine_cfg["max_batch"]
    return {"kv_pool_bytes_full": slots * engine_cfg["max_seq_len"]
            * cache_bytes_per_token(cfg, itemsize),
            "kv_pool_bytes_window": 0,
            "kv_pool_bytes_state": slots * state_bytes_per_seq(cfg, itemsize)}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _init(name, shape):
    """(kind, mean, std) of a leaf: a normal draw, or Mamba's published
    initialisation of A_log, D and dt_bias."""
    for kind in ("A_log", "D", "dt_bias"):
        if name.endswith("self_attn." + kind):
            return kind, 0.0, 0.0
    # another leaf of one dimension is a norm's gain, but for the conv's bias
    gain = len(shape) == 1 and not name.endswith("conv_bias")
    return "normal", 1.0 if gain else 0.0, STD


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape: tuple, dtype_name: str, kind: str):
    dtype = jnp.dtype(dtype_name)

    def make(seed, index, mean, std):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(20250601), seed), index)
        if kind == "A_log":
            row = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
            value = jnp.broadcast_to(row, shape)
        elif kind == "D":
            value = jnp.ones(shape, jnp.float32)
        elif kind == "dt_bias":
            u = jax.random.uniform(key, shape, jnp.float32)
            dt = jnp.exp(math.log(DT_MIN)
                         + u * (math.log(DT_MAX) - math.log(DT_MIN)))
            # the inverse of softplus
            value = dt + jnp.log(-jnp.expm1(-dt))
        else:
            value = mean + std * jax.random.normal(key, shape, jnp.float32)
        return value.astype(dtype)

    return jax.jit(make)


def make_weights(cfg, seed: int, dtype="bfloat16") -> dict:
    """name -> array of `dtype`, the same for the same (cfg sizes, seed)."""
    seed = _weights.seed_array(seed)
    name = jnp.dtype(dtype).name
    out = {}
    for i, (leaf, shape) in enumerate(leaf_specs(cfg)):
        kind, mean, std = _init(leaf, shape)
        out[leaf] = _leaf_maker(tuple(shape), name, kind)(
            seed, jnp.uint32(i), jnp.float32(mean), jnp.float32(std))
    return out


# ---------------------------------------------------------------------------
# the program, through its public entry points
# ---------------------------------------------------------------------------

KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "attn_layer_period",
        "attn_layer_offset", "expert_layer_period", "expert_layer_offset",
        "num_experts", "num_experts_per_tok", "mamba_d_state",
        "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
        "mamba_proj_bias", "rms_norm_eps", "max_position_embeddings",
        "sliding_window", "tie_word_embeddings")


def model_config(cfg: dict):
    from paddle_tpu.models import JambaConfig

    return JambaConfig(dtype=cfg["dtype"], **{k: cfg[k] for k in KEYS})


def build_model(cfg: dict, seed: int):
    """`JambaForCausalLM` at the configuration's sizes, in eval mode,
    holding the seed's weights in the configuration's dtype."""
    import paddle_tpu as paddle
    from paddle_tpu.models import JambaForCausalLM
    from paddle_tpu.nn import initializer

    from benchmark import program

    placeholder = program._Placeholder(cfg["dtype"])
    initializer.set_global_initializer(placeholder, placeholder)
    try:
        model = JambaForCausalLM(model_config(cfg))
    finally:
        initializer.set_global_initializer(None, None)
    paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    model.eval()
    params = dict(model.named_parameters())
    specs = dict(leaf_specs(cfg))
    shapes = {name: tuple(p.shape) for name, p in params.items()}
    if specs != shapes:
        raise RuntimeError(
            "the family's weight table and the program's parameters differ: "
            f"{sorted(set(specs.items()) ^ set(shapes.items()))[:6]}")
    for p in params.values():
        p._rebind(None)   # the placeholders go before the weights come
    for name, array in make_weights(cfg, seed, cfg["dtype"]).items():
        params[name]._rebind(array)
    return model


def build_engine(model, engine_cfg: dict):
    from benchmark import program

    return program.build_engine(model, engine_cfg)


def scan_states(engine, cfg, slot) -> dict:
    """{Mamba layer: the scan's state [d_state, d_inner] the engine holds
    for `slot`}, float32 on the host: a Mamba layer's second pool (its
    first is the conv tail)."""
    return {i: np.asarray(engine.v_pages[i][slot]).astype(np.float32)
            for i in range(cfg["num_hidden_layers"])
            if not is_attention(cfg, i)}


# ---------------------------------------------------------------------------
# what the algorithm needs
# ---------------------------------------------------------------------------


def _matrix_params(shapes) -> int:
    return sum(s[0] * s[1] for _, s in shapes if len(s) == 2)


def mamba_matrix_params(cfg) -> int:
    """The four projections of a Mamba mixer (the conv's taps are not a
    matrix product)."""
    return _matrix_params(s for s in _mamba_shapes(cfg)
                          if s[0].endswith("proj.weight"))


def attn_matrix_params(cfg) -> int:
    return _matrix_params(_attn_shapes(cfg))


def matmul_params_per_token(cfg, head: bool = True) -> float:
    """Parameters in a matrix product applied to one token: every layer's
    mixer and FFN, the head."""
    return mamba_layers(cfg) * mamba_matrix_params(cfg) \
        + attention_layers(cfg) * attn_matrix_params(cfg) \
        + cfg["num_hidden_layers"] * _matrix_params(_ffn_shapes(cfg)) \
        + (cfg["vocab_size"] * cfg["hidden_size"] if head else 0)


def pair_flops(cfg) -> int:
    """Operations of one (query, key) pair in one attention layer, all
    heads: a key's width for the score and a value's for the weighted
    sum, a multiply-add each."""
    return 2 * cfg["num_attention_heads"] * 2 * head_dim(cfg)


def full_attn_flops(cfg, prompt_len: int) -> float:
    """The attention layers' share of one prompt's prefill: both products
    of the n (n + 1) / 2 causal pairs."""
    n = prompt_len
    return pair_flops(cfg) * attention_layers(cfg) * (n * (n + 1) // 2)


def prefill_flops(cfg, prompt_len: int, head_tokens: int = 1) -> float:
    """One prompt through the model: the matrices on every token, the
    attention's pairs, the head for the last position only."""
    return 2 * matmul_params_per_token(cfg, head=False) * prompt_len \
        + full_attn_flops(cfg, prompt_len) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] * head_tokens


def decode_flops(cfg, context_len: float) -> float:
    """One new token of one sequence against `context_len` cached tokens
    (itself included) in the attention layers."""
    return 2 * matmul_params_per_token(cfg) \
        + pair_flops(cfg) * attention_layers(cfg) * context_len


def decode_bytes(cfg, live_context_tokens: float, rows: float,
                 itemsize=BF16) -> float:
    """Bytes one decode step of `rows` rows must move: every weight once
    (the tied embedding is the head's matrix, read whole), K and V of the
    live tokens in the attention layers, and every live row's state READ
    and WRITTEN in each Mamba layer."""
    return itemsize * param_count(cfg) \
        + cache_bytes_per_token(cfg, itemsize) * live_context_tokens \
        + 2 * rows * state_bytes_per_seq(cfg, itemsize)


def ssm_scan_bytes(cfg, tokens: float, rows: float, itemsize=BF16) -> float:
    """The least bytes the Mamba layers' scans must move over `rows`
    prompts of `tokens` real tokens in all, whatever implements them: a
    token and a layer, u' and dt in and y out at `itemsize` a channel of
    d_inner, B and C in at float32 a channel of d_state; a prompt and a
    layer, the final state out at float32."""
    e, n = d_inner(cfg), cfg["mamba_d_state"]
    return mamba_layers(cfg) * (tokens * (3 * itemsize * e + 2 * F32 * n)
                                + rows * F32 * n * e)
