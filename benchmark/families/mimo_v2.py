"""The `mimo_v2` family (MiMo-V2.5: window layers with a sink in the
softmax beside full layers of another kv-head count, keys wider than
values, partial rope with a base a kind, routed experts with no shared one,
norms before the sublayers) as the benchmark reaches it.

Three things, all from the configuration file's keys, as
`families/afmoe.py` gives them for its family:

- the WEIGHT TABLE: every leaf of `MiMoV2ForCausalLM.named_parameters()` by
  name, made on the device from the run's seed in the served dtype, a leaf
  a program. Matrices and embeddings N(0, 0.02), norm gains 1 + N(0, 0.02)
  so that a dropped gain shows, the window layers' `attention_sink_bias`
  N(0, 0.1) and the routers' `expert_bias` (the source's
  e_score_correction_bias) N(0, 0.01), small but not zero, so that the
  sink's column and the pick's bias are both live. The program and the
  plain reference (`benchmark/reference/mimo_v2.py`) are both handed this
  table;
- the BUILD through the program's public entry points
  (`models.MiMoV2ForCausalLM`, `inference.ServingEngine`);
- what the ALGORITHM needs, from shapes alone: operations per token and per
  attended pair, bytes a decode step must move. A layer's cache row is its
  KIND's: a full layer holds `num_key_value_heads` heads and a window
  layer `swa_num_key_value_heads`, each a key of `head_dim` and a value of
  `v_head_dim` numbers; the bytes here are those TRUE widths, whatever a
  pool pads a key to (`pool_bytes` says what the pools hold). A window
  layer attends and reads `min(context, sliding_window)` positions a row, a
  full layer the context; only the experts a step's rows HIT are counted
  as read.

In the file `n_routed_experts` is the number of experts HELD here and
`router_experts` the deployment's count (the router's width); `ep_rank`
says which block of them.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights as _weights
from benchmark.families.pangu_ultra_moe import (BF16, STD, _ffn_shapes,
                                                _leaf_maker, _matrix_params)

BIAS_STD = 0.01
SINK_STD = 0.1
KINDS = ("full", "window")


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def attention(cfg, kind) -> dict:
    """heads, kv_heads, key_dim, value_dim, sink of a layer of `kind`."""
    pre = "swa_" if kind == "window" else ""
    return dict(
        heads=cfg[pre + "num_attention_heads"],
        kv_heads=cfg[pre + "num_key_value_heads"],
        key_dim=cfg[pre + "head_dim"], value_dim=cfg[pre + "v_head_dim"],
        sink=cfg["add_swa_attention_sink_bias" if kind == "window"
                 else "add_full_attention_sink_bias"])


def kind_of(cfg, i) -> str:
    return "window" if cfg["hybrid_layer_pattern"][i] else "full"


def _attn_shapes(cfg, kind):
    a, hid = attention(cfg, kind), cfg["hidden_size"]
    shapes = (("q_proj.weight", (hid, a["heads"] * a["key_dim"])),
              ("k_proj.weight", (hid, a["kv_heads"] * a["key_dim"])),
              ("v_proj.weight", (hid, a["kv_heads"] * a["value_dim"])),
              ("o_proj.weight", (a["heads"] * a["value_dim"], hid)))
    if a["sink"]:
        shapes += (("attention_sink_bias", (a["heads"],)),)
    return shapes


def layers_of(cfg, kind) -> int:
    return sum(kind_of(cfg, i) == kind
               for i in range(cfg["num_hidden_layers"]))


def window_layers(cfg) -> int:
    return layers_of(cfg, "window")


def full_layers(cfg) -> int:
    return layers_of(cfg, "full")


def expert_layers(cfg) -> int:
    return sum(cfg["moe_layer_freq"])


def cache_bytes_per_token(cfg, kind, itemsize=BF16) -> int:
    """One layer's cache row of `kind`: a key and a value of every kv head
    the kind has, at their TRUE widths."""
    a = attention(cfg, kind)
    return itemsize * a["kv_heads"] * (a["key_dim"] + a["value_dim"])


def page_bytes(cfg, page_size, kind, itemsize=BF16) -> int:
    """K and V of one page of one layer of `kind`, every kv head: what the
    decode attention has to read for a live page."""
    return page_size * cache_bytes_per_token(cfg, kind, itemsize)


def pool_width(width) -> int:
    """How wide the program STORES a cache row of `width` numbers
    (`models/latent_moe.py`'s `_pool_width`: a row wider than one lane tile
    that does not fill whole ones is padded to the next): 192 -> 256."""
    return width if width <= 128 or width % 128 == 0 \
        else -(-width // 128) * 128


def pool_bytes(cfg, engine_cfg, kind, itemsize=BF16) -> int:
    """What the engine's pools of the layers of `kind` hold: every slot's
    every page in a full layer, a ring of ceil(window / page) + 1 pages a
    slot in a window layer, a key `pool_width` wide."""
    a, page = attention(cfg, kind), engine_cfg["page_size"]
    pages = engine_cfg["max_seq_len"] // page if kind == "full" \
        else -(-cfg["sliding_window"] // page) + 1
    return layers_of(cfg, kind) * engine_cfg["max_batch"] * pages * page \
        * itemsize * a["kv_heads"] * (pool_width(a["key_dim"])
                                      + a["value_dim"])


def leaf_specs(cfg) -> tuple:
    """((name, shape, mean, std), ...) in `named_parameters()` order."""
    hid, held = cfg["hidden_size"], cfg["n_routed_experts"]
    fe = cfg["moe_intermediate_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], hid))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hid,)))
        out += [(p + f"self_attn.{n}", s)
                for n, s in _attn_shapes(cfg, kind_of(cfg, i))]
        out.append((p + "pre_mlp_layernorm.weight", (hid,)))
        if cfg["moe_layer_freq"][i]:
            out += [(p + "mlp.experts.w_gate", (held, hid, fe)),
                    (p + "mlp.experts.w_up", (held, hid, fe)),
                    (p + "mlp.experts.w_down", (held, fe, hid)),
                    (p + "mlp.experts.gate.weight",
                     (hid, cfg["router_experts"])),
                    (p + "mlp.experts.gate.expert_bias",
                     (cfg["router_experts"],))]
        else:
            out += [(p + f"mlp.{n}.weight", s)
                    for n, s in _ffn_shapes(hid, cfg["intermediate_size"])]
    out.append(("model.norm.weight", (hid,)))
    out.append(("lm_head.weight", (hid, cfg["vocab_size"])))

    def draw(name, shape):
        if name.endswith("expert_bias"):
            return 0.0, BIAS_STD
        if name.endswith("attention_sink_bias"):
            return 0.0, SINK_STD
        # another leaf of one dimension is a norm's gain
        return (1.0 if len(shape) == 1 else 0.0), STD

    return tuple((name, shape) + draw(name, shape) for name, shape in out)


def param_count(cfg) -> int:
    total = 0
    for _, shape, _, _ in leaf_specs(cfg):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def weight_bytes(cfg, itemsize=BF16) -> int:
    return itemsize * param_count(cfg)


def make_weights(cfg, seed: int, dtype="bfloat16") -> dict:
    """name -> array of `dtype`, the same for the same (cfg sizes, seed)."""
    seed = _weights.seed_array(seed)
    name = jnp.dtype(dtype).name
    return {leaf: _leaf_maker(tuple(shape), name)(
                seed, jnp.uint32(i), jnp.float32(mean), jnp.float32(std))
            for i, (leaf, shape, mean, std) in enumerate(leaf_specs(cfg))}


# ---------------------------------------------------------------------------
# the program, through its public entry points
# ---------------------------------------------------------------------------


def model_config(cfg: dict):
    from paddle_tpu.models import MiMoV2Config

    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads",
            "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "sliding_window", "partial_rotary_factor", "rope_theta",
            "swa_rope_theta", "attention_value_scale",
            "add_full_attention_sink_bias", "add_swa_attention_sink_bias",
            "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
            "routed_scaling_factor", "layernorm_epsilon",
            "max_position_embeddings", "tie_word_embeddings")
    return MiMoV2Config(
        n_routed_experts=cfg["router_experts"], ep_rank=cfg["ep_rank"],
        ep_degree=cfg["router_experts"] // cfg["n_routed_experts"],
        hybrid_layer_pattern=tuple(cfg["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(cfg["moe_layer_freq"]), dtype=cfg["dtype"],
        **{k: cfg[k] for k in same})


def build_model(cfg: dict, seed: int):
    """`MiMoV2ForCausalLM` at the configuration's sizes, in eval mode,
    holding the seed's weights in the configuration's dtype."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MiMoV2ForCausalLM
    from paddle_tpu.nn import initializer

    from benchmark import program

    placeholder = program._Placeholder(cfg["dtype"])
    initializer.set_global_initializer(placeholder, placeholder)
    try:
        model = MiMoV2ForCausalLM(model_config(cfg))
    finally:
        initializer.set_global_initializer(None, None)
    paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    model.eval()
    params = dict(model.named_parameters())
    specs = {name: tuple(shape) for name, shape, _, _ in leaf_specs(cfg)}
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


# ---------------------------------------------------------------------------
# what the algorithm needs
# ---------------------------------------------------------------------------


def attn_matrix_params(cfg, kind) -> int:
    return _matrix_params(_attn_shapes(cfg, kind))


def expert_params(cfg) -> int:
    """One routed expert (three matrices)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_per_token(cfg) -> float:
    """Token-expert pairs that land HERE per token and expert layer under
    uniform routing: top_k x held / all."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def experts_hit(cfg, rows: float) -> float:
    """Held experts at least one of `rows` tokens picks, in expectation
    under uniform routing: held x (1 - (1 - top_k / all)^rows)."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["router_experts"]
    return cfg["n_routed_experts"] * (1.0 - miss ** rows)


def dense_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - expert_layers(cfg)


def matmul_params_per_token(cfg, head: bool = True) -> float:
    """Parameters in a matrix product applied to one token: every layer's
    attention matrices (its kind's), the dense FFN or the router, the
    routed experts a token is expected to reach here, the head."""
    hid = cfg["hidden_size"]
    per_expert_layer = pairs_per_token(cfg) * expert_params(cfg) \
        + hid * cfg["router_experts"]
    return sum(layers_of(cfg, k) * attn_matrix_params(cfg, k)
               for k in KINDS) \
        + dense_layers(cfg) * 3 * hid * cfg["intermediate_size"] \
        + expert_layers(cfg) * per_expert_layer \
        + (cfg["vocab_size"] * hid if head else 0)


def pair_flops(cfg, kind) -> int:
    """Operations of one (query, key) pair in one layer of `kind`, all
    heads: the key's width for the score and the value's for the weighted
    sum, a multiply-add each."""
    a = attention(cfg, kind)
    return 2 * a["heads"] * (a["key_dim"] + a["value_dim"])


def window_pairs(n: int, window: int) -> int:
    """(query, key) pairs of a causal prompt of `n` positions in a layer
    that sees `window` positions back, the query's own included."""
    w = min(n, window)
    return w * (w + 1) // 2 + (n - w) * window


def window_attn_flops(cfg, prompt_len: int) -> float:
    """The window layers' attention of one prompt's prefill: both products
    of every visible (query, key) pair."""
    return pair_flops(cfg, "window") * window_layers(cfg) * window_pairs(
        prompt_len, cfg["sliding_window"])


def full_attn_flops(cfg, prompt_len: int) -> float:
    """The full layers' attention of one prompt's prefill: both products
    of the n (n + 1) / 2 causal pairs."""
    n = prompt_len
    return pair_flops(cfg, "full") * full_layers(cfg) * (n * (n + 1) // 2)


def prefill_flops(cfg, prompt_len: int, head_tokens: int = 1) -> float:
    """One prompt through the model: the matrices on every token, both
    kinds' attention, the head for the last position only."""
    return 2 * matmul_params_per_token(cfg, head=False) * prompt_len \
        + full_attn_flops(cfg, prompt_len) \
        + window_attn_flops(cfg, prompt_len) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] * head_tokens


def decode_flops(cfg, context_len: float) -> float:
    """One new token of one sequence against `context_len` cached tokens
    (itself included): the context in a full layer, at most the window in
    a window layer (from a MEAN context it errs high only where rows
    straddle the window: at 128 none of this family's cells do)."""
    return 2 * matmul_params_per_token(cfg) \
        + pair_flops(cfg, "full") * full_layers(cfg) * context_len \
        + pair_flops(cfg, "window") * window_layers(cfg) \
        * min(context_len, cfg["sliding_window"])


def attn_cache_bytes(cfg, full_tokens: float, window_tokens: float,
                     itemsize=BF16) -> float:
    """Bytes of K and V a decode step's attention must read: `full_tokens`
    positions a full layer (the rows' contexts, summed) and
    `window_tokens` a window layer (each row's min(context, window),
    summed), each at its kind's row."""
    return full_layers(cfg) * full_tokens \
        * cache_bytes_per_token(cfg, "full", itemsize) \
        + window_layers(cfg) * window_tokens \
        * cache_bytes_per_token(cfg, "window", itemsize)


def decode_bytes(cfg, live_context_tokens: float, rows: float,
                 hit: float = None, itemsize=BF16) -> float:
    """Bytes one decode step of `rows` rows must move: every matrix outside
    the routed experts once, the held experts HIT (`hit` a layer: counted
    by the program, else expected under uniform routing), and the cache
    rows the attention needs, a window layer counted at rows x min(mean
    context, window)."""
    if hit is None:
        hit = experts_hit(cfg, rows)
    held_all = expert_layers(cfg) * cfg["n_routed_experts"] \
        * expert_params(cfg)
    # of the embedding a step reads one row a token, not the table
    hid = cfg["hidden_size"]
    read = param_count(cfg) - held_all - (cfg["vocab_size"] - rows) * hid \
        + expert_layers(cfg) * hit * expert_params(cfg)
    mean = live_context_tokens / rows if rows else 0.0
    window_tokens = rows * min(mean, cfg["sliding_window"])
    return itemsize * read + attn_cache_bytes(
        cfg, live_context_tokens, window_tokens, itemsize)
