"""The `afmoe` family (Trinity-Mini: window and full gated grouped-query
attention layers, routed + shared experts, sandwich norms) as the
benchmark reaches it.

Three things, all from the configuration file's keys, as
`families/pangu_ultra_moe.py` gives them for its family:

- the WEIGHT TABLE: every leaf of `AfmoeForCausalLM.named_parameters()` by
  name, made on the device from the run's seed in the served dtype, a leaf
  a program. Matrices and embeddings N(0, 0.02), norm gains 1 + N(0, 0.02)
  so that a dropped gain shows, the routers' `expert_bias` N(0, 0.01) so
  that the pick's bias is live. The program and the plain reference
  (`benchmark/reference/afmoe.py`) are both handed this table;
- the BUILD through the program's public entry points
  (`models.AfmoeForCausalLM`, `inference.ServingEngine`);
- what the ALGORITHM needs, from shapes alone: operations per token and per
  attended pair, bytes a decode step must move. A window layer attends and
  reads `min(context, sliding_window)` positions a row, a full layer the
  context; only the experts a step's rows HIT are counted as read.

In the file `num_experts` is the number of experts HELD here and
`router_experts` the deployment's count (the router's width); `ep_rank`
says which block of them.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights as _weights
from benchmark.families.pangu_ultra_moe import (BF16, STD, _ffn_shapes,
                                                _leaf_maker, _matrix_params)

BIAS_STD = 0.01


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def _attn_shapes(cfg):
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, hid = cfg["head_dim"], cfg["hidden_size"]
    return (("q_proj", (hid, h * d)), ("k_proj", (hid, kv * d)),
            ("v_proj", (hid, kv * d)), ("gate_proj", (hid, h * d)),
            ("o_proj", (h * d, hid)), ("q_norm", (d,)), ("k_norm", (d,)))


def expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def window_layers(cfg) -> int:
    return sum(t == "sliding_attention" for t in cfg["layer_types"])


def full_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - window_layers(cfg)


def cache_bytes_per_token(cfg, itemsize=BF16) -> int:
    """One layer's cache row: K and V of every kv head."""
    return itemsize * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def page_bytes(cfg, page_size, kind=None, itemsize=BF16) -> int:
    """K and V of one page of one layer, every kv head: what the decode
    attention reads for a live page. The window and the full layers share
    one layout, so `kind` ("window" / "full") changes nothing; it is taken
    so that a reader asks every family the same way."""
    return page_size * cache_bytes_per_token(cfg, itemsize)


def leaf_specs(cfg) -> tuple:
    """((name, shape, mean, std), ...) in `named_parameters()` order."""
    hid, held = cfg["hidden_size"], cfg["num_experts"]
    fe = cfg["moe_intermediate_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], hid))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hid,)))
        out += [(p + f"self_attn.{n}.weight", s)
                for n, s in _attn_shapes(cfg)]
        out.append((p + "pre_mlp_layernorm.weight", (hid,)))
        if i < cfg["num_dense_layers"]:
            out += [(p + f"mlp.{n}.weight", s)
                    for n, s in _ffn_shapes(hid, cfg["intermediate_size"])]
        else:
            out += [(p + "mlp.experts.w_gate", (held, hid, fe)),
                    (p + "mlp.experts.w_up", (held, hid, fe)),
                    (p + "mlp.experts.w_down", (held, fe, hid)),
                    (p + "mlp.experts.gate.weight",
                     (hid, cfg["router_experts"])),
                    (p + "mlp.experts.gate.expert_bias",
                     (cfg["router_experts"],))]
            out += [(p + f"mlp.shared_experts.{n}.weight", s)
                    for n, s in _ffn_shapes(
                        hid, cfg["num_shared_experts"] * fe)]
        out += [(p + "post_attention_layernorm.weight", (hid,)),
                (p + "post_mlp_layernorm.weight", (hid,))]
    out.append(("model.norm.weight", (hid,)))
    out.append(("lm_head.weight", (hid, cfg["vocab_size"])))
    # a leaf of one dimension is a norm's gain, but for a router's bias
    return tuple(
        (name, shape, 0.0, BIAS_STD) if name.endswith("expert_bias")
        else (name, shape, 1.0 if len(shape) == 1 else 0.0, STD)
        for name, shape in out)


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
    from paddle_tpu.models import AfmoeConfig

    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "global_attn_every_n_layers", "sliding_window",
            "num_dense_layers", "num_experts_per_tok", "num_shared_experts",
            "route_norm", "route_scale", "mup_enabled", "rms_norm_eps",
            "rope_theta", "max_position_embeddings", "tie_word_embeddings")
    return AfmoeConfig(
        num_experts=cfg["router_experts"], ep_rank=cfg["ep_rank"],
        ep_degree=cfg["router_experts"] // cfg["num_experts"],
        layer_types=tuple(cfg["layer_types"]), dtype=cfg["dtype"],
        **{k: cfg[k] for k in same})


def build_model(cfg: dict, seed: int):
    """`AfmoeForCausalLM` at the configuration's sizes, in eval mode,
    holding the seed's weights in the configuration's dtype."""
    import paddle_tpu as paddle
    from paddle_tpu.models import AfmoeForCausalLM
    from paddle_tpu.nn import initializer

    from benchmark import program

    placeholder = program._Placeholder(cfg["dtype"])
    initializer.set_global_initializer(placeholder, placeholder)
    try:
        model = AfmoeForCausalLM(model_config(cfg))
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


def attn_matrix_params(cfg) -> int:
    return _matrix_params(_attn_shapes(cfg))


def expert_params(cfg) -> int:
    """One routed expert (three matrices)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_per_token(cfg) -> float:
    """Token-expert pairs that land HERE per token and expert layer under
    uniform routing: top_k x held / all."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def experts_hit(cfg, rows: float) -> float:
    """Held experts at least one of `rows` tokens picks, in expectation
    under uniform routing: held x (1 - (1 - top_k / all)^rows)."""
    miss = 1.0 - cfg["num_experts_per_tok"] / cfg["router_experts"]
    return cfg["num_experts"] * (1.0 - miss ** rows)


def matmul_params_per_token(cfg, head: bool = True) -> float:
    """Parameters in a matrix product applied to one token: every layer's
    attention matrices, the dense FFN or the shared expert and the router,
    the routed experts a token is expected to reach here, the head."""
    hid = cfg["hidden_size"]
    per_expert_layer = (cfg["num_shared_experts"] + pairs_per_token(cfg)) \
        * expert_params(cfg) + hid * cfg["router_experts"]
    return cfg["num_hidden_layers"] * attn_matrix_params(cfg) \
        + cfg["num_dense_layers"] * 3 * hid * cfg["intermediate_size"] \
        + expert_layers(cfg) * per_expert_layer \
        + (cfg["vocab_size"] * hid if head else 0)


def pair_flops(cfg) -> int:
    """Operations of one (query, key) pair in one layer, all heads: d for
    the score and d for the weighted sum, a multiply-add each."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def window_pairs(n: int, window: int) -> int:
    """(query, key) pairs of a causal prompt of `n` positions in a layer
    that sees `window` positions back, the query's own included."""
    w = min(n, window)
    return w * (w + 1) // 2 + (n - w) * window


def window_attn_flops(cfg, prompt_len: int) -> float:
    """The window layers' attention of one prompt's prefill: both products
    of every visible (query, key) pair."""
    return pair_flops(cfg) * window_layers(cfg) * window_pairs(
        prompt_len, cfg["sliding_window"])


def prefill_flops(cfg, prompt_len: int, head_tokens: int = 1) -> float:
    """One prompt through the model: causal attention touches n (n + 1) / 2
    pairs in a full layer and `window_pairs` in a window layer; the head is
    needed for the last position only."""
    n = prompt_len
    pairs = full_layers(cfg) * (n * (n + 1) // 2) \
        + window_layers(cfg) * window_pairs(n, cfg["sliding_window"])
    return 2 * matmul_params_per_token(cfg, head=False) * n \
        + pair_flops(cfg) * pairs \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] * head_tokens


def decode_flops(cfg, context_len: float) -> float:
    """One new token of one sequence against `context_len` cached tokens
    (itself included): the context in a full layer, at most the window in
    a window layer. From a MEAN context over several rows it errs HIGH
    (min(mean, window) >= mean of min): exact where every row is on one
    side of the window."""
    seen = full_layers(cfg) * context_len \
        + window_layers(cfg) * min(context_len, cfg["sliding_window"])
    return 2 * matmul_params_per_token(cfg) + pair_flops(cfg) * seen


def attn_cache_bytes(cfg, full_tokens: float, window_tokens: float,
                     itemsize=BF16) -> float:
    """Bytes of K and V a decode step's attention must read: `full_tokens`
    positions a full layer (the rows' contexts, summed) and
    `window_tokens` a window layer (each row's min(context, window),
    summed)."""
    return cache_bytes_per_token(cfg, itemsize) * (
        full_layers(cfg) * full_tokens + window_layers(cfg) * window_tokens)


def decode_bytes(cfg, live_context_tokens: float, rows: float,
                 hit: float = None, itemsize=BF16) -> float:
    """Bytes one decode step of `rows` rows must move: every matrix outside
    the routed experts once, the held experts HIT (`hit` a layer: counted
    by the program, else expected under uniform routing), and the cache
    rows the attention needs. With only `live_context_tokens` (the rows'
    contexts summed) and `rows` to hand, a window layer is counted at
    rows x min(mean context, window): that errs HIGH against the truth
    (the sum of each row's min), by as much as the rows' contexts straddle
    the window. Held against the burst's own live-page counters in a traced
    run of `mixed-closed` (8 rows, mean context 2,779; PERF.md section 5):
    the window layers 805 MB a step here against 680 MB of live pages (high
    by 18 % of that term), the full layers 364 against 376 MB (a page is
    read whole), the step's 6.71 GB high by 113 MB, 1.7 %."""
    if hit is None:
        hit = experts_hit(cfg, rows)
    held_all = expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    # of the embedding a step reads one row a token, not the table
    hid = cfg["hidden_size"]
    read = param_count(cfg) - held_all - (cfg["vocab_size"] - rows) * hid \
        + expert_layers(cfg) * hit * expert_params(cfg)
    mean = live_context_tokens / rows if rows else 0.0
    window_tokens = rows * min(mean, cfg["sliding_window"])
    return itemsize * read + attn_cache_bytes(
        cfg, live_context_tokens, window_tokens, itemsize)
