"""The `pangu_ultra_moe` family (openPangu-Ultra-MoE: latent attention,
routed + shared experts, sandwich norms) as the benchmark reaches it.

Three things, all from the configuration file's keys:

- the WEIGHT TABLE: every leaf of `LatentMoEForCausalLM.named_parameters()`
  by name, made on the device from the run's seed in the served dtype, a
  leaf a program (one program for all 9.84 GB would be free to hold every
  leaf's float32 normals at once). Matrices and embeddings N(0, 0.02),
  norm gains 1 + N(0, 0.02) so that a dropped gain shows (as
  `benchmark/weights.py` does for GPT). The program and the plain reference
  (`benchmark/reference/pangu_ultra_moe.py`) are both handed this table;
- the BUILD through the program's public entry points
  (`models.LatentMoEForCausalLM`, `inference.ServingEngine`);
- what the ALGORITHM needs, from shapes alone (`benchmark/flops.py`'s part
  for GPT): operations per token and per attended pair, bytes a decode
  step must move. Only the experts a step's rows HIT are counted as read,
  in expectation under uniform routing (`experts_hit`): a need that counted
  all the held experts would let a program that skips idle ones read over
  100%.

In the file `n_routed_experts` is the number of experts HELD here and
`router_experts` the deployment's count (the router's width);
`ep_rank` says which block of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import weights as _weights

BF16 = 2  # bytes
STD = 0.02


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def _attn_shapes(cfg):
    h, hid = cfg["num_attention_heads"], cfg["hidden_size"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (("q_a_proj", (hid, cfg["q_lora_rank"])),
            ("q_a_layernorm", (cfg["q_lora_rank"],)),
            ("q_b_proj", (cfg["q_lora_rank"], h * (nope + rope))),
            ("kv_a_proj_with_mqa", (hid, cfg["kv_lora_rank"] + rope)),
            ("kv_a_layernorm", (cfg["kv_lora_rank"],)),
            ("kv_b_proj", (cfg["kv_lora_rank"], h * (nope + v))),
            ("o_proj", (h * v, hid)))


def _ffn_shapes(hid, width):
    return (("gate_proj", (hid, width)), ("up_proj", (hid, width)),
            ("down_proj", (width, hid)))


def expert_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def cache_bytes_per_token(cfg, itemsize=BF16) -> int:
    """One layer's cache row: the latent and the rope key."""
    return itemsize * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def leaf_specs(cfg) -> tuple:
    """((name, shape, mean, std), ...) in `named_parameters()` order."""
    hid, held = cfg["hidden_size"], cfg["n_routed_experts"]
    fe = cfg["moe_intermediate_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], hid))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hid,)))
        out += [(p + f"self_attn.{n}.weight", s)
                for n, s in _attn_shapes(cfg)]
        out.append((p + "pre_mlp_layernorm.weight", (hid,)))
        if i < cfg["first_k_dense_replace"]:
            out += [(p + f"mlp.{n}.weight", s)
                    for n, s in _ffn_shapes(hid, cfg["intermediate_size"])]
        else:
            out += [(p + "mlp.experts.w_gate", (held, hid, fe)),
                    (p + "mlp.experts.w_up", (held, hid, fe)),
                    (p + "mlp.experts.w_down", (held, fe, hid)),
                    (p + "mlp.experts.gate.weight",
                     (hid, cfg["router_experts"]))]
            out += [(p + f"mlp.shared_experts.{n}.weight", s)
                    for n, s in _ffn_shapes(
                        hid, cfg["n_shared_experts"] * fe)]
        if cfg["sandwich_norm"]:
            out += [(p + "post_attention_layernorm.weight", (hid,)),
                    (p + "post_mlp_layernorm.weight", (hid,))]
    out.append(("model.norm.weight", (hid,)))
    if not cfg["tie_word_embeddings"]:
        out.append(("lm_head.weight", (hid, cfg["vocab_size"])))
    # a leaf of one dimension is a norm's gain
    return tuple((name, shape, 1.0 if len(shape) == 1 else 0.0, STD)
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


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape: tuple, dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def make(seed, index, mean, std):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(20250601), seed), index)
        return (mean + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    return jax.jit(make)


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
    from paddle_tpu.models import LatentMoEConfig

    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_shared_experts",
            "norm_topk_prob", "routed_scaling_factor",
            "first_k_dense_replace", "sandwich_norm", "rms_norm_eps",
            "rope_theta", "max_position_embeddings", "tie_word_embeddings")
    return LatentMoEConfig(
        n_routed_experts=cfg["router_experts"], ep_rank=cfg["ep_rank"],
        ep_degree=cfg["router_experts"] // cfg["n_routed_experts"],
        dtype=cfg["dtype"], **{k: cfg[k] for k in same})


def build_model(cfg: dict, seed: int):
    """`LatentMoEForCausalLM` at the configuration's sizes, in eval mode,
    holding the seed's weights in the configuration's dtype."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LatentMoEForCausalLM
    from paddle_tpu.nn import initializer

    from benchmark import program

    placeholder = program._Placeholder(cfg["dtype"])
    initializer.set_global_initializer(placeholder, placeholder)
    try:
        model = LatentMoEForCausalLM(model_config(cfg))
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


def _matrix_params(shapes) -> int:
    return sum(s[0] * s[1] for _, s in shapes if len(s) == 2)


def attn_matrix_params(cfg) -> int:
    return _matrix_params(_attn_shapes(cfg))


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


def matmul_params_per_token(cfg, head: bool = True) -> float:
    """Parameters in a matrix product applied to one token: every layer's
    attention matrices, the dense FFN or the shared expert and the router,
    the routed experts a token is expected to reach here, the head."""
    hid = cfg["hidden_size"]
    dense_layers = cfg["first_k_dense_replace"]
    per_expert_layer = (cfg["n_shared_experts"] + pairs_per_token(cfg)) \
        * expert_params(cfg) + hid * cfg["router_experts"]
    return cfg["num_hidden_layers"] * attn_matrix_params(cfg) \
        + dense_layers * 3 * hid * cfg["intermediate_size"] \
        + expert_layers(cfg) * per_expert_layer \
        + (cfg["vocab_size"] * hid if head else 0)


def pair_flops(cfg) -> int:
    """Operations of one (query, key) pair in one layer, all heads:
    (nope + rope) for the score and v for the weighted sum, a multiply-add
    each. The decompressed form's count: the absorbed form pays more a pair
    (rank + rope and rank) and saves decompressing K and V."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def prefill_flops(cfg, prompt_len: int, head_tokens: int = 1) -> float:
    """One prompt through the model: causal attention touches n (n + 1) / 2
    pairs a layer; the head is needed for the last position only."""
    n = prompt_len
    return 2 * matmul_params_per_token(cfg, head=False) * n \
        + pair_flops(cfg) * cfg["num_hidden_layers"] * n * (n + 1) // 2 \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] * head_tokens


def decode_flops(cfg, context_len: float) -> float:
    """One new token of one sequence against `context_len` cached tokens
    (itself included)."""
    return 2 * matmul_params_per_token(cfg) \
        + pair_flops(cfg) * cfg["num_hidden_layers"] * context_len


def decode_bytes(cfg, live_context_tokens: float, rows: float,
                 hit: float = None, itemsize=BF16) -> float:
    """Bytes one decode step of `rows` rows must move: every matrix outside
    the routed experts once, the held experts HIT (`hit` a layer: counted
    by the program, else expected under uniform routing), and one cache
    row a live token a layer."""
    if hit is None:
        hit = experts_hit(cfg, rows)
    held_all = expert_layers(cfg) * cfg["n_routed_experts"] \
        * expert_params(cfg)
    # of the embedding a step reads one row a token, not the table
    hid = cfg["hidden_size"]
    read = param_count(cfg) - held_all - (cfg["vocab_size"] - rows) * hid \
        + expert_layers(cfg) * hit * expert_params(cfg)
    return itemsize * read + cache_bytes_per_token(cfg, itemsize) \
        * cfg["num_hidden_layers"] * live_context_tokens
