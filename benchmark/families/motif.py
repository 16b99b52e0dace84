"""The `motif` family (Motif-3-Beta: grouped differential attention on
latent rows over a window and over every position, routed + shared experts
with PolyNorm, four residual streams mixed by mHC) as the benchmark
reaches it.

Three things, all from the configuration file's keys, as
`families/mimo_v2.py` gives them for its family:

- the WEIGHT TABLE: every leaf of `MotifForCausalLM.named_parameters()` by
  name, made on the device from the run's seed in the served dtype, a leaf
  a program. Matrices and embeddings N(0, 0.02), norm gains 1 + N(0,
  0.02); PolyNorm's weights 1/3 + N(0, 0.1) and bias N(0, 0.3), so that
  its three terms differ and its clamp is reached; mHC's phi N(0, 0.02),
  alpha 0.3 + N(0, 0.05) and bias N(0, 0.5), so that H_res is a mix far
  from the identity. The program and the plain reference
  (`benchmark/reference/motif.py`) are both handed this table;
- the BUILD through the program's public entry points
  (`models.MotifForCausalLM`, `inference.ServingEngine`);
- what the ALGORITHM needs, from shapes alone: operations per token and per
  attended pair, bytes a decode step must move, bytes the residual mixes
  of a prefill must move, and the PolyNorm expert kernels' operations and
  bytes. A layer's cache row is the latent row, `kv_lora_rank +
  qk_rope_head_dim` numbers, whatever its kind; a window layer attends and
  reads `min(context, sliding_window)` positions a row, a full layer the
  context; only the experts a step's rows HIT are counted as read.

In the file `num_experts` is the number of experts HELD here and
`router_experts` the deployment's count (the router's width); `ep_rank`
says which block of them. `first_layer` is the published index of the
stack's first layer.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import weights as _weights
from benchmark.families.pangu_ultra_moe import (BF16, STD, _ffn_shapes,
                                                _leaf_maker, _matrix_params)

KINDS = ("full", "window")
DRAWS = {   # leaf name ending -> (mean, std)
    "act_fn.weight": (1.0 / 3.0, 0.1), "act_weight": (1.0 / 3.0, 0.1),
    "act_fn.bias": (0.0, 0.3), "act_bias": (0.0, 0.3),
    "_hc.phi": (0.0, STD), "_hc.alpha": (0.3, 0.05), "_hc.bias": (0.0, 0.5),
}


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def kind_of(cfg, i) -> str:
    period = cfg["sliding_window_period"]
    return "full" if (cfg["first_layer"] + i) % period == period - 1 \
        else "window"


def layers_of(cfg, kind) -> int:
    return sum(kind_of(cfg, i) == kind
               for i in range(cfg["num_hidden_layers"]))


def window_layers(cfg) -> int:
    return layers_of(cfg, "window")


def full_layers(cfg) -> int:
    return layers_of(cfg, "full")


def is_dense(cfg, i) -> bool:
    return cfg["first_layer"] + i < cfg["n_dense_first_layers"]


def expert_layers(cfg) -> int:
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def dense_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - expert_layers(cfg)


def signal_heads(cfg) -> int:
    return cfg["num_attention_heads"] - cfg["num_noise_heads"]


def _attn_shapes(cfg):
    h, hid = cfg["num_attention_heads"], cfg["hidden_size"]
    rope, v = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    nope = cfg["head_dim"] - rope
    signal = signal_heads(cfg)
    return (("q_a_proj", (hid, cfg["q_lora_rank"])),
            ("q_a_layernorm", (cfg["q_lora_rank"],)),
            ("q_b_proj", (cfg["q_lora_rank"], h * cfg["head_dim"])),
            ("kv_a_proj_with_mqa", (hid, cfg["kv_lora_rank"] + rope)),
            ("kv_a_layernorm", (cfg["kv_lora_rank"],)),
            ("kv_b_proj", (cfg["kv_lora_rank"],
                           cfg["num_key_value_heads"] * (nope + v))),
            ("o_proj", (signal * v, hid)),
            ("lambda_proj", (hid, signal)),
            ("gate_proj", (hid, signal * v)))


def _hc_shapes(cfg):
    n, c = cfg["mhc_expansion_rate"], cfg["hidden_size"]
    return (("phi", (n * c, 2 * n + n * n)), ("alpha", (3,)),
            ("bias", (2 * n + n * n,)))


def cache_bytes_per_token(cfg, itemsize=BF16) -> int:
    """One layer's cache row, whatever its kind: the latent and the rope
    key."""
    return itemsize * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def page_bytes(cfg, page_size, kind=None, itemsize=BF16) -> int:
    """One page of one layer's latent rows (both kinds alike)."""
    return page_size * cache_bytes_per_token(cfg, itemsize)


def pool_bytes(cfg, engine_cfg, kind, itemsize=BF16) -> int:
    """What the engine's pools of the layers of `kind` hold: every slot's
    every page in a full layer, a ring of ceil(window / page) + 1 pages a
    slot in a window layer."""
    page = engine_cfg["page_size"]
    pages = engine_cfg["max_seq_len"] // page if kind == "full" \
        else -(-cfg["sliding_window"] // page) + 1
    return layers_of(cfg, kind) * engine_cfg["max_batch"] * pages \
        * page_bytes(cfg, page, itemsize=itemsize)


def leaf_specs(cfg) -> tuple:
    """((name, shape, mean, std), ...) in `named_parameters()` order."""
    hid, held = cfg["hidden_size"], cfg["num_experts"]
    fe = cfg["moe_intermediate_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], hid))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.append((p + "input_layernorm.weight", (hid,)))
        out += [(p + f"self_attn.{n}.weight", s) for n, s in
                _attn_shapes(cfg)]
        out.append((p + "pre_mlp_layernorm.weight", (hid,)))
        if is_dense(cfg, i):
            out += [(p + f"mlp.{n}.weight", s)
                    for n, s in _ffn_shapes(hid, cfg["intermediate_size"])]
            out += [(p + "mlp.act_fn.weight", (3,)),
                    (p + "mlp.act_fn.bias", (1,))]
        else:
            out += [(p + "mlp.experts.w_gate", (held, hid, fe)),
                    (p + "mlp.experts.w_up", (held, hid, fe)),
                    (p + "mlp.experts.w_down", (held, fe, hid)),
                    (p + "mlp.experts.act_weight", (held, 3)),
                    (p + "mlp.experts.act_bias", (held, 1)),
                    (p + "mlp.experts.gate.weight",
                     (hid, cfg["router_experts"]))]
            out += [(p + f"mlp.shared_experts.{n}.weight", s)
                    for n, s in _ffn_shapes(
                        hid, cfg["num_shared_experts"] * fe)]
            out += [(p + "mlp.shared_experts.act_fn.weight", (3,)),
                    (p + "mlp.shared_experts.act_fn.bias", (1,))]
        out += [(p + f"{s}_hc.{n}", shape) for s in ("attn", "mlp")
                for n, shape in _hc_shapes(cfg)]
    out.append(("model.norm.weight", (hid,)))
    out.append(("lm_head.weight", (hid, cfg["vocab_size"])))

    def draw(name, shape):
        for end, ms in DRAWS.items():
            if name.endswith(end):
                return ms
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
    from paddle_tpu.models import MotifConfig

    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "num_noise_heads",
            "head_dim", "q_lora_rank", "kv_lora_rank", "qk_rope_head_dim",
            "v_head_dim", "first_layer", "sliding_window",
            "sliding_window_period", "n_dense_first_layers", "experts_top_k",
            "num_shared_experts", "route_norm", "route_scale",
            "mhc_expansion_rate", "mhc_sinkhorn_iters",
            "polynorm_output_scale", "polynorm_bias_clamp", "polynorm_eps",
            "hidden_clamp", "rms_norm_eps", "rope_theta", "swa_rope_theta",
            "max_position_embeddings", "tie_word_embeddings")
    r = cfg["rope_scaling"]
    return MotifConfig(
        num_experts=cfg["router_experts"], ep_rank=cfg["ep_rank"],
        ep_degree=cfg["router_experts"] // cfg["num_experts"],
        rope_scaling={k: r[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow")},
        dtype=cfg["dtype"], **{k: cfg[k] for k in same})


def build_model(cfg: dict, seed: int):
    """`MotifForCausalLM` at the configuration's sizes, in eval mode,
    holding the seed's weights in the configuration's dtype."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MotifForCausalLM
    from paddle_tpu.nn import initializer

    from benchmark import program

    placeholder = program._Placeholder(cfg["dtype"])
    initializer.set_global_initializer(placeholder, placeholder)
    try:
        model = MotifForCausalLM(model_config(cfg))
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


def hc_matrix_params(cfg) -> int:
    """mHC's phi of one sublayer."""
    return _matrix_params(_hc_shapes(cfg))


def expert_params(cfg) -> int:
    """One routed expert (three matrices)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_per_token(cfg) -> float:
    """Token-expert pairs that land HERE per token and expert layer under
    uniform routing: top_k x held / all."""
    return cfg["experts_top_k"] * cfg["num_experts"] / cfg["router_experts"]


def experts_hit(cfg, rows: float) -> float:
    """Held experts at least one of `rows` tokens picks, in expectation
    under uniform routing: held x (1 - (1 - top_k / all)^rows)."""
    miss = 1.0 - cfg["experts_top_k"] / cfg["router_experts"]
    return cfg["num_experts"] * (1.0 - miss ** rows)


def matmul_params_per_token(cfg, head: bool = True) -> float:
    """Parameters in a matrix product applied to one token: every layer's
    attention matrices and two mHC phis, the dense FFN or the shared
    expert and the router, the routed experts a token is expected to
    reach here, the head."""
    hid = cfg["hidden_size"]
    per_expert_layer = (cfg["num_shared_experts"] + pairs_per_token(cfg)) \
        * expert_params(cfg) + hid * cfg["router_experts"]
    return cfg["num_hidden_layers"] * (attn_matrix_params(cfg)
                                       + 2 * hc_matrix_params(cfg)) \
        + dense_layers(cfg) * 3 * hid * cfg["intermediate_size"] \
        + expert_layers(cfg) * per_expert_layer \
        + (cfg["vocab_size"] * hid if head else 0)


def pair_flops(cfg, kind=None) -> int:
    """Operations of one (query, key) pair in one layer, all query heads:
    the key's width for the score and the value's for the weighted sum, a
    multiply-add each (the decompressed form's count, as
    `families/pangu_ultra_moe.py` counts it)."""
    return 2 * cfg["num_attention_heads"] * (cfg["head_dim"]
                                             + cfg["v_head_dim"])


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


def full_attn_flops(cfg, prompt_len: int) -> float:
    """The full layers' attention of one prompt's prefill: both products
    of the n (n + 1) / 2 causal pairs."""
    n = prompt_len
    return pair_flops(cfg) * full_layers(cfg) * (n * (n + 1) // 2)


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
    a window layer."""
    return 2 * matmul_params_per_token(cfg) + pair_flops(cfg) * (
        full_layers(cfg) * context_len
        + window_layers(cfg) * min(context_len, cfg["sliding_window"]))


def decode_bytes(cfg, live_context_tokens: float, rows: float,
                 hit: float = None, itemsize=BF16) -> float:
    """Bytes one decode step of `rows` rows must move: every matrix outside
    the routed experts once, the held experts HIT (`hit` a layer: counted
    by the program, else expected under uniform routing), one latent row a
    live token a full layer and rows x min(mean context, window) of them a
    window layer."""
    if hit is None:
        hit = experts_hit(cfg, rows)
    held_all = expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    # of the embedding a step reads one row a token, not the table
    hid = cfg["hidden_size"]
    read = param_count(cfg) - held_all - (cfg["vocab_size"] - rows) * hid \
        + expert_layers(cfg) * hit * expert_params(cfg)
    mean = live_context_tokens / rows if rows else 0.0
    return itemsize * read + cache_bytes_per_token(cfg, itemsize) * (
        full_layers(cfg) * live_context_tokens
        + window_layers(cfg) * rows * min(mean, cfg["sliding_window"]))


def hyper_bytes(cfg, tokens: float, itemsize=BF16) -> float:
    """The least bytes the residual mixes of `tokens` tokens must move,
    every sublayer of every layer: the read reads the n streams and writes
    the sublayer's input; the write reads the streams and the sublayer's
    output and writes the streams back (phi's 0.8 M a sublayer, read once a
    program, left out)."""
    n, c = cfg["mhc_expansion_rate"], cfg["hidden_size"]
    return itemsize * tokens * 2 * cfg["num_hidden_layers"] \
        * (3 * n * c + 2 * c)


def expert_pair_flops(cfg) -> float:
    """A (token, held expert) pair's products in a PolyNorm expert kernel:
    gate, up and down, a multiply-add each."""
    return 2 * expert_params(cfg)


def expert_read_bytes(cfg, experts: float, itemsize=BF16) -> float:
    """The weights a PolyNorm expert kernel reads for `experts` expert
    calls: the three matrices and the activation's four numbers each."""
    return itemsize * experts * (expert_params(cfg) + 4)
