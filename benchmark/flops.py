"""Operations and bytes a GPT-family program NEEDS, from its shapes alone.

Nothing here reads `cost_analysis()`: that counts what one implementation
executes (recomputation, padding, dequantised copies) and changes when the
implementation does. These are the algorithm's numbers, so a roofline share
built on them falls when a program wastes work and cannot pass 100%.

`cfg` is a configuration file's dict (benchmark/configs/*.json): hidden_size,
num_hidden_layers, num_attention_heads, intermediate_size, vocab_size,
max_position_embeddings. A multiply-add is two operations.
"""
from __future__ import annotations

BF16 = 2  # bytes


def matmul_params(cfg) -> int:
    """Parameters that sit in a matrix product applied to every token: per
    layer QKV + output projection (4 h^2) and the GPT MLP's two matrices
    (2 h f; llama's gated MLP would have three), plus the tied head (v h).
    Embedding lookups, biases and LayerNorms multiply nothing."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f) \
        + cfg["vocab_size"] * h


def weight_bytes(cfg, itemsize=BF16) -> int:
    """Every parameter the serving step reads once per token step."""
    h, f, layers = (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"])
    per_layer = 4 * h * h + 2 * h * f + (3 * h + h + f + h) + 4 * h
    return itemsize * (layers * per_layer + cfg["vocab_size"] * h
                       + cfg["max_position_embeddings"] * h + 2 * h)


def forward_flops(cfg, new_tokens: int, attended: int) -> int:
    """Forward operations for `new_tokens` tokens of ONE sequence whose
    tokens together attend to `attended` (query, key) pairs: 2 per matmul
    parameter per token, and 4 h per pair and layer (QK^T and AV)."""
    return 2 * matmul_params(cfg) * new_tokens \
        + 4 * cfg["hidden_size"] * cfg["num_hidden_layers"] * attended


def prefill_flops(cfg, prompt_len: int, head_tokens: int = 1) -> int:
    """One prompt through the model: causal attention touches
    n (n + 1) / 2 pairs; the head is needed for the last position only."""
    n = prompt_len
    body = forward_flops(cfg, n, n * (n + 1) // 2)
    return body - 2 * cfg["vocab_size"] * cfg["hidden_size"] * (n - head_tokens)


def decode_flops(cfg, context_len: int) -> int:
    """One new token of one sequence against `context_len` cached tokens
    (itself included)."""
    return forward_flops(cfg, 1, context_len)


def decode_bytes(cfg, live_context_tokens: int, itemsize=BF16) -> int:
    """Bytes one decode step must move: every weight once, and the keys
    and values of every live cached token (2 h per token and layer)."""
    kv = 2 * cfg["hidden_size"] * cfg["num_hidden_layers"] * itemsize
    return weight_bytes(cfg, itemsize) + kv * live_context_tokens


def train_flops_per_token(cfg, seq_len: int) -> int:
    """Forward + backward for one token of a `seq_len` causal sequence:
    6 per matmul parameter, and 12 h per layer per attended pair with
    (seq_len + 1) / 2 pairs a token on average. Recomputation is not
    counted: it is work the implementation chose, not work the model
    needs."""
    attn = 12 * cfg["hidden_size"] * cfg["num_hidden_layers"] \
        * (seq_len + 1) // 2
    return 6 * matmul_params(cfg) + attn


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
