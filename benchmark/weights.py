"""Seeded GPT-family weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test and the plain
reference are both GIVEN this module's output for the run's seed, so the
reference takes nothing the program made. Leaves carry the names
`GPTForCausalLM.named_parameters()` uses, because the program is handed the
dict as it is; the reference reads the same names.

Distribution (GPT-2/3's published initialisation, arXiv:2005.14165 §2.1 via
GPT-2): matrices and token embeddings N(0, 0.02), the two residual output
projections N(0, 0.02 / sqrt(2 L)), positions N(0, 0.01). Departure, so that
a dropped bias or LayerNorm gain shows in the comparison: biases are
N(0, 0.02) instead of 0, gains 1 + N(0, 0.02) instead of 1.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def leaf_specs(cfg) -> tuple:
    """((name, shape, mean, std), ...) in `named_parameters()` order."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    out_std = 0.02 / math.sqrt(2 * layers)
    specs = [("gpt.embed_tokens.weight", (cfg["vocab_size"], h), 0.0, 0.02),
             ("gpt.embed_positions.weight",
              (cfg["max_position_embeddings"], h), 0.0, 0.01)]
    for i in range(layers):
        p = f"gpt.layers.{i}."
        specs += [
            (p + "ln_1.weight", (h,), 1.0, 0.02),
            (p + "ln_1.bias", (h,), 0.0, 0.02),
            (p + "attn.qkv_proj.weight", (h, 3 * h), 0.0, 0.02),
            (p + "attn.qkv_proj.bias", (3 * h,), 0.0, 0.02),
            (p + "attn.out_proj.weight", (h, h), 0.0, out_std),
            (p + "attn.out_proj.bias", (h,), 0.0, 0.02),
            (p + "ln_2.weight", (h,), 1.0, 0.02),
            (p + "ln_2.bias", (h,), 0.0, 0.02),
            (p + "mlp.fc_in.weight", (h, f), 0.0, 0.02),
            (p + "mlp.fc_in.bias", (f,), 0.0, 0.02),
            (p + "mlp.fc_out.weight", (f, h), 0.0, out_std),
            (p + "mlp.fc_out.bias", (h,), 0.0, 0.02),
        ]
    specs += [("gpt.ln_f.weight", (h,), 1.0, 0.02),
              ("gpt.ln_f.bias", (h,), 0.0, 0.02)]
    return tuple(specs)


@functools.lru_cache(maxsize=None)
def _maker(specs: tuple, dtype_name: str):
    dtype = jnp.dtype(dtype_name)

    def make(seed):
        key = jax.random.fold_in(jax.random.key(20050514), seed)
        out = {}
        for i, (name, shape, mean, std) in enumerate(specs):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (mean + std * z).astype(dtype)
        return out

    return jax.jit(make)


def seed_array(seed: int):
    """The run's seed as the uint32 the jitted programs take: a runtime
    argument, so another seed compiles nothing. Seeds above 2**32 wrap."""
    return jnp.asarray(int(seed) % (1 << 32), jnp.uint32)


def make(cfg, seed: int, dtype="bfloat16") -> dict:
    """name -> array of `dtype`, the same for the same (cfg sizes, seed)."""
    return _maker(leaf_specs(cfg), jnp.dtype(dtype).name)(seed_array(seed))
