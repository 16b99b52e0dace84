"""The system under test, as the benchmark reaches it.

The ONLY module of the benchmark that imports `paddle_tpu`. It goes through
the entry points a user calls — `models.GPTForCausalLM`,
`inference.ServingEngine` (`add_request`, `step`, `on_token`),
`models.build_train_step`, `optimizer.AdamW` — and sets no `FLAGS_*`.
Everything it hands the program (weights, prompts, batches) the benchmark
made from the seed.
"""
from __future__ import annotations

import gc
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as _weights

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def configure_compile_cache():
    """Keep XLA's persistent compile cache at ONE fixed path inside the
    checkout (`<checkout>/.jax_cache`, where `framework/compile_cache.py`
    puts it too when left alone), whatever `JAX_COMPILATION_CACHE_DIR`
    names, without a size limit and whatever a program cost to compile: a
    second run in the same checkout then compiles nothing. A directory
    handed in from outside may be capped (192 MiB on the chip machines, my
    chip runs, PR 24): the 32 prefill programs of one serving cell do not
    fit, least-recently-used eviction then misses every one of them on
    every run, and a run's set-up stays at 15 minutes."""
    import paddle_tpu  # noqa: F401  (its import configures jax first)
    from paddle_tpu.framework import compile_cache

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache


class _Placeholder:
    """An initializer that allocates nothing worth the name: the model's own
    initialisation (float32 normals on device 0, cast afterwards) would be
    thrown away at once, and for the larger configurations does not fit."""

    def __init__(self, dtype):
        self.dtype = jnp.dtype(dtype)

    def __call__(self, shape, dtype):
        return jnp.zeros(tuple(shape), self.dtype)


def build_model(cfg: dict, seed: int, train: bool, recompute: bool = False):
    """`GPTForCausalLM` at the configuration's sizes, holding the
    benchmark's seeded weights in the configuration's dtype."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import initializer

    gcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_recompute=recompute)
    placeholder = _Placeholder(cfg["dtype"])
    initializer.set_global_initializer(placeholder, placeholder)
    try:
        model = GPTForCausalLM(gcfg)
    finally:
        initializer.set_global_initializer(None, None)
    paddle.amp.decorate(model, level="O2", dtype=cfg["dtype"])
    model.train() if train else model.eval()
    set_weights(model, cfg, seed)
    return model


def set_weights(model, cfg: dict, seed: int):
    """Replace every parameter by the seed's weights. The old buffers are
    dropped first, so the peak is one copy of the model."""
    params = dict(model.named_parameters())
    specs = {name: shape for name, shape, _, _ in _weights.leaf_specs(cfg)}
    if set(specs) != set(params):
        raise RuntimeError(
            "the benchmark's weight table and the program's parameters "
            f"differ: {sorted(set(specs) ^ set(params))[:6]}")
    for name, p in params.items():
        if tuple(p.shape) != tuple(specs[name]):
            raise RuntimeError(f"{name}: {tuple(p.shape)} != {specs[name]}")
        p._rebind(None)
    new = _weights.make(cfg, seed, cfg["dtype"])
    for name, p in params.items():
        p._rebind(new[name])


def build_engine(model, engine_cfg: dict):
    from paddle_tpu.inference import ServingEngine

    return ServingEngine(
        model, max_batch=engine_cfg["max_batch"],
        max_seq_len=engine_cfg["max_seq_len"],
        page_size=engine_cfg["page_size"],
        decode_burst=engine_cfg["decode_burst"],
        decode_strategy="greedy_search")


def compile_entries() -> int:
    from paddle_tpu.framework import compile_cache

    return compile_cache.entry_count()


def build_trainer(model, trainer_cfg: dict):
    """(step, optimizer): the callable `build_train_step` returns, with
    AdamW as the configuration states it."""
    import paddle_tpu as paddle
    from paddle_tpu.models import build_train_step

    opt = paddle.optimizer.AdamW(
        learning_rate=trainer_cfg["learning_rate"],
        beta1=trainer_cfg["beta1"], beta2=trainer_cfg["beta2"],
        epsilon=trainer_cfg["epsilon"],
        weight_decay=trainer_cfg["weight_decay"],
        multi_precision=trainer_cfg["multi_precision"],
        parameters=model.parameters())
    return build_train_step(model, opt), opt


def to_tensor(array):
    import paddle_tpu as paddle

    return paddle.to_tensor(array)


def optimizer_state(step) -> dict:
    """name -> the optimizer's state dict for that parameter, as the step
    holds it now."""
    holder = getattr(step, "_opt_state_holder", None) or \
        step._inner._opt_state_holder
    return holder["state"]


def release(*objects):
    """Drop the program's device state (the caller deletes its own names)
    so the reference has the chip to itself."""
    for obj in objects:
        for attr in ("k_pages", "v_pages", "_params", "_buffers"):
            if hasattr(obj, attr):
                setattr(obj, attr, None)
        if hasattr(obj, "named_parameters"):
            for _, p in obj.named_parameters():
                p._rebind(None)
        holder = getattr(obj, "_opt_state_holder", None) or getattr(
            getattr(obj, "_inner", None), "_opt_state_holder", None)
        if holder is not None:
            holder["state"] = None
    gc.collect()


def prefill_rounds(engine, lo: int, hi: int) -> list:
    """[((requests, batch bucket, token bucket), a prompt length that
    lands there), ...] for every prefill of 1..max_batch prompts whose
    longest has lo..hi tokens — the engine's own policy is ASKED for each,
    not restated."""
    rounds = {}
    for n in range(1, engine.max_batch + 1):
        for length in range(lo, hi + 1):
            new = [(i, range(length)) for i in range(n)]
            nb, bucket = engine.scheduler.prefill_bucket(engine, new)
            rounds.setdefault((n, int(nb), int(bucket)), length)
    return sorted(rounds.items())


def engine_stats(engine) -> dict:
    """What the engine holds before a step: rows that will decode, the
    cached tokens they attend to, and the share of the page pool in use.
    Rows admitted by the step itself are not seen (they show in the
    next)."""
    live = [s for s in engine.slots if s.active and not s.prefilling]
    return {"rows": len(live),
            "kv_tokens": sum(s.context_len for s in live),
            "pages_used": 1.0 - len(engine._free_pages)
            / engine._n_pages_total}
