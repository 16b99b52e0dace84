"""Benchmark entry point: ONE configuration per invocation, measured on
the TPU this process holds.

    python bench.py             # needs a TPU; fails without one
    python bench.py --smoke     # tiny CPU correctness run, prints no rate

`BENCH_CONFIG` selects the configuration (llama | resnet | serving); the
`BENCH_*` variables below size and tune it. The last stdout line is one
JSON object. A measured row carries the device it ran on (`platform`,
`device_kind`, `devices`) next to the metric; a `--smoke` row carries
correctness facts only (losses, token counts, recompiles) — a CPU run
never fills a device metric. Any failure is a non-zero exit: there is no
CPU fallback, no cached row and no error row that exits 0. The process
touches jax itself and starts no child that needs the chip.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np


def model_flops_per_token(cfg, seq_len, causal=True):
    """6*N (fwd+bwd matmul flops per token per param) + attention term."""
    h = cfg.hidden_size
    l = cfg.num_hidden_layers
    v = cfg.vocab_size
    inter = cfg.intermediate_size
    # params in matmuls per layer: qkv+o (4 h^2) + mlp (3 h*inter)
    per_layer = 4 * h * h + 3 * h * inter
    n_matmul = l * per_layer + v * h  # + lm_head
    flops = 6 * n_matmul
    # attention scores/values: QK^T + AV, fwd 4*s*h, fwd+bwd 12*s*h per
    # token per layer for full attention; the model is causal so the honest
    # achieved-flops count is half that (avg context length s/2)
    attn = 12 * seq_len * h * l
    flops += attn // 2 if causal else attn
    return flops


def _overlap_efficiency(entry):
    """The run's measured collective overlap share (hidden / raw wait
    seconds) from the stepledger aggregate — None when no collective
    wait was observed (single-device runs)."""
    from paddle_tpu.observability import stepledger as _sl

    a = _sl.snapshot().get(entry) or {}
    raw = float(a.get("coll_raw", 0.0))
    return round(float(a.get("coll_hidden", 0.0)) / raw, 4) \
        if raw > 0 else None


def _observability_columns():
    """The memory/compile columns of a row: the run's peak device bytes
    (allocator high-water mark; live-sweep max on CPU) and total XLA
    compiles attributed to watched callables."""
    from paddle_tpu.observability import compilewatch, memwatch

    return {"peak_hbm_bytes": int(memwatch.peak_hbm_bytes()),
            "compiles": int(compilewatch.total_compiles())}


def _git_commit():
    """Short HEAD commit of the repo this file lives in ("unknown" when
    git or its metadata is absent — a chip-tool copy has neither, so
    nothing may depend on the value)."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
        return out or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _append_history(result):
    """Append a MEASURED row (+ commit, date) to BENCH_HISTORY.jsonl next
    to this script — the trajectory tools/bench_compare.py gates
    against. Smoke rows carry no metric and are not recorded."""
    row = dict(result)
    row.setdefault("commit", _git_commit())
    row.setdefault("date",
                   time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_HISTORY.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")


def _device_columns(jax):
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "devices": len(jax.devices()),
            # the key tools/bench_compare.py matches rows on
            "backend": d.platform}


def _require(ok, what):
    if not ok:
        raise SystemExit(f"bench.py: check failed: {what}")


def main(argv):
    smoke = "--smoke" in argv
    import jax

    dev = jax.devices()[0]
    if not smoke and dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU and found platform="
            f"{dev.platform!r} ({dev.device_kind}). There is no CPU "
            f"fallback; `python bench.py --smoke` is the CPU correctness "
            f"run (it prints no rate).")

    import paddle_tpu as paddle

    # memwatch/compilewatch ride along so every row carries
    # peak_hbm_bytes + compiles (their on-path cost on the chip: ROADMAP
    # S6, not measured)
    paddle.set_flags({"FLAGS_memwatch": True, "FLAGS_compilewatch": True})

    which = os.environ.get("BENCH_CONFIG", "llama")
    run = {"llama": bench_llama, "resnet": bench_resnet,
           "serving": bench_serving}.get(which)
    if run is None:
        raise SystemExit(f"BENCH_CONFIG={which!r}: expected llama | "
                         f"resnet | serving")
    metric, unit, amount, seconds, facts = run(paddle, jax, smoke)
    facts.update(_device_columns(jax))
    facts.update(_observability_columns())
    if smoke:
        return {"smoke": True, "config": which, "ok": True,
                "checks": facts}
    return {"metric": metric, "value": round(amount / seconds, 2),
            "unit": unit, "extra": facts}


def bench_llama(paddle, jax, smoke):
    """The LLaMA-family train step (the llama presets leave the benchmark
    with the `benchmark` PR, ROADMAP S1/R1)."""
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   build_train_step)

    size = os.environ.get("BENCH_MODEL", "base")
    if smoke:
        cfg = LlamaConfig.tiny(vocab=512, hidden=128, layers=2, heads=4,
                               seq=128)
        batch, seq, iters = 4, 128, 5
    elif size == "1b":
        # the largest LLaMA that fits one 16 GB chip with AdamW state
        # (~0.74B params)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=12,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=2048, dtype="bfloat16")
        cfg.scan_layers = os.environ.get("BENCH_SCAN_LAYERS", "1") == "1"
        batch, seq, iters = 4, 2048, 10
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024, dtype="bfloat16")
        batch, seq, iters = 32, 1024, 20
    # tuning overrides (tools/mfu_sweep.py drives these)
    batch = int(os.environ.get("BENCH_BATCH", batch))
    seq = int(os.environ.get("BENCH_SEQ", seq))
    iters = int(os.environ.get("BENCH_ITERS", iters))
    if seq > cfg.max_position_embeddings:
        cfg.max_position_embeddings = seq
    if "BENCH_RECOMPUTE" in os.environ:
        cfg.use_recompute = os.environ["BENCH_RECOMPUTE"] == "1"
    if size != "1b" and "BENCH_SCAN_LAYERS" in os.environ:
        cfg.scan_layers = os.environ["BENCH_SCAN_LAYERS"] == "1"
    if "BENCH_FUSED_CE" in os.environ:
        # chunked fused head+CE: logits never materialize
        cfg.fused_ce_chunks = int(os.environ["BENCH_FUSED_CE"])

    # overlap engine knobs (ISSUE 12): BENCH_OVERLAP=0 reverts to the
    # per-param grad sync; bucket/prefetch sizes are comparability keys
    # too. Stepledger rides along (block cadence pushed past the run so
    # it never syncs mid-timing) purely to measure overlap_efficiency.
    overlap = os.environ.get("BENCH_OVERLAP", "1") == "1"
    grad_bucket_mb = int(os.environ.get("BENCH_GRAD_BUCKET_MB", "25"))
    prefetch_depth = int(os.environ.get("BENCH_PREFETCH_DEPTH", "2"))
    paddle.set_flags({"FLAGS_train_overlap": overlap,
                      "FLAGS_grad_bucket_mb": grad_bucket_mb,
                      "FLAGS_prefetch_depth": prefetch_depth,
                      "FLAGS_stepledger": True,
                      "FLAGS_stepledger_block_every": 1_000_000})

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if not smoke:
        # bf16 weights: MXU-native (SURVEY.md "MXU")
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = build_train_step(model, opt)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    y = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))

    loss_first = float(step(x, y))  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss_last = float(loss)  # blocks
    dt = time.perf_counter() - t0
    _require(math.isfinite(loss_first) and math.isfinite(loss_last),
             f"loss not finite ({loss_first}, {loss_last})")
    _require(loss_last < loss_first,
             f"loss did not fall on a fixed batch "
             f"({loss_first} -> {loss_last})")

    facts = {
        "batch": batch, "seq": seq, "hidden": cfg.hidden_size,
        "layers": cfg.num_hidden_layers,
        # tuning knobs mfu_sweep varies at identical geometry —
        # recorded so bench_compare never judges a canonical run
        # against a sweep variant's row (or vice versa)
        "recompute": bool(getattr(cfg, "use_recompute", False)),
        "scan_layers": bool(getattr(cfg, "scan_layers", False)),
        "fused_ce": int(getattr(cfg, "fused_ce_chunks", 0) or 0),
        "params_b": round(
            sum(int(np.prod(p.shape)) for p in model.parameters()) / 1e9,
            3),
        "loss_first": round(loss_first, 4),
        "loss_last": round(loss_last, 4),
        "overlap": bool(overlap), "grad_bucket_mb": grad_bucket_mb,
        "prefetch_depth": prefetch_depth,
        "overlap_efficiency": _overlap_efficiency("train.step"),
    }
    n_dev = len(jax.devices())
    tokens = batch * seq * iters
    if not smoke:
        from paddle_tpu.observability import device_peaks as _dp

        # an unknown device_kind is an error here, never another chip's
        # peak
        kind = _dp.require_kind(jax.devices()[0].device_kind)
        peak_chip = _dp.PEAK_FLOPS_BF16[kind]
        mfu = (tokens / dt) * model_flops_per_token(cfg, seq) \
            / (peak_chip * n_dev)
        facts.update({
            "mfu": round(mfu, 4),
            "mfu_note": (f"causal model flops vs "
                         f"{peak_chip / 1e12:.0f} TFLOPs bf16 peak "
                         f"(observability/device_peaks.py)"),
            "peak_flops_per_chip": peak_chip})
    return ("llama_train_tokens_per_sec_per_chip", "tokens/s/chip",
            tokens / n_dev, dt, facts)


def bench_resnet(paddle, jax, smoke):
    """ResNet50 images/sec, data-parallel layout (single-chip here; dp
    axis over all visible devices)."""
    if smoke:
        depth, batch, size, iters = 18, 8, 32, 2
    else:
        depth, batch, size, iters = 50, 64, 224, 10
    paddle.seed(0)
    net = getattr(paddle.vision.models, f"resnet{depth}")()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=net.parameters())
    ce = paddle.nn.CrossEntropyLoss()
    from paddle_tpu.jit import train_step as _ts

    step = _ts(net, lambda out, y: ce(out, y), opt)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 3, size, size).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)))
    loss_first = float(step(x, y))  # compile + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss_last = float(loss)  # host sync; steps chain through donated params
    dt = time.perf_counter() - t0
    # finite only: this row's loss RISES on the chip record there is
    # (ROADMAP S5) — to be fixed or explained before the cell is kept
    _require(math.isfinite(loss_first) and math.isfinite(loss_last),
             f"loss not finite ({loss_first}, {loss_last})")
    facts = {"depth": depth, "batch": batch, "image": size,
             "loss_first": round(loss_first, 4),
             "loss_last": round(loss_last, 4)}
    return ("resnet_train_images_per_sec", "images/s", batch * iters, dt,
            facts)


def bench_serving(paddle, jax, smoke):
    """Continuous-batching decode throughput over the paged KV cache
    (FusedMultiTransformer serving parity)."""
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import compilewatch as _cwatch

    size = os.environ.get("BENCH_SERVING_MODEL", "base")
    if smoke:
        cfg = LlamaConfig.tiny(vocab=256, hidden=64, layers=2, heads=2,
                               seq=64)
        max_batch, prompt_len, new_tokens = 2, 8, 8
    elif size == "3b":
        # 2.2B-param proxy for the LLaMA-2-7B intent: bf16 weights
        # (4.4 GB) fit one v5e, then weight-only quant
        # (BENCH_SERVING_QUANT) halves/quarters them
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2560,
                          intermediate_size=6912, num_hidden_layers=26,
                          num_attention_heads=20, num_key_value_heads=20,
                          max_position_embeddings=2048, dtype="bfloat16")
        max_batch, prompt_len, new_tokens = 8, 128, 128
    else:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=2048, dtype="bfloat16")
        max_batch, prompt_len, new_tokens = 8, 128, 128
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    # count BEFORE weight-only quant repacks [k,n] into nibble/byte pools
    params_b = round(sum(int(np.prod(p.shape))
                         for p in model.parameters()) / 1e9, 3)
    if not smoke:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    # BENCH_SERVING_QUANT=weight_only_int8|weight_only_int4 swaps the
    # projection weights to quantized HBM storage
    quant = os.environ.get("BENCH_SERVING_QUANT", "")
    if quant:
        from paddle_tpu.nn.quant import quantize_for_inference

        quantize_for_inference(model, algo=quant, exclude=("lm_head",))
    # BENCH_SERVING_KV=int8 stores KV pages as int8 + per-token scales
    kv_quant = os.environ.get("BENCH_SERVING_KV", "") or None
    # multi-step scheduling: K decode iterations per compiled call (one
    # host sync per burst)
    burst = int(os.environ.get("BENCH_SERVING_BURST",
                               "4" if smoke else "16"))
    # BENCH_SERVING_ASYNC=N keeps N bursts in flight (device-side decode
    # carry)
    async_depth = int(os.environ.get("BENCH_SERVING_ASYNC", "0"))
    # BENCH_SERVING_SPEC=W: self-speculative decoding with a W-token
    # verify window (greedy-exact; BENCH_SERVING_SPEC_LAYERS overrides
    # the shallow-exit draft depth). Spec and async are mutually
    # exclusive — spec wins when both are set.
    spec = int(os.environ.get("BENCH_SERVING_SPEC", "0"))
    spec_layers = int(os.environ.get("BENCH_SERVING_SPEC_LAYERS", "0"))
    if spec:
        async_depth = 0
    engine = ServingEngine(model, max_batch=max_batch,
                           max_seq_len=prompt_len + new_tokens,
                           page_size=16, decode_strategy="greedy_search",
                           decode_burst=burst, kv_cache_quant=kv_quant,
                           async_depth=async_depth,
                           spec_decode=spec or None,
                           spec_draft_layers=spec_layers or None)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (prompt_len,))
               for _ in range(max_batch)]
    # warmup: engine.warmup() compiles the single-token-prefill bucket +
    # both decode programs; a throwaway FULL batch then compiles the real
    # traffic shape (nb=max_batch, bucket=prompt_len prefill) so no XLA
    # compile lands inside the timed region
    engine.warmup(prompt_len=prompt_len)
    for p in prompts:
        engine.add_request(p, max_new_tokens=4)
    engine.run()
    t0 = time.perf_counter()
    for p in prompts:
        engine.add_request(p, max_new_tokens=new_tokens)
    finished = engine.run()
    dt = time.perf_counter() - t0
    generated = sum(len(f.output_ids) for f in finished)
    recompiles = int(_cwatch.recompiles("serving.decode"))
    _require(len(finished) == len(prompts)
             and generated == len(prompts) * new_tokens,
             f"{len(finished)} requests returned {generated} tokens; "
             f"expected {len(prompts)} x {new_tokens}")
    _require(recompiles == 0,
             f"{recompiles} decode recompile(s) after warmup")
    facts = {"requests": len(finished), "batch": max_batch,
             "prompt_len": prompt_len, "new_tokens": new_tokens,
             "generated_tokens": generated,
             "decode_burst": burst, "async_depth": async_depth,
             "quant": quant or None, "kv_quant": kv_quant,
             "spec_decode": engine.spec_decode or None,
             "draft_layers": engine.spec_draft_layers
             if engine.spec_decode else None,
             "acceptance_rate": round(
                 engine._spec_accepted_total
                 / engine._spec_proposed_total, 4)
             if engine._spec_proposed_total else None,
             "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
             "params_b": params_b, "decode_recompiles": recompiles,
             "replicas": 1, "router_policy": None}
    return ("serving_decode_tokens_per_sec", "tokens/s", generated, dt,
            facts)


if __name__ == "__main__":
    result = main(sys.argv[1:])
    if not result.get("smoke"):
        _append_history(result)
    print(json.dumps(result))
