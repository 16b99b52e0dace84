#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # no arguments; needs a TPU

ONE process that takes the chip itself (it starts no other process) and
drives the two main paths through the entry points a user calls —
`import paddle_tpu as paddle`, `models.GPTForCausalLM`,
`models.build_train_step`, `inference.ServingEngine` — at the published
widths of GPT-3 1.3B (`GPTConfig.gpt3_1p3b()`: hidden 2048, 16 heads x 128,
FFN 8192, vocab 50,304, 2,048 learned positions). No width is cut. Depth is
cut only where one chip cannot hold the optimizer state (the train phase
says by how much). Weights are random, from a seed.

Phases, in order; the first failed check or uncaught exception ends the run
non-zero, and no phase is wrapped in a handler that lets the next one start:

  device    platform must be `tpu`; device_kind must be in the peak table;
            block_until_ready must wait for the device
  serve     24 layers, bf16: warmup, more greedy requests than slots, exact
            budgets, no decode recompile, logits-level agreement with the
            model's own dense forward
  train     AdamW, batch 4 x seq 2048, five steps on one fixed batch
  kernels   every Pallas kernel in paddle_tpu/kernels/, compiled by Mosaic
            (interpret=False asserted), against its XLA reference
  serve/pallas  the engine again at a page of 128 tokens, where its own
            decode takes the Pallas paged kernel (the serve phase's pages
            of 16 take the XLA gather)
  four chips    only when >= 4 devices are visible: tp=4 train (12 layers
            against the one-chip losses, then all 24), tp=4 engine at both
            page sizes, and a check that every device holds its share

Times, compile seconds and peak HBM are printed for the record. They are
not metrics: no rate is derived from them and none belongs in a document.

The last stdout line is `{"ok": true, "device": {...}}` with the device as
jax reports it. Without an accelerator the script exits non-zero before it
builds anything and prints no result.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import sys
import time

import numpy as np

# --- tolerances, each with its reason -------------------------------------

# A kernel result is bf16 (8 significant bits): one ulp is 2^-8 of a value's
# magnitude. Four ulps of the reference's largest magnitude: one for storing
# the result, the rest for the bf16 passes the MXU makes inside f32 dots.
# Each case prints its worst; on the chip none has read over two.
KERNEL_TOL = 4 * 2.0 ** -8

# Greedy tokens of a random-weight model flip on rounding, so token equality
# proves nothing. The engine's token must instead be within LOGIT_TOL of the
# dense forward's best logit at its position. A logit here is the final
# LayerNorm output (unit variance, hidden 2048) against a row of the tied
# XavierNormal embedding (std sqrt(2 / (50304 + 2048))): sigma 0.28, the
# best of 50k near 1.2, where a bf16 ulp is 2^-7. The paged path (f32
# softmax over gathered pages) and the dense one (bf16 probabilities) round
# differently through 24 layers; eight ulps. A wrong token sits about 1.2
# below the maximum.
LOGIT_TOL = 8 * 2.0 ** -7

# The loss of a bf16 O2 model is itself a bf16 value: near 11 its ulp is
# 2^-4 = 0.0625. tp=4 splits every row-parallel reduction into four partial
# sums and AdamW's normalised update amplifies grad rounding from step to
# step; two ulps.
TP_LOSS_TOL = 2 * 2.0 ** -4

# First loss of a random-weight model: ln(vocab) plus half the logit
# variance, which for the tied XavierNormal embedding is hidden / (vocab +
# hidden) (`expected_first_loss`). Two bf16 ulps at the loss's magnitude.
FIRST_LOSS_TOL = 2 * 2.0 ** -4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is cut to. `full()` is the contract; `tiny()` exists so
    the CPU tests can drive the same phase code (tests/test_chip_smoke.py)."""
    full_width: bool
    serve_batch: int
    serve_seq: int
    page: int
    burst: int
    n_requests: int
    prompt_lo: int
    prompt_hi: int
    new_tokens: int
    pallas_requests: int
    pallas_new_tokens: int
    train_layers: int
    train_batch: int
    train_seq: int
    train_steps: int
    tp_full_steps: int

    @staticmethod
    def full():
        return Sizes(full_width=True, serve_batch=8, serve_seq=2048,
                     page=16, burst=16, n_requests=12, prompt_lo=64,
                     prompt_hi=1024, new_tokens=64, pallas_requests=4,
                     pallas_new_tokens=32, train_layers=12,
                     train_batch=4, train_seq=2048,
                     train_steps=5, tp_full_steps=3)

    @staticmethod
    def tiny():
        return Sizes(full_width=False, serve_batch=2, serve_seq=128,
                     page=16, burst=4, n_requests=3, prompt_lo=8,
                     prompt_hi=48, new_tokens=8, pallas_requests=2,
                     pallas_new_tokens=6, train_layers=1,
                     train_batch=2, train_seq=32,
                     train_steps=5, tp_full_steps=2)

    def config(self, layers=None, recompute=False):
        from paddle_tpu.models import GPTConfig

        cfg = GPTConfig.gpt3_1p3b() if self.full_width else \
            GPTConfig.tiny(vocab=128, hidden=64, layers=2, heads=4, seq=128)
        if layers is not None:
            cfg.num_hidden_layers = layers
        cfg.use_recompute = recompute
        return cfg


def check(ok, what):
    """One named check: print it when it holds, end the run when not."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def say(text):
    print(text, flush=True)


def hbm(jax):
    """'[device] bytes_in_use/peak_bytes_in_use' in GB, every local device
    (zeros where the allocator reports nothing: the CPU under test)."""
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return " ".join(
        f"[{i}] {st.get('bytes_in_use', 0) / 1e9:.2f}"
        f"/{st.get('peak_bytes_in_use', 0) / 1e9:.2f}"
        for i, st in enumerate(stats))


def release(jax):
    """Drop what a phase left on the device before the next one sizes
    itself against the same 16 GB."""
    gc.collect()
    say(f"  HBM in use/peak GB after release: {hbm(jax)}")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase():
    say("== device")
    import jax

    devs = jax.devices()
    dev = devs[0]
    say(f"  platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: jax found no TPU (platform={dev.platform!r}); "
            f"nothing was built and nothing is reported")

    import jaxlib
    from importlib import metadata

    import paddle_tpu as paddle
    from paddle_tpu import kernels
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.observability import device_peaks

    say(f"  jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={metadata.version('libtpu')} "
        f"python={sys.version.split()[0]}")
    # everything that compiles is cached, whatever it cost: a second run on
    # the same cache directory must then add no entry (with jax's default
    # one-second threshold a compile near it is cached in one run and not
    # in the other)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    say(f"  compile cache: {compile_cache.cache_dir()} "
        f"({compile_cache.entry_count()} entries at start; "
        f"{compile_cache.ENV_VAR} "
        f"{'set' if os.environ.get(compile_cache.ENV_VAR) else 'not set'})")

    kind = device_peaks.require_kind(dev.device_kind)  # unknown: an error
    peak = device_peaks.PEAK_FLOPS_BF16[kind]
    say(f"  peak table: {kind}: {peak / 1e12:.0f} TFLOP/s bf16, "
        f"{device_peaks.PEAK_HBM_BYTES_PER_S[kind] / 1e9:.0f} GB/s HBM "
        f"(published; observability/device_peaks.py)")
    check(kernels.interpret() is False,
          "Pallas kernels compile under Mosaic here (interpret=False)")
    check(paddle.get_device().startswith("tpu"),
          f"paddle.get_device() reports {paddle.get_device()}")
    sync_check(jax, peak)
    paddle.set_flags({"FLAGS_compilewatch": True})
    return jax, paddle


def sync_check(jax, peak_flops):
    """`block_until_ready` must return only when the device is done: every
    timing taken on this chip rests on it (observability/stepledger.py
    blocks with it and nothing else). The chained matmuls below cannot
    finish faster than their FLOPs over the chip's published peak, so a
    block that returns sooner did not wait."""
    import jax.numpy as jnp

    n, length = 4096, 64

    @jax.jit
    def chain(x):
        # the identity stays the identity, so values stay bounded; x is an
        # argument, so nothing folds away
        return jax.lax.scan(lambda c, _: (c @ c, None), x, None,
                            length=length)[0]

    x = jnp.eye(n, dtype=jnp.bfloat16)
    chain(x).block_until_ready()  # compile
    floor = length * 2 * n ** 3 / peak_flops
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    y.block_until_ready()
    t2 = time.perf_counter()
    corner = float(y[0, 0])
    t3 = time.perf_counter()
    say(f"  sync: dispatch returned after {1e3 * (t1 - t0):.2f} ms, "
        f"block_until_ready after {1e3 * (t2 - t0):.2f} ms, host read "
        f"after the block took {1e3 * (t3 - t2):.2f} ms; the work needs "
        f">= {1e3 * floor:.2f} ms at peak")
    check(corner == 1.0, "chained matmul result is right")
    check(t2 - t0 >= floor,
          "block_until_ready waited for the device (returned no sooner "
          "than the work can run at the chip's peak)")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def build_lm(paddle, cfg, train):
    """GPTForCausalLM at `cfg`, random weights from seed 0, bf16 O2 — the
    same weights every time it is called with the same cfg."""
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    model.train() if train else model.eval()
    return model


def make_requests(sizes, vocab, n):
    rng = np.random.RandomState(1)
    lens = rng.randint(sizes.prompt_lo, sizes.prompt_hi + 1, size=n)
    return [rng.randint(0, vocab, (int(k),)) for k in lens]


def logit_gaps(paddle, model, prompt, out_tokens):
    """How far below the dense forward's best logit each engine token
    sits, teacher-forced on the engine's own stream."""
    ids = np.concatenate([prompt, out_tokens])
    dense = paddle.jit.to_static(model.forward)  # one program, not per-op
    with paddle.no_grad():
        logits = dense(paddle.to_tensor(ids[None]))
    rows = np.asarray(
        logits._data[0, len(prompt) - 1:len(ids) - 1].astype("float32"))
    chosen = rows[np.arange(len(out_tokens)), out_tokens]
    return rows.max(-1) - chosen


def run_engine(jax, paddle, sizes, model, prompts, new_tokens, label,
               mesh=None):
    """warmup, answer `prompts`, check budgets / vocabulary / recompiles /
    logits-level agreement. Returns the token streams by request order."""
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.observability import compilewatch

    vocab = model.config.vocab_size
    t0 = time.perf_counter()
    engine = ServingEngine(model, max_batch=sizes.serve_batch,
                           max_seq_len=sizes.serve_seq,
                           page_size=sizes.page, decode_burst=sizes.burst,
                           mesh=mesh)
    warm_s = engine.warmup()
    recompiles0 = compilewatch.recompiles("serving.decode")
    t1 = time.perf_counter()
    rids = [engine.add_request(p, max_new_tokens=new_tokens)
            for p in prompts]
    finished = {f.request_id: np.asarray(f.output_ids)
                for f in engine.run()}
    t2 = time.perf_counter()
    say(f"  {label}: build+warmup {t1 - t0:.1f} s (warmup {warm_s:.1f} s), "
        f"{len(prompts)} requests in {t2 - t1:.1f} s, prompt lengths "
        f"{[len(p) for p in prompts]}")
    check(sorted(finished) == sorted(rids),
          f"{label}: all {len(rids)} requests came back (more requests "
          f"than the {sizes.serve_batch} slots: {len(rids) > sizes.serve_batch})")
    check(all(len(finished[r]) == new_tokens for r in rids),
          f"{label}: every request returned exactly {new_tokens} tokens")
    check(all(((finished[r] >= 0) & (finished[r] < vocab)).all()
              for r in rids),
          f"{label}: every token is inside the vocabulary of {vocab}")
    recompiles = compilewatch.recompiles("serving.decode") - recompiles0
    check(recompiles == 0,
          f"{label}: no decode recompile after warmup ({recompiles})")
    # the shortest prompt keeps the dense forward small
    k = int(np.argmin([len(p) for p in prompts]))
    gaps = logit_gaps(paddle, model, prompts[k], finished[rids[k]])
    say(f"  {label}: request {k} (prompt {len(prompts[k])}): "
        f"{int((gaps == 0).sum())}/{len(gaps)} tokens are the dense "
        f"forward's argmax, largest gap {gaps.max():.4f}")
    check(float(gaps.max()) <= LOGIT_TOL,
          f"{label}: prefill-then-decode agrees with the dense forward: "
          f"every token within {LOGIT_TOL} of the best logit")
    streams = [finished[r] for r in rids]
    del engine
    return streams


def serve_phase(jax, paddle, sizes):
    cfg = sizes.config()
    say(f"== serve: GPTForCausalLM {cfg.num_hidden_layers} layers, hidden "
        f"{cfg.hidden_size}, vocab {cfg.vocab_size}, bf16; "
        f"ServingEngine(max_batch={sizes.serve_batch}, "
        f"max_seq_len={sizes.serve_seq}, page_size={sizes.page}, "
        f"decode_burst={sizes.burst})")
    model = build_lm(paddle, cfg, train=False)
    prompts = make_requests(sizes, cfg.vocab_size, sizes.n_requests)
    streams = run_engine(jax, paddle, sizes, model, prompts,
                         sizes.new_tokens, "serve")
    say(f"  HBM in use/peak GB: {hbm(jax)}")
    del model
    release(jax)
    say("PASSED serve")
    return prompts, streams


def run_engine_on_pallas(jax, paddle, sizes, prompts, label, mesh=None):
    """The same engine at a page that fills a K tile, as the benchmark's
    cells serve: the engine's own choice is then the Pallas paged kernel
    (counted at trace time), not the XLA gather."""
    from paddle_tpu.kernels import paged_attention as pa

    cfg = sizes.config()
    model = build_lm(paddle, cfg, train=False)
    sizes = dataclasses.replace(
        sizes, page=max(sizes.page, pa._KERNEL_MIN_PAGE))
    traced = count_calls(pa, "paged_attention")
    try:
        run_engine(jax, paddle, sizes, model,
                   prompts[:sizes.pallas_requests],
                   sizes.pallas_new_tokens, label, mesh=mesh)
    finally:
        n = traced.restore()
    check(n >= cfg.num_hidden_layers,
          f"{label}: the decode programs traced the Pallas paged kernel "
          f"({n} times, >= one per layer)")
    del model
    release(jax)


def serve_pallas_phase(jax, paddle, sizes, prompts):
    say(f"== serve/pallas: same engine at a page of 128 "
        f"(mapped context {sizes.serve_seq})")
    run_engine_on_pallas(jax, paddle, sizes, prompts, "serve/pallas")
    say("PASSED serve/pallas")


class count_calls:
    """Count calls of `module.name` (trace-time calls, for a function that
    only runs under jit) until `restore()`."""

    def __init__(self, module, name):
        self.module, self.name, self.n = module, name, 0
        self.orig = getattr(module, name)

        def counted(*a, **kw):
            self.n += 1
            return self.orig(*a, **kw)

        setattr(module, name, counted)

    def restore(self):
        setattr(self.module, self.name, self.orig)
        return self.n


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_batch(sizes, vocab):
    rng = np.random.RandomState(0)
    shape = (sizes.train_batch, sizes.train_seq)
    return rng.randint(0, vocab, shape), rng.randint(0, vocab, shape)


def run_train(jax, paddle, sizes, layers, steps, label, mesh=None):
    """build_train_step (with use_recompute) on one fixed batch. Returns
    the losses and the trained model."""
    from paddle_tpu.models import build_train_step
    from paddle_tpu.observability import compilewatch

    cfg = sizes.config(layers=layers, recompute=True)
    model = build_lm(paddle, cfg, train=True)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # GPT-3 1.3B's published learning rate
    opt = paddle.optimizer.AdamW(learning_rate=2e-4,
                                 parameters=model.parameters())
    step = build_train_step(model, opt, mesh=mesh)
    x, y = train_batch(sizes, cfg.vocab_size)
    x, y = paddle.to_tensor(x), paddle.to_tensor(y)
    compiles0 = compilewatch.snapshot().get("jit.train_step", {}) \
        .get("compiles", 0)
    losses, t0 = [], time.perf_counter()
    for i in range(steps):
        losses.append(float(step(x, y)))  # float() waits for the step
        if i == 0:
            first_s = time.perf_counter() - t0
    total_s = time.perf_counter() - t0
    compiles = compilewatch.snapshot()["jit.train_step"]["compiles"] \
        - compiles0
    say(f"  {label}: {layers} layers, {n_params / 1e9:.3f} B parameters, "
        f"use_recompute, batch {sizes.train_batch} x seq "
        f"{sizes.train_seq}; first step (with compile) {first_s:.1f} s, "
        f"all {steps} steps {total_s:.1f} s")
    say(f"  {label}: losses {[round(v, 4) for v in losses]}")
    say(f"  {label}: HBM in use/peak GB: {hbm(jax)}")
    want = expected_first_loss(cfg)
    check(all(math.isfinite(v) for v in losses),
          f"{label}: every loss is finite")
    check(abs(losses[0] - want) <= FIRST_LOSS_TOL,
          f"{label}: first loss {losses[0]:.3f} is near ln(vocab) + half "
          f"the logit variance = {want:.3f}")
    check(losses[-1] < losses[0],
          f"{label}: last loss {losses[-1]:.3f} is below the first")
    check(compiles == 1, f"{label}: one compile only ({compiles})")
    return losses, model


def expected_first_loss(cfg):
    """Cross entropy of logits ~ N(0, s^2) against any label: ln(vocab) +
    s^2 / 2, with s^2 = hidden * 2 / (vocab + hidden) for a unit-variance
    hidden state against the tied XavierNormal embedding."""
    return math.log(cfg.vocab_size) + cfg.hidden_size / (
        cfg.vocab_size + cfg.hidden_size)


def train_phase(jax, paddle, sizes):
    say(f"== train: GPT-3 1.3B widths, depth cut to {sizes.train_layers} "
        f"of 24 layers (one 16 GB chip cannot hold bf16 weights + f32 "
        f"AdamW moments for all 24: that is 13 GB before activations)"
        if sizes.full_width else "== train (tiny)")
    losses, model = run_train(jax, paddle, sizes, sizes.train_layers,
                              sizes.train_steps, "train")
    del model
    release(jax)
    say("PASSED train")
    return losses


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """`fn(*args)` runs the Pallas kernel; `ref(*args)` is its plain XLA
    reference, traced at the highest matmul precision."""
    name: str
    make_args: object   # np.random.Generator -> tuple of host arrays
    fn: object
    ref: object


def _bf16(rng, shape, scale=1.0):
    import ml_dtypes

    return (rng.standard_normal(shape, dtype=np.float32) * scale).astype(
        ml_dtypes.bfloat16)


def kernel_cases(heads=16, head_dim=128, hidden=2048, ffn=8192,
                 flash_seq=4096, tokens=8192, decode_batch=8,
                 pages_per_seq=160, wide=8192, expert_hidden=7680,
                 expert_width=2048, experts_held=16, gqa_seq=2048,
                 gqa_kv_heads=8, gqa_group=8):
    """Every Pallas kernel entry point of paddle_tpu/kernels/, by default
    at this model's head geometry. tests/test_kernels_compile_tpu.py
    compiles the same table ahead of time for a v5e, so a Mosaic refusal
    shows in the sandbox before it costs chip time; tests/test_chip_smoke.py
    runs it small in interpret mode, so a wrong REFERENCE shows there
    too."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.distributed.models.moe import expert_share
    from paddle_tpu.kernels import expert_grouped as eg
    from paddle_tpu.kernels import expert_hit as eh
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.kernels import quant_matmul as qm
    from paddle_tpu.kernels import rms_norm as rn

    f32 = jnp.float32
    scale = 1.0 / math.sqrt(head_dim)

    def qkv(seq):
        return lambda rng: tuple(_bf16(rng, (1, seq, heads, head_dim))
                                 for _ in range(3))

    def dense_attention(q, k, v, keep=None, segments=None):
        """softmax(q k^T) v, causal, [b, s, h, d], in f32."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32),
                       k.astype(f32)) * scale
        n = s.shape[-1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        if segments is not None:
            mask = mask & (segments[:, None] == segments[None, :])
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if keep is not None:
            p = jnp.where(keep[0], p, 0.0) / keep[1]
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(f32))

    def grads(f):
        """(q, k, v) -> d/d(q,k,v) of sum(f(q,k,v) * cot), cot fixed."""
        def g(q, k, v):
            cot = jnp.cos(jnp.arange(q.size, dtype=f32)).reshape(q.shape)
            return jax.grad(
                lambda *a: jnp.sum(f(*a).astype(f32) * cot),
                argnums=(0, 1, 2))(q, k, v)
        return g

    flash = lambda q, k, v: fa.flash_attention_bshd(q, k, v, causal=True)

    # -- flash with in-kernel dropout: the reference evaluates the same
    # counter-based mask in plain XLA over the whole score matrix
    drop_rate, drop_seed, drop_seq = 0.1, 1234, flash_seq // 2

    def flash_drop(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True,
                                       dropout=drop_rate,
                                       dropout_seed=drop_seed)

    def dense_drop(q, k, v):
        keep = jax.vmap(lambda bh: fa._dropout_keep(
            jnp.int32(drop_seed), bh, 0, 0, drop_seq, drop_seq,
            drop_rate))(jnp.arange(heads, dtype=jnp.int32))
        return dense_attention(q, k, v, keep=(keep[None], 1.0 - drop_rate))

    # -- varlen: four packed sequences, flash_seq tokens (from 4096 on the
    # backward is the streamed Pallas pair, not the XLA recompute)
    cu = (np.asarray([0, 600, 2000, 3400, 4096]) * flash_seq
          // 4096).astype(np.int32)
    longest = int(np.diff(cu).max())

    def varlen_args(rng):
        return tuple(_bf16(rng, (int(cu[-1]), heads, head_dim))
                     for _ in range(3))

    def varlen(q, k, v):
        return fa.flash_attn_unpadded(q, k, v, cu, cu, longest, longest,
                                      causal=True)[0]

    def dense_varlen(q, k, v):
        seg = jnp.searchsorted(jnp.asarray(cu[1:]),
                               jnp.arange(int(cu[-1])), side="right")
        return dense_attention(q[None], k[None], v[None],
                               segments=seg)[0]

    # -- paged decode; by default above the engine's XLA crossover
    # (mapped context 2560, every row longer than 2048)
    batch, page = decode_batch, 16
    n_pages = batch * pages_per_seq
    mapped = page * pages_per_seq

    def paged_args(quant):
        def make(rng):
            q = _bf16(rng, (batch, heads, head_dim))
            kp = _bf16(rng, (heads, n_pages, page, head_dim))
            vp = _bf16(rng, (heads, n_pages, page, head_dim))
            tables = rng.permutation(n_pages).astype(np.int32).reshape(
                batch, pages_per_seq)
            lens = rng.integers(mapped * 4 // 5 + 1, mapped + 1,
                                size=batch).astype(np.int32)
            if not quant:
                return q, kp, vp, tables, lens
            # np.array, not asarray: a view would keep the device array
            kq, ks = (np.array(a) for a in
                      pa._quant_kv_token(jnp.asarray(kp)))
            vq, vs = (np.array(a) for a in
                      pa._quant_kv_token(jnp.asarray(vp)))
            pad = ((0, 0), (0, 0), (0, pa._SCALE_LANES - page))
            return (q, kq, vq, np.pad(ks, pad), np.pad(vs, pad),
                    tables, lens)
        return make

    def paged_q8(impl):
        return lambda q, kp, vp, ks, vs, tables, lens: impl(
            q, kp, vp, tables, lens, k_scales=ks, v_scales=vs)

    # -- a window layer whose keys are wider than its values, with a sink a
    # head in the softmax (mimo-v2.5-ep16-l11: 8 kv heads of 8 query heads,
    # keys of 192 over values of 128, a window of 128): its prefill, and its
    # decode step over rings of 2 pages of 256 that store a key 256 wide
    # and score it by 192
    sink_heads, window = gqa_kv_heads * gqa_group, 128

    def sink_of(rng):
        return rng.standard_normal(sink_heads, dtype=np.float32)

    def gqa_args(rng):
        return (_bf16(rng, (1, gqa_seq, sink_heads, 192)),
                _bf16(rng, (1, gqa_seq, gqa_kv_heads, 192)),
                _bf16(rng, (1, gqa_seq, gqa_kv_heads, 128)), sink_of(rng))

    def gqa_window(q, k, v, sink):
        return fa.flash_attention_gqa_bshd(q, k, v, window=window, sink=sink)

    def dense_window(q, k, v, sink):
        """The same in f32: a sink is one more column of the softmax."""
        n = q.shape[1]
        kk = jnp.repeat(k.astype(f32), gqa_group, axis=2)
        vv = jnp.repeat(v.astype(f32), gqa_group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), kk) / math.sqrt(192)
        back = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
        s = jnp.where((back >= 0) & (back < window), s, -jnp.inf)
        column = jnp.broadcast_to(sink[None, :, None, None], s.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, column], -1), -1)[..., :n]
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    def ring_args(rng):
        rows = jnp.arange(decode_batch)
        seen = rng.integers(1, 3000, size=decode_batch).astype(np.int32)
        tables, read, first = (np.array(a) for a in pa.ring_view(
            rows, 2, 256, jnp.asarray(seen), window))
        return (_bf16(rng, (decode_batch, sink_heads, 256)),
                _bf16(rng, (gqa_kv_heads, decode_batch * 2, 256, 256)),
                _bf16(rng, (gqa_kv_heads, decode_batch * 2, 256, 128)),
                tables, read, first, sink_of(rng))

    def ring_decode(impl):
        return lambda q, kp, vp, tables, read, first, sink: impl(
            q, kp, vp, tables, read, first=first, sink=sink,
            scale=1.0 / math.sqrt(192))

    # -- the expert layer of a decode step (16 rows through the held
    # experts of openpangu-ultra-moe-ep16-l5) with 0, 1, 3/8 and all of the
    # held experts hit; expert e's matrices are one random matrix rolled by
    # e rows, distinct and cheap to make on the host
    def expert_args(rng):
        def stack(rows, cols):
            base = _bf16(rng, (rows, cols), 0.02)
            return np.stack([np.roll(base, 37 * e, axis=0)
                             for e in range(experts_held)])
        routing = (0.2 + rng.random((16, experts_held))).astype(np.float32)
        return (_bf16(rng, (16, expert_hidden)), routing,
                stack(expert_hidden, expert_width),
                stack(expert_hidden, expert_width),
                stack(expert_width, expert_hidden))

    def grouped_args(picks):
        """A prefill's 1,280 tokens (tiles of 256 rows), each on `picks`
        of the held experts: one pair a token as the cells' routers give,
        or 8, the most a token can make."""
        def make(rng):
            _, _, *stacks = expert_args(rng)
            routing = np.zeros((1280, experts_held), np.float32)
            for row in routing:
                row[rng.choice(experts_held, picks, replace=False)] = \
                    0.2 + rng.random(picks)
            return (_bf16(rng, (1280, expert_hidden)), routing, *stacks)
        return make

    def experts_hit(n_hit, ffn):
        """`ffn` with the routing weights of all but `n_hit` experts
        (spread over the held ones) set to 0."""
        hit = np.zeros(experts_held, np.float32)
        hit[np.linspace(0, experts_held - 1, n_hit).astype(int)] = 1.0
        return lambda x, routing, *ws: ffn(x, routing * hit, *ws)

    # -- rms_norm at the train step's token count, quantized matmuls
    def rms_args(cols):
        return lambda rng: (_bf16(rng, (tokens, cols)),
                            _bf16(rng, (cols,), 0.1) + 1)

    def rms_ref(x, w):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w.astype(f32)

    def rms_grads(f):
        def g(x, w):
            cot = jnp.cos(jnp.arange(x.size, dtype=f32)).reshape(x.shape)
            return jax.grad(lambda *a: jnp.sum(f(*a).astype(f32) * cot),
                            argnums=(0, 1))(x, w)
        return g

    def qmm_args(weight_dtype):
        def make(rng):
            import paddle_tpu as paddle
            from paddle_tpu.nn.quant import weight_quantize

            w = rng.standard_normal((hidden, ffn), dtype=np.float32) * 0.02
            qw, sc = weight_quantize(
                paddle.to_tensor(w), algo=f"weight_only_{weight_dtype}")
            return (_bf16(rng, (decode_batch, hidden)),
                    np.array(qw._data), np.array(sc._data))
        return make

    def qmm(weight_dtype):
        return lambda x, qw, sc: qm.quant_matmul_fused(
            x, qw, sc, weight_dtype=weight_dtype)

    def qmm_ref(weight_dtype):
        return lambda x, qw, sc: jnp.matmul(
            x.astype(f32), qm.dequantize(qw, sc, weight_dtype, f32))

    # one make_args per operand set: its fwd and fwd+bwd cases share it
    flash_args, drop_args = qkv(flash_seq), qkv(drop_seq)
    paged, paged_quant = paged_args(False), paged_args(True)
    rms_hidden, rms_wide = rms_args(hidden), rms_args(wide)
    return [
        KernelCase("flash fwd", flash_args, flash, dense_attention),
        KernelCase("flash fwd+bwd", flash_args, grads(flash),
                   grads(dense_attention)),
        KernelCase("flash dropout fwd", drop_args, flash_drop, dense_drop),
        KernelCase("flash dropout fwd+bwd", drop_args, grads(flash_drop),
                   grads(dense_drop)),
        KernelCase("flash varlen fwd", varlen_args, varlen, dense_varlen),
        KernelCase("flash varlen fwd+bwd", varlen_args, grads(varlen),
                   grads(dense_varlen)),
        KernelCase("paged decode bf16", paged, pa.paged_attention,
                   pa.paged_attention_xla),
        KernelCase("paged decode int8-KV", paged_quant,
                   paged_q8(pa.paged_attention),
                   paged_q8(pa.paged_attention_xla)),
        KernelCase("gqa prefill window + sink, key 192 / value 128",
                   gqa_args, gqa_window, dense_window),
        KernelCase("paged decode window + sink, key 256 / value 128",
                   ring_args, ring_decode(pa.paged_attention),
                   ring_decode(pa.paged_attention_xla)),
        *(KernelCase(f"expert hit {n_hit} of {experts_held}", expert_args,
                     experts_hit(n_hit, eh.hit_ffn),
                     experts_hit(n_hit, expert_share.share_ffn))
          for n_hit in (0, 1, experts_held * 3 // 8, experts_held)),
        *(KernelCase(f"expert grouped {picks} a token", grouped_args(picks),
                     eg.grouped_ffn, expert_share.share_ffn)
          for picks in (1, 8)),
        KernelCase(f"rms_norm {hidden} fwd", rms_hidden, rn.rms_norm,
                   rms_ref),
        KernelCase(f"rms_norm {hidden} fwd+bwd", rms_hidden,
                   rms_grads(rn.rms_norm), rms_grads(rms_ref)),
        KernelCase(f"rms_norm {wide} fwd+bwd", rms_wide,
                   rms_grads(rn.rms_norm), rms_grads(rms_ref)),
        KernelCase("quant_matmul_fused int8", qmm_args("int8"), qmm("int8"),
                   qmm_ref("int8")),
        KernelCase("quant_matmul_fused int4", qmm_args("int4"), qmm("int4"),
                   qmm_ref("int4")),
    ]


def pallas_interpret_flags(fn, *args):
    """The `interpret` parameter of every pallas_call `fn` traces to."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["interpret"])
                continue
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) \
                        else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def reference_error(jax, case, args):
    """Run kernel and reference on `args`; the largest |difference| over
    the reference's largest magnitude, over all outputs. The reference
    sees the same values in f32 (integer operands — tables, lengths,
    quantized weights — as they are) at the highest matmul precision."""
    import jax.numpy as jnp

    got = jax.block_until_ready(jax.jit(case.fn)(*args))
    ref_args = tuple(a.astype(jnp.float32)
                     if jnp.issubdtype(a.dtype, jnp.floating) else a
                     for a in args)
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(case.ref)(*ref_args))
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g.astype(jnp.float32))
        w = np.asarray(w.astype(jnp.float32))
        if g.shape != w.shape or not np.isfinite(g).all():
            raise SystemExit(f"chip_smoke: FAILED: {case.name}: output "
                             f"{g.shape} vs reference {w.shape}, finite "
                             f"{bool(np.isfinite(g).all())}")
        worst = max(worst, float(np.abs(g - w).max()
                                 / max(np.abs(w).max(), 1e-6)))
    return worst


def kernels_phase(jax):
    say("== kernels: every Pallas kernel, Mosaic-compiled, against its XLA "
        f"reference (tolerance {KERNEL_TOL:.4f} of the reference's "
        "largest magnitude)")
    for case in kernel_cases():
        rng = np.random.default_rng(7)
        args = tuple(jax.device_put(a) for a in case.make_args(rng))
        flags = pallas_interpret_flags(case.fn, *args)
        check(flags and not any(flags),
              f"{case.name}: {len(flags)} pallas_call(s), interpret=False")
        t0 = time.perf_counter()
        worst = reference_error(jax, case, args)
        check(worst <= KERNEL_TOL,
              f"{case.name}: finite, agrees with the XLA reference "
              f"(worst {worst:.5f}; {time.perf_counter() - t0:.1f} s)")
        del args
    release(jax)
    say("PASSED kernels")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def device_bytes_in_use(jax):
    """Each local device's allocator reading."""
    return [d.memory_stats()["bytes_in_use"] for d in jax.local_devices()]


def spread_check(jax, model, label):
    """State must be spread over all four devices, not resident on the
    first: read every device's allocator and the parameters' shards
    (observability/memwatch.py reads device 0 only and is no witness)."""
    used = device_bytes_in_use(jax)[:4]
    share = [u / max(sum(used), 1) for u in used]
    say(f"  {label}: bytes in use per device GB "
        f"{[round(u / 1e9, 2) for u in used]}")
    check(min(share) >= 0.15 and max(share) <= 0.40,
          f"{label}: every device holds between 15% and 40% of the bytes "
          f"in use ({[round(s, 3) for s in share]})")
    arrays = [p._data for p in model.parameters()]
    check(all(len({s.device for s in a.addressable_shards}) == 4
              for a in arrays),
          f"{label}: each of the {len(arrays)} parameters has a shard on "
          f"each of the 4 devices")
    total = sum(a.nbytes for a in arrays)
    split = sum(a.nbytes for a in arrays
                if a.addressable_shards[0].data.shape != a.shape)
    check(split / total >= 0.9,
          f"{label}: {100 * split / total:.1f}% of the parameter bytes are "
          f"split over tp (the rest — norms, biases of row-parallel "
          f"layers, positions — is replicated)")


def tp_train_phase(jax, paddle, sizes, mesh, one_chip_losses):
    # (a) the one-chip train run again, at equal depth, on the same batch
    losses, model = run_train(jax, paddle, sizes, sizes.train_layers,
                              sizes.train_steps, "tp4 train (a)",
                              mesh=mesh)
    deltas = [abs(a - b) for a, b in zip(losses, one_chip_losses)]
    say(f"  tp4 train (a): |loss - one-chip loss| per step "
        f"{[round(d, 4) for d in deltas]}")
    check(max(deltas) <= TP_LOSS_TOL,
          f"tp4 train (a): losses agree with the one-chip run within "
          f"{TP_LOSS_TOL}")
    del model
    release(jax)

    # (b) all 24 layers: BASELINE.json configs[2]
    full_layers = sizes.config().num_hidden_layers
    _, model = run_train(jax, paddle, sizes, full_layers,
                         sizes.tp_full_steps, "tp4 train (b)", mesh=mesh)
    spread_check(jax, model, "tp4 train (b)")
    del model
    release(jax)


def tp_serve_phase(jax, paddle, sizes, mesh, prompts, one_chip_streams):
    # (c) the engine on the XLA decode path, answering the same requests
    model = build_lm(paddle, sizes.config(), train=False)
    streams = run_engine(jax, paddle, sizes, model, prompts,
                         sizes.new_tokens, "tp4 serve (c, XLA decode)",
                         mesh=mesh)
    same = sum(int((a == b).all()) for a, b in
               zip(streams, one_chip_streams))
    say(f"  tp4 serve: {same}/{len(streams)} token streams equal the "
        f"one-chip engine's (tokens flip on rounding; not a check)")
    spread_check(jax, model, "tp4 serve (c)")
    del model
    release(jax)


def four_chip_phase(jax, paddle, sizes, one_chip_losses, prompts,
                    one_chip_streams):
    import paddle_tpu.distributed.mesh as mesh_mod

    say("== four chips: one process, one Mesh, tp=4")
    mesh = mesh_mod.init_mesh(tp=4)
    say(f"  mesh {dict(mesh.shape)} over "
        f"{[d.id for d in mesh.devices.flat]}")
    tp_train_phase(jax, paddle, sizes, mesh, one_chip_losses)
    tp_serve_phase(jax, paddle, sizes, mesh, prompts, one_chip_streams)
    # (c) again at a page of 128: Pallas inside the shard_map
    run_engine_on_pallas(jax, paddle, sizes, prompts,
                         "tp4 serve (c, Pallas decode in shard_map)",
                         mesh=mesh)
    mesh_mod.set_mesh(None)
    say("PASSED four chips")


# ---------------------------------------------------------------------------


def main():
    t_start = time.perf_counter()
    jax, paddle = device_phase()
    from paddle_tpu.framework import compile_cache

    sizes = Sizes.full()
    prompts, streams = serve_phase(jax, paddle, sizes)
    losses = train_phase(jax, paddle, sizes)
    kernels_phase(jax)
    serve_pallas_phase(jax, paddle, sizes, prompts)
    if len(jax.devices()) >= 4:
        four_chip_phase(jax, paddle, sizes, losses, prompts, streams)
    else:
        say(f"== four chips: skipped, {len(jax.devices())} device(s) "
            f"visible")
    dev = jax.devices()[0]
    say(f"compile cache: {compile_cache.cache_dir()} holds "
        f"{compile_cache.entry_count()} entries at the end")
    say(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t_start:.0f} s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}),
          flush=True)


if __name__ == "__main__":
    main()
