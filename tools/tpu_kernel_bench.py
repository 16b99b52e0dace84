"""Validate + benchmark the Pallas kernels on the TPU this process holds.

Runs flash fwd and fwd+bwd at a sweep of sequence lengths, paged decode,
rms_norm and the fused matmul on the chip, checks numerics against the
XLA reference (paddle layout [b, s, h, d]), and prints the timing table
the dispatch thresholds in nn/functional/attention.py are set from.
Refuses to start off a TPU (interpret-mode timings mean nothing) and
exits non-zero if any section failed; partial rows still reach --json.

Usage (through the chip tool): python tools/tpu_kernel_bench.py [--quick]
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def xla_sdpa(q, k, v, causal):
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d)
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, vt), 1, 2)


def _first_leaf(out):
    return jax.tree_util.tree_leaves(out)[0]


def timeit(fn, q, *rest, iters=20):
    """Device-time measurement: iterate INSIDE one program via lax.scan.

    `iters` kernel executions run inside ONE jitted lax.scan — one
    dispatch, one program, serialized iterations (the carry folds each
    output back into the next input so iterations can neither be elided
    nor overlapped) — so the per-iteration quotient is device time with
    the per-dispatch host cost amortized iters-fold; identical machinery
    times the Pallas and XLA variants so the comparison stays fair.
    """

    @jax.jit
    def many(q0, *rest_):
        def body(carry, _):
            out = fn(carry, *rest_)
            # serialize: next input depends on EVERY output leaf — fn is
            # inlined here, so a leaf the carry ignores is dead code XLA
            # will eliminate (e.g. dk/dv of a grad tuple, biasing the
            # backward comparison toward whichever variant can be
            # partially DCE'd). Scale by a runtime-tiny factor (not
            # literal 0.0, which the algebraic simplifier may fold) so
            # the carry stays q0-valued with realistic data.
            total = sum(jnp.sum(leaf).astype(jnp.float32)
                        for leaf in jax.tree_util.tree_leaves(out))
            dep = total * jnp.float32(1e-30)
            return carry + dep.astype(carry.dtype), None

        return jax.lax.scan(body, q0, None, length=iters)[0]

    jax.block_until_ready(many(q, *rest))  # compile + first execution
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        out = many(q, *rest)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (reps * iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None,
                    help="write rows incrementally to this JSON file "
                         "(partial results survive a timeout kill)")
    ap.add_argument("--no-autotune", action="store_true",
                    help="skip the autotune candidate-table section "
                         "(per-candidate timings incl. both flash bwd "
                         "strategies)")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"tpu_kernel_bench: needs a TPU, found platform="
                 f"{dev.platform!r} ({dev.device_kind}); run it through "
                 f"the chip tool")

    from paddle_tpu.kernels import flash_attention as fa

    backend = f"{dev.platform}:{dev.device_kind}"
    print(f"backend={backend} devices={jax.devices()}", file=sys.stderr)
    failed = []  # sections that raised: recorded, reported, then exit 1

    def _dump(path, backend_, rows_, extra_=None):
        """Incremental JSON write: partial results survive a timeout kill
        (the --json contract)."""
        if not path:
            return
        payload = {"backend": backend_, "kernel": "flash_attention",
                   "rows": rows_}
        if extra_ is not None:
            payload["extra"] = extra_
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)  # atomic: a mid-write kill never corrupts

    seqs = [512, 1024, 2048] if args.quick else [512, 1024, 2048, 4096, 8192]
    b, h, d = 4, 8, 128
    causal = True
    rows = []
    for s in seqs:
        # the binding memory constraint is the XLA REFERENCE's f32 score
        # matrix (b*h*s^2*4 bytes, twice live in its backward), not the
        # inputs: cap it at ~2 GB so the comparison fits a 16 GB chip
        # (seq 8192 at b=4 OOMed with an 8 GB scores temp, round 4)
        b_eff = b
        while b_eff > 1 and b_eff * h * s * s * 4 > 2 * 2**30:
            b_eff //= 2
        key = jax.random.PRNGKey(0)
        kq, kk, kv, kg = jax.random.split(key, 4)
        shape = (b_eff, s, h, d)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        do = jax.random.normal(kg, shape, jnp.bfloat16)

        flash = jax.jit(functools.partial(fa.flash_attention_bshd,
                                          causal=causal))
        ref = jax.jit(functools.partial(xla_sdpa, causal=causal))

        # --- forward numerics ---
        o_f = np.asarray(flash(q, k, v), dtype=np.float32)
        o_r = np.asarray(ref(q, k, v), dtype=np.float32)
        fwd_err = float(np.max(np.abs(o_f - o_r)))

        # --- backward numerics (force the Pallas bwd regardless of the
        # dispatch threshold, so seq<4096 also validates it) ---
        def loss_flash(q_, k_, v_):
            return jnp.sum(flash(q_, k_, v_).astype(jnp.float32) *
                           do.astype(jnp.float32))

        def loss_ref(q_, k_, v_):
            return jnp.sum(ref(q_, k_, v_).astype(jnp.float32) *
                           do.astype(jnp.float32))

        saved = fa._PALLAS_BWD_MIN_SEQ
        try:
            fa._PALLAS_BWD_MIN_SEQ = 0  # force Pallas backward
            g_f = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
            bwd_errs = []
            g_r = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
            for a_, b_ in zip(g_f, g_r):
                bwd_errs.append(float(np.max(np.abs(
                    np.asarray(a_, np.float32) - np.asarray(b_, np.float32)))))
            bwd_err = max(bwd_errs)

            # --- timing ---
            t_flash_f = timeit(flash, q, k, v)
            t_ref_f = timeit(ref, q, k, v)
            gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
            gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))
            t_flash_b = timeit(gf, q, k, v)
            t_ref_b = timeit(gr, q, k, v)
            fa._PALLAS_BWD_MIN_SEQ = 10**9  # force XLA-recompute bwd
            gx = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
            t_mixed_b = timeit(gx, q, k, v)
        finally:
            fa._PALLAS_BWD_MIN_SEQ = saved

        rows.append(dict(seq=s, b=b_eff, fwd_err=fwd_err, bwd_err=bwd_err,
                         t_flash_fwd=t_flash_f * 1e3, t_xla_fwd=t_ref_f * 1e3,
                         t_flash_bwd=t_flash_b * 1e3, t_xla_bwd=t_ref_b * 1e3,
                         t_mixed_bwd=t_mixed_b * 1e3))
        _dump(args.json, backend, rows)
        r = rows[-1]
        print(f"seq={s:5d} b={b_eff}  fwd_err={fwd_err:.4f} "
              f"bwd_err={bwd_err:.4f}  "
              f"fwd: pallas {r['t_flash_fwd']:.2f}ms xla {r['t_xla_fwd']:.2f}ms "
              f"({r['t_xla_fwd']/r['t_flash_fwd']:.2f}x) | "
              f"grad: pallas {r['t_flash_bwd']:.2f}ms "
              f"mixed {r['t_mixed_bwd']:.2f}ms xla {r['t_xla_bwd']:.2f}ms")
    print("\nsummary (speedup = xla_time / pallas_time):")
    for r in rows:
        print(f"  seq {r['seq']:5d}: fwd {r['t_xla_fwd']/r['t_flash_fwd']:.2f}x"
              f"  full-grad {r['t_xla_bwd']/r['t_flash_bwd']:.2f}x"
              f"  vs-mixed {r['t_mixed_bwd']/r['t_flash_bwd']:.2f}x")

    # --- paged decode + rms_norm: the other two Pallas families on the
    # real Mosaic compiler
    extra = {}
    try:
        from paddle_tpu.kernels import paged_attention as pa

        b_dec, kvh, hd = 8, 8, 128
        f_pal = jax.jit(pa.paged_attention)
        f_xla = jax.jit(pa.paged_attention_xla)
        # ctx sweep: locates the dense-gather vs page-grid crossover that
        # paged_attention_dispatch's _XLA_DECODE_MAX_CTX encodes. Each
        # ctx also runs with 128-token pages: one page per grid step
        # means page_size IS the K-block, so 16-token pages starve the
        # MXU 8-fold while 128-token pages feed it full 128x128 tiles
        # (the engine supports either; fragmentation is the trade).
        rows_dec = []
        for page, ppseq in ((16, 64), (128, 8),      # 1k mapped ctx
                            (16, 256), (128, 32),    # 4k
                            (16, 512), (128, 64)):   # 8k
            n_pages = b_dec * ppseq
            key = jax.random.PRNGKey(1)
            kq, kk2, kv2 = jax.random.split(key, 3)
            qd = jax.random.normal(kq, (b_dec, kvh, hd), jnp.bfloat16)
            kp = jax.random.normal(kk2, (kvh, n_pages, page, hd),
                                   jnp.bfloat16)
            vp = jax.random.normal(kv2, (kvh, n_pages, page, hd),
                                   jnp.bfloat16)
            tables = jnp.arange(n_pages, dtype=jnp.int32).reshape(
                b_dec, ppseq)
            lens = jnp.full((b_dec,), page * ppseq - 3, jnp.int32)
            o_p = np.asarray(f_pal(qd, kp, vp, tables, lens), np.float32)
            o_x = np.asarray(f_xla(qd, kp, vp, tables, lens), np.float32)
            paged_err = float(np.max(np.abs(o_p - o_x)))
            t_p = timeit(f_pal, qd, kp, vp, tables, lens)
            t_x = timeit(f_xla, qd, kp, vp, tables, lens)
            row = dict(
                err_vs_xla=paged_err, t_pallas_ms=t_p * 1e3,
                t_xla_ms=t_x * 1e3, ctx=page * ppseq, page_size=page,
                batch=b_dec)
            if page == 16 and ppseq % pa._GROUP_PAGES == 0:
                # grouped-fetch kernel: G pages per step via HBM DMA
                f_grp = jax.jit(pa.paged_attention_grouped)
                o_g = np.asarray(f_grp(qd, kp, vp, tables, lens),
                                 np.float32)
                row["grouped_err"] = float(np.max(np.abs(o_g - o_x)))
                row["t_grouped_ms"] = timeit(
                    f_grp, qd, kp, vp, tables, lens) * 1e3
            rows_dec.append(row)
            extra_g = (f" grouped {row['t_grouped_ms']:.3f}ms"
                       if "t_grouped_ms" in row else "")
            print(f"paged decode ctx={page*ppseq:5d} page={page:3d}: "
                  f"err={paged_err:.4f}"
                  f" pallas {t_p*1e3:.3f}ms xla {t_x*1e3:.3f}ms "
                  f"({t_x/t_p:.2f}x){extra_g}")
            # bank into `extra` itself so a later failure (next ctx, q8
            # variant) can't drop already-measured rows at the final dump
            extra["paged_decode"] = rows_dec
            _dump(args.json, backend, rows, extra)

        # int8-KV variant: the quant BlockSpecs lower differently (4D
        # scale tiles) — interpret mode can't catch Mosaic tiling rejects,
        # so the real-compiler run here is the coverage that matters.
        # Rebuilt at the 1024-token context explicitly (NOT the sweep
        # loop's last geometry): comparable to prior rounds and far from
        # the XLA reference's dense-dequant OOM regime.
        page, ppseq = 16, 64
        n_pages = b_dec * ppseq
        key = jax.random.PRNGKey(1)
        kq, kk2, kv2 = jax.random.split(key, 3)
        qd = jax.random.normal(kq, (b_dec, kvh, hd), jnp.bfloat16)
        kp = jax.random.normal(kk2, (kvh, n_pages, page, hd), jnp.bfloat16)
        vp = jax.random.normal(kv2, (kvh, n_pages, page, hd), jnp.bfloat16)
        tables = jnp.arange(n_pages, dtype=jnp.int32).reshape(b_dec, ppseq)
        lens = jnp.full((b_dec,), page * ppseq - 3, jnp.int32)
        kpq = (kp * 127).astype(jnp.int8)
        vpq = (vp * 127).astype(jnp.int8)
        sc = jnp.full((kvh, n_pages, 128), 1.0 / 127, jnp.float32)
        o_pq = np.asarray(f_pal(qd, kpq, vpq, tables, lens,
                                k_scales=sc, v_scales=sc), np.float32)
        o_xq = np.asarray(f_xla(qd, kpq, vpq, tables, lens,
                                k_scales=sc, v_scales=sc), np.float32)
        q_err = float(np.max(np.abs(o_pq - o_xq)))

        def paged_q8(qq, kp_, vp_, tb_, ln_, s1, s2):
            return pa.paged_attention(qq, kp_, vp_, tb_, ln_,
                                      k_scales=s1, v_scales=s2)

        t_pq = timeit(paged_q8, qd, kpq, vpq, tables, lens, sc, sc)
        extra["paged_decode_q8"] = dict(
            err_vs_xla=q_err, t_pallas_ms=t_pq * 1e3,
            ctx=page * ppseq, batch=b_dec)
        print(f"paged decode int8-kv: err={q_err:.4f} "
              f"pallas {t_pq*1e3:.3f}ms")
    except Exception as e:  # noqa: BLE001 — record, don't kill the sweep
        # separate key: a late failure (e.g. the q8 variant) must not
        # clobber ctx-sweep rows already banked under "paged_decode"
        extra["paged_decode_error"] = f"{type(e).__name__}: {e}"[:300]
        failed.append("paged_decode")
        print(f"paged decode FAILED: {e}", file=sys.stderr)
    _dump(args.json, backend, rows, extra)

    try:
        from paddle_tpu.kernels import rms_norm as rn

        rows_n, cols_n = 8192, 4096
        key = jax.random.PRNGKey(2)
        xr = jax.random.normal(key, (rows_n, cols_n), jnp.bfloat16)
        wr = jnp.ones((cols_n,), jnp.bfloat16)
        f_pal = jax.jit(rn.rms_norm)

        def ref_rms(x_, w_):
            xf = x_.astype(jnp.float32)
            r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1,
                                       keepdims=True) + 1e-6)
            return (xf * r * w_.astype(jnp.float32)).astype(x_.dtype)

        f_xla = jax.jit(ref_rms)
        o_p = np.asarray(f_pal(xr, wr), np.float32)
        o_x = np.asarray(f_xla(xr, wr), np.float32)
        rms_err = float(np.max(np.abs(o_p - o_x)))
        t_p = timeit(f_pal, xr, wr)
        t_x = timeit(f_xla, xr, wr)
        extra["rms_norm"] = dict(err_vs_xla=rms_err, t_pallas_ms=t_p * 1e3,
                                 t_xla_ms=t_x * 1e3,
                                 shape=[rows_n, cols_n])
        print(f"rms_norm: err={rms_err:.5f} pallas {t_p*1e3:.3f}ms "
              f"xla {t_x*1e3:.3f}ms ({t_x/t_p:.2f}x)")
    except Exception as e:  # noqa: BLE001
        extra["rms_norm"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        failed.append("rms_norm")
        print(f"rms_norm FAILED: {e}", file=sys.stderr)
    _dump(args.json, backend, rows, extra)

    try:
        from paddle_tpu.kernels import matmul as mm

        # MLP-shaped matmul (ISSUE 12): tokens x hidden @ hidden x ffn —
        # the largest compute bucket of the train step per the stepledger
        # waterfall. Time the default fused blocks against the XLA
        # lowering; the autotune section below races the full block grid.
        m_mm, k_mm, n_mm = 4096, 4096, 16384
        key = jax.random.PRNGKey(3)
        kx, kw2 = jax.random.split(key)
        xm = jax.random.normal(kx, (m_mm, k_mm), jnp.bfloat16)
        wm = jax.random.normal(kw2, (k_mm, n_mm), jnp.bfloat16) * 0.02
        f_pal = jax.jit(functools.partial(mm.matmul_fused,
                                          block_n=256, block_k=256))
        f_xla = jax.jit(mm.matmul_xla)
        o_p = np.asarray(f_pal(xm, wm), np.float32)
        o_x = np.asarray(f_xla(xm, wm), np.float32)
        mm_err = float(np.max(np.abs(o_p - o_x)))
        t_p = timeit(f_pal, xm, wm)
        t_x = timeit(f_xla, xm, wm)
        extra["matmul"] = dict(err_vs_xla=mm_err, t_pallas_ms=t_p * 1e3,
                               t_xla_ms=t_x * 1e3,
                               shape=[m_mm, k_mm, n_mm])
        print(f"matmul: err={mm_err:.5f} pallas {t_p*1e3:.3f}ms "
              f"xla {t_x*1e3:.3f}ms ({t_x/t_p:.2f}x)")
    except Exception as e:  # noqa: BLE001
        extra["matmul"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        failed.append("matmul")
        print(f"matmul FAILED: {e}", file=sys.stderr)
    _dump(args.json, backend, rows, extra)

    # --- autotune candidate table (ISSUE 2): time EVERY registered
    # candidate — XLA, flash fwd across the block grid, and both backward
    # strategies (fused pair + split dq/dkv at per-pass tuned blocks) —
    # and emit the rows the measured dispatch will consume. On a real
    # chip this both populates the persistent autotune cache AND banks
    # the full per-candidate table into the bench JSON, so the next
    # on-chip window captures real crossovers instead of extrapolations.
    if not args.no_autotune:
        import tempfile

        from paddle_tpu.framework import config as _config
        from paddle_tpu.kernels import autotune as at

        # fresh cache dir: a warm user cache would satisfy every lookup
        # and this window would re-emit LAST window's timings as new
        # evidence — each bench capture must actually measure
        _config.set_flags({
            "FLAGS_autotune": "on",
            "FLAGS_autotune_cache_dir":
                tempfile.mkdtemp(prefix="kernel_bench_autotune_"),
            # measurement context, not a serving hot path: include the
            # flag-gated grouped-fetch candidate in the emitted table so
            # the capture shows whether it ever beats per-page/XLA
            "FLAGS_paged_grouped_kernel": True})
        at.reset_tuner()
        tuner = at.get_tuner()
        extra["autotune"] = {"device_kind": at.device_kind(),
                             "cache_path": tuner.cache_path(),
                             "entries": {}}
        scale = 1.0 / math.sqrt(d)
        printed = set()
        for s in seqs:
            b_eff = b
            while b_eff > 1 and b_eff * h * s * s * 4 > 2 * 2**30:
                b_eff //= 2
            try:
                at.choose_flash_fwd(b_eff * h, s, s, d, "bfloat16",
                                    causal, scale, training=False)
                # tunes flash_bwd_dq + flash_bwd_dkv sub-ops, then the
                # top-level xla/fused/split choice
                at.choose_flash_bwd(b_eff * h, s, s, d, "bfloat16",
                                    scale, causal, 128, 128)
            except Exception as e:  # noqa: BLE001 — keep earlier rows
                extra["autotune"]["entries"][f"seq{s}_error"] = \
                    f"{type(e).__name__}: {e}"[:300]
                failed.append(f"autotune_seq{s}")
            table = tuner.snapshot()
            extra["autotune"]["entries"].update(table)
            _dump(args.json, backend, rows, extra)
            for key in sorted(set(table) - printed):
                printed.add(key)
                e_ = table[key]
                tm = ", ".join(f"{n}={t:.3f}ms" for n, t in sorted(
                    e_["timings_ms"].items(), key=lambda kv: kv[1]))
                print(f"autotune {key}: winner={e_['winner']}  {tm}")
        try:
            at.choose_rms_norm(8192, 4096, "bfloat16")
            at.choose_paged_decode(8, 8, 8, 128, 16, 64, "bfloat16",
                                   False)
            at.choose_paged_decode(8, 8, 8, 128, 128, 8, "bfloat16",
                                   False)
            # MLP matmul family (ISSUE 12): both halves of the FFN at a
            # training token count, plus a decode-sized m
            at.choose_matmul(4096, 4096, 16384, "bfloat16")
            at.choose_matmul(4096, 16384, 4096, "bfloat16")
            at.choose_matmul(64, 4096, 16384, "bfloat16")
        except Exception as e:  # noqa: BLE001
            extra["autotune"]["entries"]["extra_ops_error"] = \
                f"{type(e).__name__}: {e}"[:300]
            failed.append("autotune_extra_ops")
        extra["autotune"]["entries"].update(tuner.snapshot())
        _dump(args.json, backend, rows, extra)
    if failed:
        sys.exit(f"tpu_kernel_bench: FAILED sections: {failed}")


if __name__ == "__main__":
    main()
