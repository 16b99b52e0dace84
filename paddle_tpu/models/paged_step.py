"""Shared paged-attention step for serving decode.

The serving path (reference: fused_multi_transformer_op, SURVEY.md §2.1)
is model-agnostic once q/k/v for the new token(s) exist: write the
token K/V into the paged pools (float or int8+scales), run decode
attention over the pages (measured XLA-gather/Pallas dispatch), all
inside an optional shard_map manual over tp — heads are embarrassingly
parallel, so q/k/v shard on the head dim, pools on their kv-head dim,
ZERO collectives inside. Model-specific position encoding (LLaMA rope)
plugs in via `rotate(q, k, lens)` applied INSIDE the mapped step, where
the per-slot positions are available.

Two shapes of step share this entry:
- s == 1: classic single-token decode (the page-grid Pallas kernel /
  measured dispatch). A row that is not active attends over nothing: the
  kernel then fetches no page through its (possibly stale) table row.
- s > 1: a WINDOW step — the speculative-decoding verify forward
  (inference/serving.py): all s tokens' K/V scatter into the pages at
  positions lens..lens+s-1 (positions at/beyond `limit_lens` masked —
  the window may overhang a row's budget), then every window position
  attends its own causal prefix in one dense-gather attention
  (kernels.paged_attention.paged_attention_window_xla).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from ..observability import tracing as _trace
from ..observability.tracing import scope
from ..tensor import Tensor, _apply_op, as_array


def _scoped(name):
    return scope(name) if name else contextlib.nullcontext()


def paged_attention_step(q, k, v, paged_cache, block_tables, context_lens,
                         active=None, mesh=None, kv_heads=None,
                         rotate=None, limit_lens=None, attend_scope=None,
                         scale=None):
    """q: [b, s, heads, d]; k/v: [b, s, kv_heads, d] (Tensors; s == 1 is
    the classic decode step, s > 1 the speculative-verify window; a value
    may be narrower than a key, and the output is as wide as a value).
    paged_cache: (k_pages, v_pages) or (k_pages, v_pages, k_scales,
    v_scales) for int8 pages. limit_lens: optional [b] — window
    positions at or beyond it write nothing (budget overhang).
    attend_scope: a scope name for the single-token attention itself (the
    cache read, scores, softmax and weighted sum), for a model that reads
    it apart from its projections. scale: the scores' factor where it is
    not 1 / sqrt(d) (a key stored wider than it is). Returns (out [b, s,
    heads * value width] Tensor, new_cache tuple)."""
    from ..distributed import mesh as _mesh
    from ..distributed.sharding_utils import in_manual_region
    from ..kernels import paged_attention as _pa

    b = q.shape[0]
    s_win = int(q.shape[1])
    n_heads = q.shape[2]
    head_dim = v.shape[3]
    if kv_heads is None:
        kv_heads = k.shape[2]
    kv_quant = len(paged_cache) == 4
    if kv_quant:
        k_pages, v_pages, k_scales, v_scales = paged_cache
    else:
        k_pages, v_pages = paged_cache
    act = active if active is not None else True
    limit = limit_lens
    act_rows = jnp.broadcast_to(jnp.asarray(as_array(act), bool), (b,))
    if s_win == 1 and _trace.counting():
        # how far the live-page read engages, from the rows' lengths
        # alone: pages a decode step has to read against pages mapped
        page_size = as_array(k_pages).shape[2]
        read = jnp.where(act_rows, as_array(context_lens) + 1, 0)
        _trace.count("attn_pages_read", jnp.sum(
            (read + page_size - 1) // page_size, dtype=jnp.int32))
        _trace.count("attn_pages_mapped", jnp.int32(
            b * as_array(block_tables).shape[1]))

    def step(qq, kk, vv, kp, vp, tables, lens, act_mask, *rest):
        if kv_quant:
            ksc, vsc = rest[:2]
            rest = rest[2:]
        lim = rest[0] if limit is not None else None
        if rotate is not None:
            qq, kk = rotate(qq, kk, lens)
        if s_win == 1:
            attn = _pa.paged_attention_dispatch if scale is None \
                else functools.partial(_pa.paged_attention_dispatch,
                                       scale=scale)
            # a row at/past its limit writes NOTHING: the draft scan of
            # a row that exhausted its budget would otherwise write
            # through stale (or zero) block-table entries into pages
            # owned by OTHER live requests (its own output is discarded
            # by the host commit, but the clobbered page is not)
            wm = act_mask if lim is None else act_mask & (lens < lim)
            # what a row attends over: its context and the token just
            # written; nothing for a row that is not active (its output
            # is discarded, and its table row may be stale)
            read = jnp.where(act_mask, lens + 1, 0)
            if kv_quant:
                with scope("kv_write"):
                    kp2, ksc2, vp2, vsc2 = _pa.update_paged_kv_cache_q8(
                        kp, ksc, vp, vsc, kk[:, 0], vv[:, 0],
                        tables, lens, active=wm)
                out = attn(qq[:, 0], kp2, vp2, tables, read,
                           k_scales=ksc2, v_scales=vsc2)
                return out[:, None], kp2, vp2, ksc2, vsc2
            with scope("kv_write"):
                kp2, vp2 = _pa.update_paged_kv_cache(
                    kp, vp, kk[:, 0].astype(kp.dtype),
                    vv[:, 0].astype(vp.dtype), tables, lens, active=wm)
            with _scoped(attend_scope):
                out = attn(qq[:, 0], kp2, vp2, tables, read)
            return out[:, None], kp2, vp2
        # window step (speculative verify): scatter the whole window,
        # then per-position causal attention over the paged prefix
        if kv_quant:
            with scope("kv_write"):
                kp2, ksc2, vp2, vsc2 = _pa.scatter_paged_kv_window_q8(
                    kp, ksc, vp, vsc, kk, vv, tables, lens,
                    limit_lens=lim, active=act_mask)
            out = _pa.paged_attention_window_xla(
                qq, kp2, vp2, tables, lens, k_scales=ksc2,
                v_scales=vsc2)
            return out, kp2, vp2, ksc2, vsc2
        with scope("kv_write"):
            kp2, vp2 = _pa.scatter_paged_kv_window(
                kp, vp, kk, vv, tables, lens, limit_lens=lim,
                active=act_mask)
        out = _pa.paged_attention_window_xla(qq, kp2, vp2, tables, lens)
        return out, kp2, vp2

    from jax.sharding import PartitionSpec as _P

    run = step
    if mesh is None:  # engine-provided mesh wins over the global one
        mesh = _mesh.get_mesh(optional=True)
    tp = int(mesh.shape["tp"]) if mesh is not None \
        and "tp" in mesh.axis_names else 1
    if tp > 1 and not in_manual_region() and kv_heads % tp == 0:
        hs = _P(None, None, "tp")      # [b, s, heads, hd]
        ps = _P("tp")                  # [kvh, n_pages, page, hd]
        rs = _P()
        # scale pools shard with their kv heads too: [kvh, n_pages, 128]
        in_specs = (hs, hs, hs, ps, ps, rs, rs, rs) + \
            ((ps, ps) if kv_quant else ()) + \
            ((rs,) if limit is not None else ())
        out_specs = (hs, ps, ps) + ((ps, ps) if kv_quant else ())
        # check_vma off: the step has no collective for the check to
        # type, and the Pallas decode kernel's plain out_shape is
        # rejected at trace time under it
        run = jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=frozenset({"tp"}), check_vma=False)

    args = [q, k, v, Tensor(as_array(k_pages)),
            Tensor(as_array(v_pages)), Tensor(as_array(block_tables)),
            Tensor(as_array(context_lens)),
            Tensor(act_rows)]
    if kv_quant:
        args += [Tensor(as_array(k_scales)), Tensor(as_array(v_scales))]
    if limit is not None:
        args += [Tensor(as_array(limit))]
    res = _apply_op(run, *args, _name="paged_attention")
    if kv_quant:
        out, new_k, new_v, new_ks, new_vs = res
        new_cache = (new_k, new_v, new_ks, new_vs)
    else:
        out, new_k, new_v = res
        new_cache = (new_k, new_v)
    from ..ops.manipulation import reshape

    out = reshape(out, [b, s_win, n_heads * head_dim])
    return out, new_cache


def window_attention_step(q, k, v, paged_cache, context_lens, window,
                          active=None, attend_scope=None, scale=None,
                          sink=None):
    """The single-token decode step of a WINDOW layer, whose pools are
    rings (`kernels/paged_attention.py`): row b of the batch owns ring b,
    its new token lands at position context_lens[b], and it attends the
    last `window` positions, that one included, through the same dispatch
    as a full layer, told the first position it sees: the kernel streams
    the ring's live pages and nothing else. q [b, 1, heads, d]; k, v [b, 1,
    kv_heads, d]; paged_cache (k_pages, v_pages) of [kv_heads, b x ring,
    page, d]. `scale`: the scores' factor where it is not 1 / sqrt(d);
    `sink` [heads]: a logit a head in the softmax's denominator. Returns
    (out [b, 1, heads * value width] Tensor, new_cache).

    Counts, where someone collects: `attn_window_pages_read` (pages the
    call streams: the copies the kernel's pipeline makes by the table of
    block indices it is handed, `decode_pages_fetched`, or every page of
    every live row's ring where the dense gather serves),
    `attn_window_pages_live` (pages holding a position a live row still
    sees, from the lengths alone) and `attn_window_pages_context` (pages a
    layer holding every position would read)."""
    from ..kernels import paged_attention as _pa

    b, n_heads, head_dim = q.shape[0], q.shape[2], v.shape[3]
    act = jnp.broadcast_to(
        jnp.asarray(True if active is None else as_array(active), bool), (b,))
    lens = as_array(context_lens)
    k_pages, v_pages = (as_array(c) for c in paged_cache)
    page_size = k_pages.shape[2]
    ring = k_pages.shape[1] // b
    rows = jnp.arange(b, dtype=jnp.int32)
    # what a row attends over: its context and the token just written;
    # nothing for a row that is not active
    seen = jnp.where(act, lens + 1, 0).astype(jnp.int32)
    tables, read, first = _pa.ring_view(rows, ring, page_size, seen, window)
    if _trace.counting():
        _trace.count("attn_window_pages_live",
                     _pa.ring_pages_live(seen, window, page_size))
        _trace.count("attn_window_pages_read", _pa.decode_pages_fetched(
            tables, read, page_size) if _pa.decode_uses_kernel(
            page_size, ring * page_size, False)
            else jnp.sum(act, dtype=jnp.int32) * ring)
        _trace.count("attn_window_pages_context", jnp.sum(
            (seen + page_size - 1) // page_size, dtype=jnp.int32))

    kw = {} if scale is None else {"scale": scale}

    def step(qq, kk, vv, kp, vp, *sunk):
        with scope("kv_write"):
            kp, vp = _pa.update_ring_kv_cache(
                kp, vp, kk[:, 0].astype(kp.dtype), vv[:, 0].astype(vp.dtype),
                rows, lens, active=act)
        with _scoped(attend_scope):
            out = _pa.paged_attention_dispatch(
                qq[:, 0], kp, vp, tables, read, first=first, **kw,
                **({"sink": sunk[0]} if sunk else {}))
        return out[:, None], kp, vp

    out, new_k, new_v = _apply_op(
        step, q, k, v, Tensor(k_pages), Tensor(v_pages),
        *(() if sink is None else (sink,)), _name="window_attention")
    from ..ops.manipulation import reshape

    return reshape(out, [b, 1, n_heads * head_dim]), (new_k, new_v)
