"""SPMD pipeline parallelism — the compiled 1F1B-family schedule.

Reference parity: fleet/meta_parallel/pipeline_parallel.py +
pp_utils/p2p_communication.py (SURVEY.md §2.3 "PP", §3.4): the reference
runs a host-orchestrated 1F1B microbatch schedule with NCCL send/recv
between per-process stage modules, plus the static-graph
fleet_executor/Interceptor actor runtime (SURVEY.md §2.1 "Fleet executor").

TPU-native design (SURVEY.md §7 phase 8): all of that machinery collapses
into ONE jitted SPMD program:

- stage weights are *stacked* arrays with a leading layer dim sharded over
  the `pp` mesh axis (each pp rank holds its stage's contiguous block of
  layers);
- the microbatch schedule is a `lax.scan` over T = M + S - 1 ticks inside a
  `shard_map` that is *manual over pp only* — tp/dp/sp stay GSPMD-auto, so
  Megatron TP layers keep working unchanged inside a stage;
- stage-to-stage transfer is `lax.ppermute` on the ICI ring — the
  send_v2/recv_v2 mapping from SURVEY.md §5;
- the backward schedule is NOT hand-written: differentiating through the
  scan+ppermute yields the reverse pipeline (ppermute transposes to the
  opposite rotation), and XLA overlaps compute with the permute traffic.
  This is the compiler-scheduled analog of 1F1B's comm/compute overlap;
- the warm-up/cool-down bubble exists as predicated no-op ticks (the
  `where(stage == 0, fresh_input, rotated_state)` select), identical cost
  shape to GPipe; interleaved/VPP-style bubble reduction = more microbatches
  per tick, exposed via `num_microbatches`.

The generic entry is `spmd_pipeline`; `stack_layer_params` builds the
stacked parameter pytree from a homogeneous list of layers (the pp analog of
`PipelineLayer`'s LayerDesc partitioning, which remains the user-facing
segmentation API).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import mesh as _mesh
from .sharding_utils import clean_spec as _clean_spec
from .sharding_utils import get_param_spec


def _pcast_varying(x, axes):
    """Mark x as varying over the manual axis/axes (scan carry
    requirement). Idempotent per axis: only the axes x is not already
    varying over are cast (pcast rejects varying->varying)."""
    if isinstance(axes, str):
        axes = (axes,)
    cur = jax.typeof(x).vma
    need = tuple(a for a in axes if a not in cur)
    if not need:
        return x
    return jax.lax.pcast(x, need, to="varying")


def _manual_batch_axes(mesh, axis_name):
    """Mesh axes folded into the pipeline shard_map's manual set beyond pp.

    With >= 2 GSPMD-auto axes alive alongside the manual pp axis, XLA's
    SPMD partitioner either CHECK-fails (spmd_partitioner_util.cc:495 —
    minimal repro: tools/xla_gather_spmd_repro.py) or places tp collectives
    inside the device-varying head `lax.cond`, where only the last stage's
    devices execute them (collective-permute rendezvous deadlock, observed
    on dp2 x pp2 x tp2). Folding the batch-like axes into the manual set
    leaves at most ONE auto axis (tp/sp) — the regime the partitioner
    handles — and makes the dp grad sync one explicit psum instead of a
    per-tick GSPMD choice.

    Returns (data_axes, inert_axes): data_axes shard the microbatch rows
    manually (explicit psum of grads/loss at the end); inert_axes (the
    ZeRO 'sharding' axis) carry no in-scan data — every value stays
    invariant over them, they are folded in only so the partitioner never
    sees them as a second auto axis.
    """
    data_axes = tuple(a for a in ("dp",) if a in mesh.axis_names
                      and int(mesh.shape[a]) > 1)
    inert_axes = tuple(a for a in ("sharding",) if a in mesh.axis_names
                       and int(mesh.shape[a]) > 1)
    return data_axes, inert_axes


def spmd_pipeline(stage_fn: Callable, stage_params, microbatches, *,
                  mesh=None, axis_name: str = "pp", stage_buffers=None):
    """Run `stage_fn` as an S-stage pipeline over `axis_name`.

    Args:
      stage_fn: (local_stage_params, x) -> y. Must be the same computation
        for every stage (homogeneous stages — e.g. a scan over the stage's
        block of decoder layers). x and y must have identical shape/dtype
        (the activation that flows through the pipeline).
      stage_params: pytree whose leaves have a leading dim divisible by S;
        leading dim is sharded over `axis_name` (each stage sees its block).
      microbatches: [M, ...] array (or pytree of such) of per-microbatch
        inputs to stage 0; replicated over `axis_name`.
      stage_buffers: optional stacked buffer pytree (stack_layer_buffers,
        leading dim sharded like stage_params). When given, stage_fn has
        the (params, buffers, x) -> (y, new_buffers) signature
        (make_stage_fn_with_buffers) and the schedule carries buffer
        updates (BN running stats) microbatch to microbatch, returning
        the updated stack alongside the outputs.

    Returns [M, ...] outputs of the last stage (a one-shard gather of the
    last stage's pp-sharded tick window — no all-reduce of the output
    volume), or (outputs, new_stage_buffers) when stage_buffers is given.
    """
    tm = jax.tree_util.tree_map
    mesh = mesh or _mesh.get_mesh()
    S = int(mesh.shape[axis_name])
    if S == 1:
        if stage_buffers is None:
            def run_one(mb):
                return stage_fn(stage_params, mb)

            return jax.lax.map(run_one, microbatches)

        def one(bufs, mb):
            y, nb = stage_fn(stage_params, bufs, mb)
            return nb, y

        new_bufs, ys = jax.lax.scan(one, stage_buffers, microbatches)
        return ys, new_bufs

    M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    T = M + S - 1

    def inner(local_params, inputs, local_bufs):
        stage = jax.lax.axis_index(axis_name)
        zero = tm(lambda x: _pcast_varying(jnp.zeros_like(x[0]), axis_name),
                  inputs)
        perm = [(i, (i + 1) % S) for i in range(S)]
        bufs0 = tm(lambda b: _pcast_varying(b, axis_name), local_bufs) \
            if stage_buffers is not None else {}

        def tick(carry, t):
            state, bufs = carry
            idx = jnp.clip(t, 0, M - 1)
            fresh = tm(lambda x: x[idx], inputs)
            x = tm(lambda f, s: jnp.where(stage == 0, f, s), fresh, state)
            if stage_buffers is None:
                y = stage_fn(local_params, x)
            else:
                y, nb = stage_fn(local_params, bufs, x)
                # garbage fill/drain ticks must not pollute running stats
                m = t - stage
                valid = (m >= 0) & (m < M)
                bufs = tm(lambda old, new: jnp.where(valid, new, old),
                          bufs, nb)
            nxt = tm(lambda a: jax.lax.ppermute(a, axis_name, perm), y)
            return (nxt, bufs), y

        (_, bufs), ys = jax.lax.scan(tick, (zero, bufs0), jnp.arange(T))
        # ticks S-1 .. T-1 on the LAST stage hold the pipeline outputs;
        # emit them pp-stacked ([1, M, ...] per stage) so the caller reads
        # the last stage's shard directly — a one-shard gather, NOT an
        # all-reduce of the full output volume
        window = tm(lambda a: a[S - 1:][None], ys)
        return window, bufs

    # manual over pp only; tp/dp/sp remain GSPMD-auto inside the stage
    stacked_spec = tm(lambda _: P(axis_name), stage_params)
    data_spec = tm(lambda _: P(), microbatches)
    buf_arg = stage_buffers if stage_buffers is not None else {}
    buf_spec = tm(lambda _: P(axis_name), buf_arg)
    stacked_out, new_bufs = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(stacked_spec, data_spec, buf_spec),
        out_specs=(tm(lambda _: P(axis_name), microbatches), buf_spec),
        axis_names=frozenset({axis_name}),
        # check_vma on: the schedule's ppermute/psum need the varying-axes
        # types (its out_specs and autodiff rely on them); the flash
        # kernel a stage may contain types its own out_shape with vma
        # (kernels/flash_attention._sds)
        check_vma=True,
    )(stage_params, microbatches, buf_arg)
    outs = tm(lambda a: a[-1], stacked_out)
    if stage_buffers is None:
        return outs
    return outs, new_bufs


def spmd_pipeline_1f1b(stage_fn, stage_params, microbatches, head_fn,
                       head_params, targets, *, mesh=None,
                       axis_name: str = "pp", stage_buffers=None):
    """Interleaved 1F1B train schedule in ONE compiled scan.

    The reference's host-orchestrated 1F1B (`PipelineParallel.train_batch`,
    fleet/meta_parallel/pipeline_parallel.py — SURVEY.md §2.3 "PP", §3.4)
    keeps at most S microbatches in flight per stage so activation memory is
    O(S), not O(M). This is the SPMD-compiled equivalent: a single
    `lax.scan` over T = M + 2(S-1) ticks where every tick performs one
    forward AND one backward microbatch step per stage (predicated during
    fill/drain), with

    - forward activations flowing via `ppermute` (+1 ring),
    - loss + initial cotangent produced at the LAST stage the same tick its
      forward microbatch arrives (head_fn runs inside the schedule),
    - cotangents flowing via the reverse `ppermute` (-1 ring) — the
      send_backward/recv_backward of pp_utils/p2p_communication.py,
    - a circular buffer of 2S-1 stage-INPUT activations per stage; the
      backward recomputes the stage forward from the saved input (remat),
      so in-flight memory is O(S) microbatch inputs — the 1F1B memory
      contract (GPipe-via-autodiff stores O(M) full per-layer residuals),
    - per-stage grad accumulation in f32, emitted pp-sharded (no grad
      all-reduce over pp; each stage owns its block's grads).

    Args:
      stage_fn: (local_stage_params, x) -> y, homogeneous across stages.
      stage_params: stacked pytree, leading dim sharded over `axis_name`.
      microbatches: [M, ...] array pytree — per-microbatch inputs to stage 0.
      head_fn: (head_params, y, target_mb) -> scalar mean loss of one
        microbatch. Runs at the last stage inside the schedule (tp/dp stay
        GSPMD-auto).
      head_params: pytree (embed/norm/lm-head weights), replicated over pp.
      targets: [M, ...] array pytree of per-microbatch labels.

    Returns (loss, d_stage_params, d_head_params, d_inputs):
      loss — scalar mean over all microbatches;
      d_stage_params — grads of stage_params (pp-sharded like the input);
      d_head_params — grads of head_params (from the last stage);
      d_inputs — [M, ...] cotangents w.r.t. microbatches (from stage 0),
        for the caller to backprop into the embedding.
    With stage_buffers (stacked BN-stat pytree; stage_fn then has the
    (params, buffers, x) -> (y, new_buffers) signature), the schedule
    carries buffer updates microbatch-to-microbatch in forward order and a
    fifth output — the updated buffer stack — is appended. The backward
    remat recomputes the stage forward with the CURRENT running stats,
    which is gradient-exact because train-mode normalization uses batch
    stats (running stats are pure outputs).
    """
    mesh = mesh or _mesh.get_mesh()
    S = int(mesh.shape[axis_name])
    tm = jax.tree_util.tree_map
    M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    inv_m = np.float32(1.0 / M)

    if S == 1:
        if stage_buffers is None:
            def one(m):
                mb = tm(lambda x: x[m], microbatches)
                tgt = tm(lambda t: t[m], targets)

                def loss_of(sp, hp, x):
                    return head_fn(hp, stage_fn(sp, x), tgt)

                loss_m, vjp = jax.vjp(loss_of, stage_params, head_params, mb)
                d_sp, d_hp, d_x = vjp(jnp.asarray(inv_m, loss_m.dtype))
                return loss_m, d_sp, d_hp, d_x

            losses, d_sps, d_hps, d_xs = jax.lax.map(one, jnp.arange(M))
            d_sp = tm(lambda a: jnp.sum(a, axis=0), d_sps)
            d_hp = tm(lambda a: jnp.sum(a, axis=0), d_hps)
            return jnp.mean(losses), d_sp, d_hp, d_xs

        def one_b(bufs, m):
            mb = tm(lambda x: x[m], microbatches)
            tgt = tm(lambda t: t[m], targets)

            def loss_of(sp, hp, x):
                y, nb = stage_fn(sp, bufs, x)
                return head_fn(hp, y, tgt), nb

            loss_m, vjp, nb = jax.vjp(loss_of, stage_params, head_params,
                                      mb, has_aux=True)
            d_sp, d_hp, d_x = vjp(jnp.asarray(inv_m, loss_m.dtype))
            return nb, (loss_m, d_sp, d_hp, d_x)

        new_bufs, (losses, d_sps, d_hps, d_xs) = jax.lax.scan(
            one_b, stage_buffers, jnp.arange(M))
        d_sp = tm(lambda a: jnp.sum(a, axis=0), d_sps)
        d_hp = tm(lambda a: jnp.sum(a, axis=0), d_hps)
        return jnp.mean(losses), d_sp, d_hp, d_xs, new_bufs

    T = M + 2 * (S - 1)
    B = 2 * S - 1  # max in-flight stage inputs (1F1B bound)

    def inner(local_params, inputs, head_params, targets, local_bufs):
        stage = jax.lax.axis_index(axis_name)
        is_last = stage == S - 1
        # head_params arrive pp-INVARIANT; vjp of an invariant input
        # against a pp-varying output inserts an implicit psum over pp,
        # which would fold every stage's (masked-out) head cotangent into
        # d_hp_m. Cast to varying so cotangents stay per-device and the
        # explicit masked psum below is the only cross-stage reduction.
        head_params = tm(lambda p: _pcast_varying(p, axis_name), head_params)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [((i + 1) % S, i) for i in range(S)]

        mb_zero = tm(lambda x: _pcast_varying(
            jnp.zeros_like(x[0]), axis_name), inputs)
        buf0 = tm(lambda x: _pcast_varying(
            jnp.zeros((B,) + x.shape[1:], x.dtype), axis_name), inputs)
        dp0 = tm(lambda p: _pcast_varying(
            jnp.zeros(p.shape, jnp.float32), axis_name), local_params)
        dh0 = tm(lambda p: _pcast_varying(
            jnp.zeros(p.shape, jnp.float32), axis_name), head_params)
        loss0 = _pcast_varying(jnp.zeros((), jnp.float32), axis_name)
        bufs0 = tm(lambda b: _pcast_varying(b, axis_name), local_bufs)

        def tick(carry, t):
            buf, fwd_c, bwd_c, d_params, d_head, loss_acc, bn_bufs = carry

            # ---- forward slot ----
            m_f = t - stage
            fwd_valid = (m_f >= 0) & (m_f < M)
            idx_f = jnp.clip(m_f, 0, M - 1)
            fresh = tm(lambda x: x[idx_f], inputs)
            x = tm(lambda f, c: jnp.where(stage == 0, f, c), fresh, fwd_c)
            slot_f = idx_f % B
            buf = tm(lambda b_, x_: b_.at[slot_f].set(
                jnp.where(fwd_valid, x_, b_[slot_f])), buf, x)
            if stage_buffers is None:
                y = stage_fn(local_params, x)
            else:
                y, nb = stage_fn(local_params, bn_bufs, x)
                # fill/drain ticks run on garbage activations — keep stats
                bn_bufs = tm(lambda old, new: jnp.where(fwd_valid, new, old),
                             bn_bufs, nb)

            # ---- head (+ initial cotangent), ONLY at the last stage ----
            # lax.cond with a device-varying predicate: non-last stages
            # skip the vocab-projection + CE fwd/vjp entirely (a masked
            # dense computation would waste (S-1)/S of all head FLOPs).
            # All devices of a tp group share a pp stage index, so the
            # GSPMD-auto tp collectives inside the branch cannot deadlock.
            tgt = tm(lambda a: a[idx_f], targets)
            head_valid = is_last & fwd_valid

            def head_loss(hp, y_):
                return head_fn(hp, y_, tgt)

            def do_head(y_):
                loss_m, head_vjp = jax.vjp(head_loss, head_params, y_)
                d_hp_m, d_y = head_vjp(_pcast_varying(
                    jnp.asarray(inv_m, loss_m.dtype), axis_name))
                return loss_m.astype(jnp.float32), d_hp_m, d_y

            def skip_head(y_):
                zl = _pcast_varying(jnp.zeros((), jnp.float32), axis_name)
                zh = tm(lambda p: _pcast_varying(
                    jnp.zeros(p.shape, p.dtype), axis_name), head_params)
                zy = tm(lambda a: _pcast_varying(
                    jnp.zeros_like(a), axis_name), y_)
                return zl, zh, zy

            loss_m, d_hp_m, d_y = jax.lax.cond(
                head_valid, do_head, skip_head, y)
            loss_acc = loss_acc + loss_m
            d_head = tm(lambda a, g: a + g.astype(jnp.float32),
                        d_head, d_hp_m)

            # ---- backward slot (remat from the saved stage input) ----
            m_b = t - (2 * S - 2 - stage)
            bwd_valid = (m_b >= 0) & (m_b < M)
            idx_b = jnp.clip(m_b, 0, M - 1)
            slot_b = idx_b % B
            x_saved = tm(lambda b_: b_[slot_b], buf)
            g_in = tm(lambda dy, c: jnp.where(is_last, dy, c), d_y, bwd_c)
            if stage_buffers is None:
                fwd_for_vjp = stage_fn
            else:
                def fwd_for_vjp(p, xx):
                    return stage_fn(p, jax.lax.stop_gradient(bn_bufs), xx)[0]
            _, stage_vjp = jax.vjp(fwd_for_vjp, local_params, x_saved)
            d_p_m, d_x = stage_vjp(g_in)
            d_params = tm(lambda a, g: a + jnp.where(
                bwd_valid, g.astype(jnp.float32), 0.0), d_params, d_p_m)
            d_x = tm(lambda g: jnp.where(bwd_valid, g, jnp.zeros_like(g)),
                     d_x)

            # ---- ring transfers ----
            fwd_c = tm(lambda a: jax.lax.ppermute(a, axis_name, fwd_perm), y)
            bwd_c = tm(lambda a: jax.lax.ppermute(a, axis_name, bwd_perm),
                       d_x)
            return (buf, fwd_c, bwd_c, d_params, d_head, loss_acc,
                    bn_bufs), d_x

        init = (buf0, mb_zero, mb_zero, dp0, dh0, loss0, bufs0)
        carry, dxs = jax.lax.scan(tick, init, jnp.arange(T))
        _, _, _, d_params, d_head, loss_acc, bn_bufs = carry

        # stage 0 emits d_inputs on ticks 2S-2 .. T-1 (microbatch order)
        d_inputs = tm(lambda a: a[2 * S - 2:][None], dxs)
        loss = jax.lax.psum(loss_acc, axis_name) * inv_m  # mean over M
        d_head = tm(lambda a: jax.lax.psum(a, axis_name), d_head)
        d_params = tm(lambda a, p: a.astype(p.dtype), d_params, local_params)
        return loss, d_params, d_head, d_inputs, bn_bufs

    stacked_spec = tm(lambda _: P(axis_name), stage_params)
    data_spec = tm(lambda _: P(), microbatches)
    head_spec = tm(lambda _: P(), head_params)
    tgt_spec = tm(lambda _: P(), targets)
    buf_arg = stage_buffers if stage_buffers is not None else {}
    buf_spec = tm(lambda _: P(axis_name), buf_arg)
    loss, d_params, d_head, d_inputs_stacked, new_bufs = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(stacked_spec, data_spec, head_spec, tgt_spec, buf_spec),
        out_specs=(P(), stacked_spec, head_spec,
                   tm(lambda _: P(axis_name), microbatches), buf_spec),
        axis_names=frozenset({axis_name}),
        check_vma=True,  # see spmd_pipeline
    )(stage_params, microbatches, head_params, targets, buf_arg)
    d_head = tm(lambda a, p: a.astype(p.dtype), d_head, head_params)
    # stage 0's shard holds the input cotangents — one-shard gather
    d_inputs = tm(lambda a: a[0], d_inputs_stacked)
    if stage_buffers is None:
        return loss, d_params, d_head, d_inputs
    return loss, d_params, d_head, d_inputs, new_bufs


# ---------------------------------------------------------------------------
# Interleaved virtual-pipeline (VPP) schedule
# ---------------------------------------------------------------------------


def _vpp_schedule(S: int, v: int, M: int):
    """Host-side simulation of the Megatron interleaved 1F1B schedule
    (reference: fleet/meta_parallel/pipeline_parallel.py interleaved /
    Megatron-LM forward_backward_pipelining_with_interleaving — SURVEY.md
    §2.3 "PP").

    Logical stage k = j*S + r lives on rank r = k % S, virtual chunk
    j = k // S.  Each rank's op order is the Megatron program: W warmup
    forwards, then 1F1B fwd/bwd pairs, then cooldown backwards, where the
    n-th forward of a rank is chunk (n//S) % v of microbatch
    (n//(S*v))*S + n%S (microbatch groups of size S per chunk), and
    backwards mirror with the chunk order reversed.

    The simulation assigns each op a global tick honoring (a) strict
    per-rank program order, (b) at most one forward and one backward per
    rank per tick (our scan tick does one of each), (c) one-tick transfer
    latency between neighbouring logical stages, (d) the head's cotangent
    being available the same tick its forward runs (the scan runs the
    forward phase before the backward phase).

    Returns a dict of numpy [T, S] int32 tables (fwd/bwd exec + receive
    sides) plus the buffer bound B (max in-flight microbatches per chunk).
    """
    total = M * v
    if M % S:
        raise ValueError(f"VPP requires microbatches ({M}) % pp ({S}) == 0")

    def fwd_op(n):
        g, rem = divmod(n, S * v)
        return (rem // S) % v, g * S + rem % S  # (chunk, microbatch)

    def bwd_op(n):
        g, rem = divmod(n, S * v)
        return v - 1 - (rem // S) % v, g * S + rem % S

    warmup = [min(total, (S - r - 1) * 2 + (v - 1) * S) for r in range(S)]
    progs = []
    for r in range(S):
        ops = [("f", n) for n in range(warmup[r])]
        nf, nb = warmup[r], 0
        while nf < total or nb < total:
            if nf < total:
                ops.append(("f", nf))
                nf += 1
            if nb < total:
                ops.append(("b", nb))
                nb += 1
        progs.append(ops)

    f_done = {}  # (r, j, m) -> tick
    b_done = {}
    ptr = [0] * S
    rows = {k: [] for k in ("f_chunk", "f_mb", "f_valid",
                            "b_chunk", "b_mb", "b_valid")}
    t, limit = 0, 4 * total + 4 * S * v + 16
    while any(ptr[r] < len(progs[r]) for r in range(S)):
        if t > limit:
            raise RuntimeError("VPP schedule simulation did not converge")
        row = {k: [0] * S for k in rows}
        # phase order matters: forwards resolve before backwards so the
        # head's same-tick d_y hand-off is representable
        executed = {r: {"f": False, "b": False} for r in range(S)}
        for kind_pass in ("f", "b"):
            for r in range(S):
                while ptr[r] < len(progs[r]):
                    kind, n = progs[r][ptr[r]]
                    if executed[r][kind]:
                        break
                    if kind == "f":
                        j, m = fwd_op(n)
                        if r == 0 and j == 0:
                            ready = True
                        elif r > 0:
                            ready = f_done.get((r - 1, j, m), t) < t
                        else:  # r == 0, j > 0: from last rank, prev chunk
                            ready = f_done.get((S - 1, j - 1, m), t) < t
                        if not ready or kind_pass == "b":
                            break
                        f_done[(r, j, m)] = t
                        row["f_chunk"][r] = j
                        row["f_mb"][r] = m
                        row["f_valid"][r] = 1
                    else:
                        j, m = bwd_op(n)
                        if r == S - 1 and j == v - 1:
                            ready = f_done.get((r, j, m), t + 1) <= t
                        elif r < S - 1:
                            ready = b_done.get((r + 1, j, m), t) < t
                        else:  # r == S-1, j < v-1: from rank 0, next chunk
                            ready = b_done.get((0, j + 1, m), t) < t
                        if not ready:
                            break
                        b_done[(r, j, m)] = t
                        row["b_chunk"][r] = j
                        row["b_mb"][r] = m
                        row["b_valid"][r] = 1
                    executed[r][kind] = True
                    ptr[r] += 1
        for k in rows:
            rows[k].append(row[k])
        t += 1
    T = t

    tab = {k: np.asarray(rows[k], np.int32) for k in rows}

    # receive-side tables: what the ring delivers at tick t (sent at t-1)
    fin = {k: np.zeros((T, S), np.int32)
           for k in ("fin_chunk", "fin_mb", "fin_valid",
                     "bin_chunk", "bin_mb", "bin_valid")}
    for t_ in range(1, T):
        for r in range(S):
            src = (r - 1) % S
            if tab["f_valid"][t_ - 1, src]:
                j = int(tab["f_chunk"][t_ - 1, src])
                jr = j if r > 0 else j + 1  # last->first hop advances chunk
                if jr < v and not (src == S - 1 and j == v - 1):
                    fin["fin_chunk"][t_, r] = jr
                    fin["fin_mb"][t_, r] = tab["f_mb"][t_ - 1, src]
                    fin["fin_valid"][t_, r] = 1
            srcb = (r + 1) % S
            if tab["b_valid"][t_ - 1, srcb]:
                j = int(tab["b_chunk"][t_ - 1, srcb])
                jr = j if r < S - 1 else j - 1  # first->last hop: prev chunk
                if jr >= 0 and not (srcb == 0 and j == 0):
                    fin["bin_chunk"][t_, r] = jr
                    fin["bin_mb"][t_, r] = tab["b_mb"][t_ - 1, srcb]
                    fin["bin_valid"][t_, r] = 1
    tab.update(fin)

    # buffer bound: max microbatches of one chunk in flight on one rank
    # between forward save and backward consume (inclusive)
    B = 1
    for r in range(S):
        for j in range(v):
            events = []
            for m in range(M):
                events.append((f_done[(r, j, m)], 1))
                events.append((b_done[(r, j, m)] + 1, -1))
            live = peak = 0
            for _, delta in sorted(events):
                live += delta
                peak = max(peak, live)
            B = max(B, peak)
    tab["B"] = B + 1  # +1: recv can land one tick before the fwd consumes
    tab["T"] = T
    return tab


def spmd_pipeline_vpp(stage_fn, stage_params, microbatches, head_fn,
                      head_params, targets, *, num_chunks: int, mesh=None,
                      axis_name: str = "pp", stage_buffers=None):
    """Interleaved virtual-pipeline (VPP) 1F1B train schedule, compiled.

    Reference: the interleaved schedule of
    fleet/meta_parallel/pipeline_parallel.py (SURVEY.md §2.3 "PP"): each
    rank owns `num_chunks` (v) non-contiguous model chunks (rank r holds
    logical stages r, S+r, 2S+r, …), shrinking the pipeline bubble by ~v
    because warm-up/drain steps are chunk-sized (1/v of a stage) instead of
    stage-sized.

    Args mirror `spmd_pipeline_1f1b`, except `stage_params` leaves carry a
    leading [S, v] pair of dims (build with `vpp_stack_layer_params`):
    dim 0 is sharded over `axis_name`, dim 1 indexes the rank's chunks —
    local chunk j is global logical stage j*S + r.  `stage_fn` receives one
    chunk's params (the [S, v] dims stripped).

    dp caveat: with dp folded into the manual axis set
    (`_manual_batch_axes`), the global loss is the EQUAL-WEIGHT mean of
    per-dp-shard means. For a plain mean criterion this is exact; for a
    masked mean (ignore_index / class weights) whose valid counts differ
    across dp shards it deviates from the global-valid-count mean — the
    same per-rank-mean semantics as the reference's distributed CE. Use
    schedule='1f1b' if exact masked-mean semantics across dp are required.

    Returns (loss, d_stage_params, d_head_params, d_inputs) exactly like
    `spmd_pipeline_1f1b` (d_stage_params in the same [S, v] layout). With
    stage_buffers (vpp_stack_layer_buffers, [S, v, Lc, ...]), stage_fn has
    the buffered signature and the updated stack is a fifth output; under
    manual dp the final running stats are the pmean over dp shards (each
    shard normalizes by its local microbatch rows — the DDP-style
    cross-replica buffer averaging).
    """
    mesh = mesh or _mesh.get_mesh()
    S = int(mesh.shape[axis_name])
    v = int(num_chunks)
    tm = jax.tree_util.tree_map
    M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    inv_m = np.float32(1.0 / M)

    if v == 1:
        # plain 1F1B with the chunk dim stripped
        flat = tm(lambda p: p[:, 0] if p.shape[1] == 1 else p, stage_params)
        if stage_buffers is not None:
            flat_b = tm(lambda b: b[:, 0], stage_buffers)
            loss, d_p, d_h, d_x, nb = spmd_pipeline_1f1b(
                stage_fn, flat, microbatches, head_fn, head_params,
                targets, mesh=mesh, axis_name=axis_name,
                stage_buffers=flat_b)
            return (loss, tm(lambda g: g[:, None], d_p), d_h, d_x,
                    tm(lambda b: b[:, None], nb))
        loss, d_p, d_h, d_x = spmd_pipeline_1f1b(
            stage_fn, flat, microbatches, head_fn, head_params, targets,
            mesh=mesh, axis_name=axis_name)
        return loss, tm(lambda g: g[:, None], d_p), d_h, d_x

    if S == 1:
        if stage_buffers is None:
            def chunk_chain(sp, x):
                for j in range(v):
                    x = stage_fn(tm(lambda p: p[0, j], sp), x)
                return x

            def one(m):
                mb = tm(lambda x: x[m], microbatches)
                tgt = tm(lambda x: x[m], targets)

                def loss_of(sp, hp, x):
                    return head_fn(hp, chunk_chain(sp, x), tgt)

                loss_m, vjp = jax.vjp(loss_of, stage_params, head_params, mb)
                d_sp, d_hp, d_x = vjp(jnp.asarray(inv_m, loss_m.dtype))
                return loss_m, d_sp, d_hp, d_x

            losses, d_sps, d_hps, d_xs = jax.lax.map(one, jnp.arange(M))
            return (jnp.mean(losses), tm(lambda a: jnp.sum(a, 0), d_sps),
                    tm(lambda a: jnp.sum(a, 0), d_hps), d_xs)

        def one_b(bufs, m):
            mb = tm(lambda x: x[m], microbatches)
            tgt = tm(lambda x: x[m], targets)

            def loss_of(sp, hp, x):
                nb = bufs
                for j in range(v):
                    x, nb_j = stage_fn(tm(lambda p: p[0, j], sp),
                                       tm(lambda b: b[0, j], nb), x)
                    nb = tm(lambda full, upd: full.at[0, j].set(upd),
                            nb, nb_j)
                return head_fn(hp, x, tgt), nb

            loss_m, vjp, nb = jax.vjp(loss_of, stage_params, head_params,
                                      mb, has_aux=True)
            d_sp, d_hp, d_x = vjp(jnp.asarray(inv_m, loss_m.dtype))
            return nb, (loss_m, d_sp, d_hp, d_x)

        new_bufs, (losses, d_sps, d_hps, d_xs) = jax.lax.scan(
            one_b, stage_buffers, jnp.arange(M))
        return (jnp.mean(losses), tm(lambda a: jnp.sum(a, 0), d_sps),
                tm(lambda a: jnp.sum(a, 0), d_hps), d_xs, new_bufs)

    data_axes, inert_axes = _manual_batch_axes(mesh, axis_name)
    manual_axes = (axis_name,) + data_axes + inert_axes
    vary = (axis_name,) + data_axes
    dp_total = int(np.prod([int(mesh.shape[a]) for a in data_axes],
                           dtype=np.int64)) if data_axes else 1
    mb_rows = jax.tree_util.tree_leaves(microbatches)[0].shape[1]
    if mb_rows % dp_total:
        raise ValueError(
            f"VPP shards each microbatch's {mb_rows} rows over the dp "
            f"axes {data_axes} (size {dp_total}) inside the schedule; pick "
            f"batch/num_microbatches so rows-per-microbatch divides dp")
    inv_scale = np.float32(1.0 / (M * dp_total))

    sched = _vpp_schedule(S, v, M)
    T, B = int(sched["T"]), int(sched["B"])
    tick_rows = {k: jnp.asarray(a) for k, a in sched.items()
                 if k not in ("T", "B")}

    def inner(local_params, inputs, head_params, targets, local_bufs):
        stage = jax.lax.axis_index(axis_name)
        is_last = stage == S - 1
        # params arrive invariant over the manual data axes; cast them
        # varying so the vjps accumulate per-device partials (ONE psum at
        # the end) instead of transposing to a psum every tick
        local_params = tm(lambda p: _pcast_varying(p[0], vary),
                          local_params)  # [v, ...]
        local_bufs = tm(lambda b: _pcast_varying(b[0], vary), local_bufs)
        head_params = tm(lambda p: _pcast_varying(p, vary), head_params)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [((i + 1) % S, i) for i in range(S)]

        def zeros_mb():
            return tm(lambda x: _pcast_varying(
                jnp.zeros_like(x[0]), vary), inputs)

        def zeros_buf():
            return tm(lambda x: _pcast_varying(
                jnp.zeros((v, B) + x.shape[1:], x.dtype), vary), inputs)

        carry0 = dict(
            fwd_c=zeros_mb(), bwd_c=zeros_mb(),
            recv_buf=zeros_buf(), remat_buf=zeros_buf(),
            cot_buf=zeros_buf(),
            d_params=tm(lambda p: _pcast_varying(
                jnp.zeros(p.shape, jnp.float32), vary), local_params),
            d_head=tm(lambda p: _pcast_varying(
                jnp.zeros(p.shape, jnp.float32), vary), head_params),
            d_inputs=tm(lambda x: _pcast_varying(
                jnp.zeros_like(x), vary), inputs),
            loss=_pcast_varying(jnp.zeros((), jnp.float32), vary),
            bn_bufs=local_bufs,
        )

        def at_set(buf, j, slot, val, valid):
            return tm(lambda b_, v_: b_.at[j, slot].set(
                jnp.where(valid, v_, b_[j, slot])), buf, val)

        def tick(carry, row):
            c = dict(carry)
            r = lambda k: row[k][stage]  # noqa: E731 — per-rank table entry

            # ---- receive ring payloads from tick t-1 ----
            c["recv_buf"] = at_set(c["recv_buf"], r("fin_chunk"),
                                   r("fin_mb") % B, c["fwd_c"],
                                   r("fin_valid") == 1)
            c["cot_buf"] = at_set(c["cot_buf"], r("bin_chunk"),
                                  r("bin_mb") % B, c["bwd_c"],
                                  r("bin_valid") == 1)

            # ---- forward phase ----
            jf, mf = r("f_chunk"), r("f_mb")
            f_valid = r("f_valid") == 1
            slot_f = mf % B
            fresh = tm(lambda x: x[mf], inputs)
            from_ring = tm(lambda b_: b_[jf, slot_f], c["recv_buf"])
            x = tm(lambda f_, b_: jnp.where((stage == 0) & (jf == 0), f_, b_),
                   fresh, from_ring)
            c["remat_buf"] = at_set(c["remat_buf"], jf, slot_f, x, f_valid)
            # chunk params selected via lax.switch with STATIC per-branch
            # slices: a dynamic-slice over the tp/dp-auto-sharded param
            # leaves sends the GSPMD partitioner into a pathological search
            # (observed: >10min compiles); static slices partition cleanly
            if stage_buffers is None:
                y = jax.lax.switch(
                    jf, [(lambda j: lambda x_: stage_fn(
                        tm(lambda p: p[j], local_params), x_))(j)
                         for j in range(v)], x)
            else:
                def fwd_chunk(j):
                    def f(args):
                        x_, bufs_ = args
                        y_, nb_j = stage_fn(
                            tm(lambda p: p[j], local_params),
                            tm(lambda b: b[j], bufs_), x_)
                        nb_full = tm(lambda full, upd: full.at[j].set(upd),
                                     bufs_, nb_j)
                        return y_, nb_full

                    return f

                y, nb = jax.lax.switch(
                    jf, [fwd_chunk(j) for j in range(v)],
                    (x, c["bn_bufs"]))
                c["bn_bufs"] = tm(
                    lambda old, new: jnp.where(f_valid, new, old),
                    c["bn_bufs"], nb)

            # head at the last logical stage (rank S-1, chunk v-1)
            tgt = tm(lambda a: a[mf], targets)
            head_valid = is_last & (jf == v - 1) & f_valid

            def do_head(y_):
                def head_loss(hp, y__):
                    return head_fn(hp, y__, tgt)

                loss_m, head_vjp = jax.vjp(head_loss, head_params, y_)
                d_hp_m, d_y = head_vjp(_pcast_varying(
                    jnp.asarray(inv_scale, loss_m.dtype), vary))
                return loss_m.astype(jnp.float32), d_hp_m, d_y

            def skip_head(y_):
                zl = _pcast_varying(jnp.zeros((), jnp.float32), vary)
                zh = tm(lambda p: _pcast_varying(
                    jnp.zeros(p.shape, p.dtype), vary), head_params)
                zy = tm(lambda a: _pcast_varying(
                    jnp.zeros_like(a), vary), y_)
                return zl, zh, zy

            loss_m, d_hp_m, d_y = jax.lax.cond(head_valid, do_head,
                                               skip_head, y)
            c["loss"] = c["loss"] + loss_m
            c["d_head"] = tm(lambda a, g: a + g.astype(jnp.float32),
                             c["d_head"], d_hp_m)
            # head cotangent is consumed from cot_buf, same chunk v-1
            c["cot_buf"] = at_set(c["cot_buf"], jnp.asarray(v - 1), slot_f,
                                  d_y, head_valid)

            # ---- backward phase (remat from saved chunk input) ----
            jb, mb_ = r("b_chunk"), r("b_mb")
            b_valid = r("b_valid") == 1
            slot_b = mb_ % B
            x_saved = tm(lambda b_: b_[jb, slot_b], c["remat_buf"])
            g_in = tm(lambda b_: b_[jb, slot_b], c["cot_buf"])

            def bwd_chunk(j):
                def f(args):
                    xs_, gi_ = args
                    pj_ = tm(lambda p: p[j], local_params)
                    if stage_buffers is None:
                        fwd_j = stage_fn
                    else:
                        bufs_j = jax.lax.stop_gradient(
                            tm(lambda b: b[j], c["bn_bufs"]))

                        def fwd_j(pp_, xx_):
                            return stage_fn(pp_, bufs_j, xx_)[0]
                    _, stage_vjp = jax.vjp(fwd_j, pj_, xs_)
                    d_pj, d_x_ = stage_vjp(gi_)
                    d_full = tm(lambda p: jnp.zeros(p.shape, jnp.float32),
                                local_params)
                    d_full = tm(lambda df, g: df.at[j].set(
                        g.astype(jnp.float32)), d_full, d_pj)
                    return d_full, d_x_

                return f

            d_p_full, d_x = jax.lax.switch(
                jb, [bwd_chunk(j) for j in range(v)], (x_saved, g_in))
            c["d_params"] = tm(
                lambda a, g: a + jnp.where(b_valid, g, 0.0),
                c["d_params"], d_p_full)
            d_x = tm(lambda g: jnp.where(b_valid, g, jnp.zeros_like(g)), d_x)
            emit_dx = (stage == 0) & (jb == 0) & b_valid
            c["d_inputs"] = tm(
                lambda acc, g: acc.at[mb_].set(
                    jnp.where(emit_dx, g, acc[mb_])), c["d_inputs"], d_x)

            # ---- ring transfers ----
            c["fwd_c"] = tm(lambda a: jax.lax.ppermute(a, axis_name,
                                                       fwd_perm), y)
            c["bwd_c"] = tm(lambda a: jax.lax.ppermute(a, axis_name,
                                                       bwd_perm), d_x)
            return c, None

        carry, _ = jax.lax.scan(tick, carry0, tick_rows)
        # one psum over pp + the manual data axes: the pp loss gather and
        # the dp gradient all-reduce in a single explicit collective each
        loss = jax.lax.psum(carry["loss"], vary) * inv_scale
        d_head = tm(lambda a: jax.lax.psum(a, vary), carry["d_head"])
        d_params = carry["d_params"]
        if data_axes:
            d_params = tm(lambda a: jax.lax.psum(a, data_axes), d_params)
        d_params = tm(lambda a, p: a.astype(p.dtype)[None],
                      d_params, local_params)
        d_inputs = tm(lambda a: a[None], carry["d_inputs"])
        bn_bufs = carry["bn_bufs"]
        if data_axes:
            # each dp shard updated stats from its local rows: emit the
            # cross-replica average (DDP-style buffer averaging)
            bn_bufs = tm(lambda b: jax.lax.pmean(b, data_axes), bn_bufs)
        bn_bufs = tm(lambda b: b[None], bn_bufs)
        return loss, d_params, d_head, d_inputs, bn_bufs

    dp_spec = data_axes if data_axes else None
    stacked_spec = tm(lambda _: P(axis_name), stage_params)
    data_spec = tm(lambda _: P(None, dp_spec), microbatches)
    head_spec = tm(lambda _: P(), head_params)
    tgt_spec = tm(lambda _: P(None, dp_spec), targets)
    buf_arg = stage_buffers if stage_buffers is not None else {}
    buf_spec = tm(lambda _: P(axis_name), buf_arg)
    loss, d_params, d_head, d_inputs_stacked, new_bufs = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(stacked_spec, data_spec, head_spec, tgt_spec, buf_spec),
        out_specs=(P(), stacked_spec, head_spec,
                   tm(lambda _: P(axis_name, None, dp_spec), microbatches),
                   buf_spec),
        axis_names=frozenset(manual_axes),
        check_vma=True,  # see spmd_pipeline
    )(stage_params, microbatches, head_params, targets, buf_arg)
    d_head = tm(lambda a, p: a.astype(p.dtype), d_head, head_params)
    # stage 0's shard holds the input cotangents — one-shard gather
    d_inputs = tm(lambda a: a[0], d_inputs_stacked)
    if stage_buffers is None:
        return loss, d_params, d_head, d_inputs
    return loss, d_params, d_head, d_inputs, new_bufs


def vpp_stack_layer_params(layers: Sequence, S: int, v: int
                           ) -> Dict[str, jax.Array]:
    """Stack homogeneous layers for VPP: suffix -> [S, v, Lc, ...] where
    [r, j] holds global chunk j*S + r (the Megatron interleaved layout:
    rank r owns logical stages r, S+r, 2S+r, …)."""
    L = len(layers)
    if L % (S * v):
        raise ValueError(f"layers ({L}) must divide pp*chunks ({S * v})")
    Lc = L // (S * v)
    trees = [dict(l.named_parameters()) for l in layers]
    names = list(trees[0].keys())
    out = {}
    for n in names:
        per_chunk = []
        for r in range(S):
            chunk_rows = []
            for j in range(v):
                c = j * S + r
                chunk_rows.append(jnp.stack(
                    [trees[c * Lc + i][n]._data for i in range(Lc)]))
            per_chunk.append(jnp.stack(chunk_rows))
        out[n] = jnp.stack(per_chunk)  # [S, v, Lc, ...]
    return out


def vpp_unstack_into_layers(stacked: Dict[str, jax.Array], layers: Sequence,
                            S: int, v: int):
    """Inverse of `vpp_stack_layer_params` (post-step write-back)."""
    L = len(layers)
    Lc = L // (S * v)
    for r in range(S):
        for j in range(v):
            c = j * S + r
            for i in range(Lc):
                layers[c * Lc + i].load_pytree(
                    {n: a[r, j, i] for n, a in stacked.items()})


def vpp_stack_layer_buffers(layers: Sequence, S: int, v: int
                            ) -> Dict[str, jax.Array]:
    """Stack layer BUFFERS in the VPP chunk layout: suffix ->
    [S, v, Lc, ...] (same indexing as `vpp_stack_layer_params`)."""
    L = len(layers)
    Lc = L // (S * v)
    trees = [dict(l.named_buffers()) for l in layers]
    names = list(trees[0].keys())
    out = {}
    for n in names:
        per_chunk = []
        for r in range(S):
            rows = []
            for j in range(v):
                c = j * S + r
                rows.append(jnp.stack(
                    [trees[c * Lc + i][n]._data for i in range(Lc)]))
            per_chunk.append(jnp.stack(rows))
        out[n] = jnp.stack(per_chunk)
    return out


# ---------------------------------------------------------------------------
# stacked-parameter utilities (LayerDesc partitioning -> stacked arrays)
# ---------------------------------------------------------------------------


def stack_layer_params(layers: Sequence) -> Dict[str, jax.Array]:
    """Stack the parameters of homogeneous layers: suffix -> [L, ...]."""
    trees = [dict(l.named_parameters()) for l in layers]
    names = list(trees[0].keys())
    for t in trees[1:]:
        if list(t.keys()) != names:
            raise ValueError("pipeline stages must be homogeneous layers")
    return {
        n: jnp.stack([t[n]._data for t in trees]) for n in names
    }


def stack_layer_buffers(layers: Sequence) -> Dict[str, jax.Array]:
    """Stack the BUFFERS (BN running stats etc.) of homogeneous layers:
    suffix -> [L, ...]. Empty dict when the layers carry no buffers."""
    trees = [dict(l.named_buffers()) for l in layers]
    names = list(trees[0].keys())
    for t in trees[1:]:
        if list(t.keys()) != names:
            raise ValueError("pipeline stages must be homogeneous layers")
    return {
        n: jnp.stack([t[n]._data for t in trees]) for n in names
    }





def stacked_param_specs(layers: Sequence, mesh, axis_name: str = "pp"
                        ) -> Dict[str, P]:
    """Sharding spec per stacked suffix: ('pp', *layer-param spec)."""
    out = {}
    for n, p in layers[0].named_parameters():
        inner = list(_clean_spec(get_param_spec(p), mesh))
        out[n] = P(axis_name, *inner)
    return out


def unstack_into_layers(stacked: Dict[str, jax.Array], layers: Sequence):
    """Write stacked arrays back into the per-layer modules (post-step).
    Works for params AND buffers alike (load_pytree keys by name)."""
    for i, layer in enumerate(layers):
        layer.load_pytree({n: a[i] for n, a in stacked.items()})


unstack_buffers_into_layers = unstack_into_layers


def make_stage_fn(template_layer, call: Optional[Callable] = None):
    """Build the homogeneous stage_fn: scan the stage's layer block through
    `template_layer` with per-layer params swapped in.

    template_layer is any one of the (identical-structure) layers; its
    arrays are rebound to traced slices during the scan, so the SAME module
    code runs for every layer of every stage.
    """
    from ..tensor import Tensor, as_array

    call = call or (lambda mod, x: mod(x))

    def stage_fn(local_params, x):
        # save/restore the template's own bindings (try/finally: a trace
        # error mid-scan must not leave the layer bound to dead scan
        # tracers, poisoning every later use of the model)
        saved = {n: p._data for n, p in template_layer.named_parameters()}

        def body(h, layer_params):
            template_layer.load_pytree(layer_params)
            out = call(template_layer, Tensor(h))
            return as_array(out), None

        try:
            h, _ = jax.lax.scan(body, x, local_params)
        finally:
            for n, p in template_layer.named_parameters():
                p._rebind(saved[n])
        return h

    return stage_fn


def make_stage_fn_with_buffers(template_layer,
                               call: Optional[Callable] = None):
    """Buffer-tracking stage_fn: (local_params, local_buffers, x) ->
    (y, new_local_buffers).

    The module's buffer updates (BN running stats rebind themselves during
    forward — nn/functional/norm.py batch_norm) are read back per layer
    and emitted as the scan's stacked output, so the schedule can carry
    them microbatch to microbatch — the reference PipelineLayer's
    sequential-stat semantics. The template's own buffer bindings are
    restored after the scan so no in-scan tracer leaks into the enclosing
    trace (the old gpipe failure mode for BN-in-stage models)."""
    from ..tensor import Tensor, as_array

    call = call or (lambda mod, x: mod(x))

    def stage_fn(local_params, local_buffers, x):
        # save/restore params AND buffers (try/finally: a trace error must
        # not leave the template bound to dead scan tracers)
        saved = {n: b._data for n, b in template_layer.named_buffers()}
        saved_p = {n: p._data for n, p in template_layer.named_parameters()}

        def body(h, pb):
            layer_params, layer_bufs = pb
            template_layer.load_pytree(layer_params)
            template_layer.load_pytree(layer_bufs)
            out = call(template_layer, Tensor(h))
            new_bufs = {n: as_array(b)
                        for n, b in template_layer.named_buffers()}
            return as_array(out), new_bufs

        try:
            h, new_stack = jax.lax.scan(body, x,
                                        (local_params, local_buffers))
        finally:
            for n, b in template_layer.named_buffers():
                b._rebind(saved[n])
            for n, p in template_layer.named_parameters():
                p._rebind(saved_p[n])
        return h, new_stack

    return stage_fn


def microbatch(x, num_microbatches: int):
    """[B, ...] -> [M, B//M, ...] (reference: PipelineParallel._split_micro)."""
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible by num_microbatches {num_microbatches}")
    return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])
