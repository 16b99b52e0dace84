"""Training: the compiled step `build_train_step` returns, fed a fresh
seeded batch from the host every step, steps dispatched back to back.

Set-up builds ONE object — the step with its state — and drives it through
its first `reference_steps` steps by the window's own call and feed; the
window goes on with that same object. What those steps produced (each
loss, the first gradient's norm per leaf as the optimizer got it, the
parameters' change per leaf) is what `check` holds against the plain
reference once the window has closed and the program's state is freed.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import program, traffic, weights
from benchmark.reference import gpt as reference


class build:
    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.cfg, self.trainer = cell.config, cell.config["trainer"]
        self.rng = np.random.default_rng(int(seed))
        self.model = program.build_model(
            self.cfg, seed, train=True, recompute=self.trainer["recompute"])
        self.step, self.opt = program.build_trainer(self.model, self.trainer)
        self.first_batches = []
        self.readings = {"losses": []}   # what `check` holds up
        self.last_loss = None
        self.steps = 0
        self.elapsed = None

    def feed(self):
        """The window's feed: one fresh host batch."""
        return traffic.train_batch(self.cell.mix, self.rng,
                                   self.cfg["vocab_size"])

    def call(self, batch):
        """The window's call."""
        x, y = batch
        self.last_loss = self.step(program.to_tensor(x),
                                   program.to_tensor(y))
        self.steps += 1
        return self.last_loss

    def sync(self):
        return float(self.last_loss)


@jax.jit
def _first_grad_norms(state, beta1):
    # moment1 after one step from zero is (1 - beta1) g
    return {k: jnp.sqrt(jnp.sum(jnp.square(v["moment1"]))) / (1 - beta1)
            for k, v in state.items()}


@jax.jit
def _changes(now, start):
    """Per leaf, the norm of (now - start), and for the vector leaves (the
    biases and LayerNorm leaves: a few MB in all) the change itself."""
    delta = {k: now[k].astype(jnp.float32) - start[k].astype(jnp.float32)
             for k in now}
    return ({k: jnp.sqrt(jnp.sum(jnp.square(d))) for k, d in delta.items()},
            {k: d for k, d in delta.items() if d.ndim == 1})


def _program_changes(system):
    """Of the float32 master weight where the optimizer keeps one, else of
    the parameter itself, against the seed's weight. The seed's weights
    are made again as arrays of their own BEFORE the subtraction is traced:
    inside one program XLA may keep them in excess precision, never
    rounding to the served type, and the difference then reads as a
    change."""
    state = program.optimizer_state(system.step)
    params = system.model.parameters_pytree()
    now = {k: v.get("master_weight", params[k]) for k, v in state.items()}
    start = weights.make(system.cfg, system.seed, system.cfg["dtype"])
    norms, vectors = _changes(now, start)
    return ({k: float(v) for k, v in norms.items()},
            {k: np.asarray(v) for k, v in vectors.items()})


def warm(system, log):
    readings = system.readings
    for i in range(int(system.cell.mix["reference_steps"])):
        batch = system.feed()
        system.first_batches.append(batch)
        with log.span("warm_step"):
            system.call(batch)
            readings["losses"].append(system.sync())
        if i == 0:
            got = _first_grad_norms(
                {k: {"moment1": v["moment1"]} for k, v in
                 program.optimizer_state(system.step).items()},
                jnp.float32(system.trainer["beta1"]))
            readings["grad_norms"] = {k: float(v) for k, v in got.items()}
    readings["change_norms"], readings["change_vectors"] = \
        _program_changes(system)
    system.steps = 0


def window(system, seconds, log, tick=None):
    clock = log.clock
    every = int(system.cell.mix["loss_every"])
    compiles0 = program.compile_entries()
    t_open = clock()
    while clock() - t_open < seconds:
        if tick is not None:
            tick(clock() - t_open)
        with log.span("feed"):
            batch = system.feed()
        with log.span("step"):
            system.call(batch)
        if system.steps % every == 0:
            with log.span("loss_read"):
                system.sync()
    with log.span("loss_read"):
        final = system.sync()
    t_close = clock()
    system.elapsed = t_close - t_open
    log.counts["compiles_in_window"] = program.compile_entries() - compiles0
    log.counts["final_loss"] = final


def end_to_end(system, seconds, log):
    """All tokens of all steps the window dispatched over all the time
    until the last of them had finished."""
    mix = system.cell.mix
    tokens = system.steps * int(mix["batch_rows"]) * int(mix["seq_len"])
    return ({"train_tokens_per_s": tokens / system.elapsed},
            system.steps, 0)


def release(system):
    program.release(system.step, system.model)
    system.step = system.model = system.opt = None


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def reference_readings(cfg, trainer, seed, batches, mode="f32", rows=None,
                       frozen=False):
    """The plain reference through the same first steps, as the readings
    `warm` takes of the program (plus the first gradient of the vector
    leaves, for the rule on it). `mode`, `rows` and `frozen` plant the
    control and the faults (a lower precision; half of the batch left out;
    a step that returns its state unchanged)."""
    start = weights.make(cfg, seed, cfg["dtype"])
    params = {k: jnp.array(v, jnp.float32, copy=True)
              for k, v in start.items()}
    state = reference.adamw_init(params)
    out = {"losses": []}
    for i, (x, y) in enumerate(batches):
        loss, grads = reference.loss_and_grads(params, cfg, x, y, mode, rows)
        out["losses"].append(loss)
        if i == 0:
            out["grad_norms"] = reference.leaf_norms(grads)
            out["grad_vectors"] = {k: np.asarray(v) for k, v in grads.items()
                                   if v.ndim == 1}
        if not frozen:
            params, state = reference.adamw_step(params, grads, state,
                                                 trainer)
        del grads
    norms, vectors = _changes(params, start)
    out["change_norms"] = {k: float(v) for k, v in norms.items()}
    out["change_vectors"] = {k: np.asarray(v) for k, v in vectors.items()}
    return out


def worst_leaf_gap(got: dict, want: dict):
    """The widest gap between the program's norm of a leaf and the
    reference's — not the norm of their difference — measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). Returns (gap, leaf)."""
    median = statistics.median(want.values())
    worst = max(want, key=lambda k: abs(got[k] - want[k])
                / max(want[k], median))
    return abs(got[worst] - want[worst]) / max(want[worst], median), worst


GRADIENT_FLOOR = 1e-3  # of the median leaf's


def moving_change_norms(readings: dict, ref: dict):
    """(program's, reference's) parameter-change norm per leaf, leaving out
    what the reference's first gradient says moves under Adam by round-off
    alone: a leaf whose gradient norm is under a thousandth of the median
    leaf's — and, inside a VECTOR leaf, the elements whose gradient is
    under a thousandth of the median leaf's root-mean-square element (a
    fused QKV bias holds the key's bias, whose gradient is nought under
    softmax, beside the query's and the value's, whose is not). The rule
    is on the reference's gradient, never on a name."""
    grads = ref["grad_norms"]
    floor = GRADIENT_FLOOR * statistics.median(grads.values())
    sizes = {k: v.size for k, v in ref["grad_vectors"].items()}
    element_floor = GRADIENT_FLOOR * statistics.median(
        grads[k] / np.sqrt(n) for k, n in sizes.items())
    got, want = {}, {}
    for k in grads:
        if grads[k] < floor:
            continue
        if k in ref["grad_vectors"]:
            keep = np.abs(ref["grad_vectors"][k]) >= element_floor
            if not keep.any():
                continue
            got[k] = float(np.linalg.norm(readings["change_vectors"][k][keep]))
            want[k] = float(np.linalg.norm(ref["change_vectors"][k][keep]))
        else:
            got[k] = readings["change_norms"][k]
            want[k] = ref["change_norms"][k]
    return got, want


def compare(readings: dict, ref: dict, limits: dict):
    """[(name, value, limit[, note]), ...] of one side against the
    reference; the note names the worst leaf and its two norms."""
    rows = [(f"loss_gap_step{i + 1}", abs(a - b), limits.get("loss_gap"))
            for i, (a, b) in enumerate(zip(readings["losses"],
                                           ref["losses"]))]
    got, want = readings["grad_norms"], ref["grad_norms"]
    gap, leaf = worst_leaf_gap(got, want)
    rows.append(("grad_norm_gap_worst_leaf", gap, limits.get("grad_norm_gap"),
                 f"{leaf}: {got[leaf]:.6g} vs {want[leaf]:.6g}"))
    got, want = moving_change_norms(readings, ref)
    gap, leaf = worst_leaf_gap(got, want)
    rows.append(("param_change_gap_worst_leaf", gap,
                 limits.get("param_change_gap"),
                 f"{leaf}: {got[leaf]:.6g} vs {want[leaf]:.6g}"))
    return rows


def check(system, log):
    with log.span("reference"):
        ref = reference_readings(system.cfg, system.trainer, system.seed,
                                 system.first_batches)
    return compare(system.readings, ref, system.cell.params["limits"])
