"""Serving, for a configuration that names its `family`: the window, the
warm-up and the end-to-end arithmetic are `drivers/serve.py`'s own; what
the family brings (`benchmark/families/<family>.py`,
`benchmark/reference/<family>.py`) is the build, the weights and the plain
reference that `check` holds the served tokens against."""
from __future__ import annotations

import importlib

import numpy as np

from benchmark import families, program, traffic
from benchmark.drivers.serve import (  # noqa: F401  (the driver's face)
    end_to_end, sample_for_check, warm, window)


class build:
    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.cfg = cell.config
        self.engine_cfg = cell.config["engine"]
        self.family = families.load(self.cfg["family"])
        self.model = self.family.build_model(self.cfg, seed)
        self.engine = self.family.build_engine(self.model, self.engine_cfg)
        self.records = []


def release(system):
    """Free the engine's page pools (whatever the cache layout calls them),
    its cached parameter tree and the model's weights, so that the
    reference has the chip to itself."""
    program.release(system.engine, system.model)
    system.engine = system.model = None


def reference_of(cfg):
    return importlib.import_module("benchmark.reference." + cfg["family"])


def logit_gaps(cfg, weights_tree, sample, pad_to, pad_out, mode="f32"):
    """`drivers/serve.py`'s `logit_gaps` against the family's reference: for
    every served token of `sample`, the gap by which its logit lies below
    the reference's best (with `mode` a lower precision, the CONTROL's
    reading: the gap of the token that precision puts first). Returns the
    gaps, one a token, and the share of (position, expert layer) whose
    top-k picks in `mode` arithmetic ("bf16", the program's stated
    precision, for the reference itself) differ as a set from the float32
    reference's."""
    reference = reference_of(cfg)
    gaps, flipped, routed = [], 0, 0
    for rec in sample:
        prompt, out = rec.request.prompt, np.asarray(rec.tokens)
        ids = np.zeros((pad_to,), np.int64)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(out)] = out
        positions = np.zeros((pad_out,), np.int64)
        positions[:len(out)] = len(prompt) - 1 + np.arange(len(out))
        picks, other_picks = [], []
        ref = reference.logits_at(weights_tree, cfg, ids, positions, "f32",
                                  picks=picks)
        other = reference.logits_at(
            weights_tree, cfg, ids, positions,
            "bf16" if mode == "f32" else mode, picks=other_picks)
        if mode == "f32":
            chosen = np.zeros((len(positions),), np.int64)
            chosen[:len(out)] = out
        else:
            chosen = other.argmax(axis=-1)
        picked = ref[np.arange(len(positions)), np.asarray(chosen)]
        gaps.append(np.asarray(ref.max(axis=-1) - picked)[:len(out)])
        for a, b in zip(picks, other_picks):
            same = np.sort(np.asarray(a), -1) == np.sort(np.asarray(b), -1)
            flipped += int((~same.all(axis=-1))[:len(out)].sum())
            routed += len(out)
    return np.concatenate(gaps), flipped / routed if routed else 0.0


def check(system, log):
    """[(name, value, limit), ...] — what decides `correct`."""
    cell, mix = system.cell, system.cell.mix
    limits = cell.params["limits"]
    sample = sample_for_check(system.records, mix["check_requests"],
                              system.seed)
    vocab = system.cfg["vocab_size"]
    out_of_vocab = sum(1 for r in system.records for t in r.tokens
                       if not 0 <= t < vocab)
    rows = [("tokens_out_of_vocab", out_of_vocab, 0)]
    if not sample:
        return rows + [("requests_checked", 0, None)]
    hi_p = traffic.length_support(mix["prompt_tokens"])[1]
    hi_o = traffic.length_support(mix["output_tokens"])[1]
    with log.span("reference"):
        gaps, flipped = logit_gaps(
            system.cfg, system.family.make_weights(
                system.cfg, system.seed, system.cfg["dtype"]),
            sample, hi_p + hi_o, hi_o)
    log.counts["tokens_checked"] = len(gaps)
    # a top-k pick that flips on rounding moves a token's logits by more
    # than rounding alone does, in the program and in a lower precision
    # alike, so the WIDEST gap does not tell the two apart and is printed
    # with no limit (a null in the cell's file); the mean and the 99th
    # percentile over the checked tokens do (PERF.md section 6)
    return rows + [
        ("logit_gap_mean", float(gaps.mean()), limits["logit_gap_mean"]),
        ("logit_gap_p99", float(np.quantile(gaps, 0.99)),
         limits["logit_gap_p99"]),
        ("logit_gap_max", float(gaps.max()), limits["logit_gap_max"]),
        ("picks_differ_share", flipped, None,
         "share of (checked position, expert layer) whose top-k picks "
         "differ between the reference in bf16 arithmetic and in float32: "
         "how often a pick flips on rounding (the program's own picks "
         "never leave the device)")]
