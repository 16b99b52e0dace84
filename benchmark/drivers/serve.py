"""Serving: `ServingEngine.add_request` / `step` with `on_token`, under an
open loop (arrivals on a schedule at the cell's fixed rate) or a closed one
(each client sends its next request when the last is answered), as the
mix's `arrivals.process` says."""
from __future__ import annotations

import numpy as np

from benchmark import program, serve_loop, traffic, weights
from benchmark.reference import gpt as reference


class build:
    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.cfg = cell.config
        self.engine_cfg = cell.config["engine"]
        self.model = program.build_model(self.cfg, seed, train=False)
        self.engine = program.build_engine(self.model, self.engine_cfg)
        self.records = []


def warm(system, log):
    """Compile and run once every program the mix's lengths can meet: one
    prefill per (requests admitted together, token bucket) the engine's own
    policy returns — the page write after a prefill is shaped by the
    number of requests, not by the batch bucket — then the engine's own
    `warmup()` for the burst and single-step decode programs."""
    engine, mix = system.engine, system.cell.mix
    lo, hi = traffic.length_support(mix["prompt_tokens"])
    rounds = program.prefill_rounds(engine, lo, hi)
    for (n, nb, bucket), length in rounds:
        with log.span("warm_prefill"):
            for _ in range(n):
                engine.add_request(np.zeros((length,), np.int64),
                                   max_new_tokens=1)
            while engine.has_work():
                engine.step()
    with log.span("warm_decode"):
        engine.warmup()
    log.counts["prefill_programs_warmed"] = len(rounds)


def window(system, seconds, log, tick=None):
    cell, engine = system.cell, system.engine
    clients = int(cell.mix["arrivals"].get("clients", 0))
    requests = traffic.serve_requests(
        cell.mix, system.seed, seconds, system.cfg["vocab_size"],
        rate=cell.params.get("rate_per_s"))
    compiles0 = program.compile_entries()
    system.records = serve_loop.run_window(
        engine, requests, seconds, log,
        drain_seconds=cell.mix["drain_seconds"], clients=clients,
        slot_tokens=(system.engine_cfg["max_batch"]
                     * system.engine_cfg["decode_burst"]),
        stats=lambda: program.engine_stats(engine), tick=tick)
    log.counts["compiles_in_window"] = program.compile_entries() - compiles0


def end_to_end(system, seconds, log):
    return serve_loop.end_to_end(system.records, seconds, log)


def release(system):
    program.release(system.engine, system.model)
    system.engine = system.model = None


def sample_for_check(records, n, seed):
    """`n` finished requests drawn from the seed, and the longest one."""
    done = [r for r in records
            if r.done and len(r.tokens) == r.request.max_new_tokens]
    if not done:
        return []
    rng = np.random.default_rng(int(seed) + 1)
    longest = max(done, key=lambda r: len(r.request.prompt) + len(r.tokens))
    picks = [done[i] for i in rng.permutation(len(done))[:n]]
    return [longest] + [r for r in picks if r is not longest]


def logit_gaps(cfg, seed, sample, pad_to, pad_out, mode="f32",
               weights_tree=None):
    """The widest gap by which a served token's logit lies below the plain
    reference's best, over every served token of `sample`; with `mode` a
    lower precision, the CONTROL's reading instead: the gap of the token
    that precision puts first. Also returns how many tokens were compared.

    Each sequence is padded to `pad_to` tokens and `pad_out` positions
    (causal attention: padding after the end changes nothing before it), so
    one program serves all."""
    w = weights_tree or weights.make(cfg, seed, cfg["dtype"])
    widest, compared = 0.0, 0
    for rec in sample:
        prompt, out = rec.request.prompt, np.asarray(rec.tokens)
        ids = np.zeros((pad_to,), np.int64)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(out)] = out
        positions = np.zeros((pad_out,), np.int64)
        positions[:len(out)] = len(prompt) - 1 + np.arange(len(out))
        ref = reference.logits_at(w, cfg, ids, positions, "f32")
        best = ref.max(axis=-1)
        if mode == "f32":
            chosen = np.zeros((len(positions),), np.int64)
            chosen[:len(out)] = out
        else:
            chosen = reference.logits_at(w, cfg, ids, positions,
                                         mode).argmax(axis=-1)
        picked = ref[np.arange(len(positions)), np.asarray(chosen)]
        gaps = np.asarray(best - picked)[:len(out)]
        widest = max(widest, float(gaps.max()))
        compared += len(out)
    return widest, compared


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
        gap, compared = logit_gaps(system.cfg, system.seed, sample,
                                   hi_p + hi_o, hi_o)
    log.counts["tokens_checked"] = compared
    return rows + [("logit_gap_max", gap, limits["logit_gap_max"])]
