"""Serving, for a family whose layers keep a STATE of fixed size a sequence
(the `jamba` family's Mamba layers): `drivers/serve_family.py` in all, and
the state itself held to the reference.

A served token's logit hardly moves with the precision a state is kept in,
so `check` also compares the states the program hands a slot. Before the
engine is released, the check's longest request is served again, alone,
through the same engine and programs: once for its first token (the slot
then holds the state its prefill wrote) and once whole (the state its last
decode step left). Each Mamba layer's scan state is read back from the
slot's pool and held to the family's float32 reference at the same token
(`reference.states_at`). A layer's gap is the RMS over its d_inner
channels of ||h_e - h_ref,e|| / ||h_ref,e||, each channel's [d_state]
apart, so that the slow channels, whose long memory a state's precision
decides, count as much as the fast ones, whose states are the larger.
The FIRST Mamba layer's gaps, after the prefill and after the decode, are
the two rows the cell's file limits: its inputs are the reference's to
the served type's rounding, where a deeper layer's have drifted by more
than a state's rounding adds (PERF.md section 6); every layer's gaps are
printed beside them."""
from __future__ import annotations

import numpy as np

from benchmark import traffic
from benchmark.drivers import serve_family
from benchmark.drivers.serve_family import (  # noqa: F401  (the driver's face)
    build, end_to_end, reference_of, sample_for_check, warm, window)


def served_states(engine, family, cfg, prompt, n_out):
    """Serve `prompt` for `n_out` greedy tokens on the idle engine and read
    its slot's scan states: (the tokens, {layer: [d_state, d_inner]
    float32})."""
    while engine.has_work():
        engine.step()
    tokens = []
    rid = engine.add_request(np.asarray(prompt), max_new_tokens=n_out,
                             on_token=lambda _rid, t: tokens.append(int(t)))
    while engine.has_work():
        engine.step()
    slot = next(i for i, s in enumerate(engine.slots) if s.request_id == rid)
    return tokens, family.scan_states(engine, cfg, slot)


def replay(system):
    """The longest checked request served again: its prompt, the tokens of
    the whole replay, the states after the prefill and after the decode."""
    sample = sample_for_check(system.records,
                              system.cell.mix["check_requests"], system.seed)
    if not sample:
        return None
    prompt = np.asarray(sample[0].request.prompt)
    n_out = sample[0].request.max_new_tokens
    engine, family, cfg = system.engine, system.family, system.cfg
    _, prefill = served_states(engine, family, cfg, prompt, 1)
    tokens, decode = served_states(engine, family, cfg, prompt, n_out)
    return prompt, tokens, prefill, decode


def release(system):
    """Replay the longest checked request before the engine goes."""
    system.replayed = replay(system)
    serve_family.release(system)


def channel_gap(got, want):
    """RMS over the channels e of ||h_e - h_ref,e|| / ||h_ref,e||, for
    states [d_state, d_inner]."""
    per = np.linalg.norm(got - want, axis=0) / np.linalg.norm(want, axis=0)
    return float(np.sqrt(np.mean(np.square(per))))


def state_gaps(cfg, weights_tree, prompt, tokens, prefill, decode, pad_to):
    """(the first Mamba layer's gap after the prefill, after the decode,
    {layer: (gap after the prefill, after the decode)})."""
    ids = np.zeros((pad_to,), np.int64)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])
    ids[:len(seq)] = seq
    ref = reference_of(cfg).states_at(weights_tree, cfg, ids,
                                      [len(prompt), len(seq)])

    layers = {i: (channel_gap(prefill[i], ref[i][0]),
                  channel_gap(decode[i], ref[i][1])) for i in sorted(ref)}
    return layers[min(layers)] + (layers,)


def check(system, log):
    """`serve_family.check`'s rows and the two state gaps."""
    rows = serve_family.check(system, log)
    limits = system.cell.params["limits"]
    if system.replayed is None:
        return rows
    prompt, tokens, prefill, decode = system.replayed
    mix = system.cell.mix
    pad_to = traffic.length_support(mix["prompt_tokens"])[1] \
        + traffic.length_support(mix["output_tokens"])[1]
    with log.span("reference_states"):
        after_prefill, after_decode, layers = state_gaps(
            system.cfg, system.family.make_weights(
                system.cfg, system.seed, system.cfg["dtype"]),
            prompt, tokens, prefill, decode, pad_to)
    return rows + [
        ("state_gap_prefill", after_prefill, limits["state_gap_prefill"]),
        ("state_gap_decode", after_decode, limits["state_gap_decode"],
         f"the scan's state of the longest checked request ({len(prompt)} "
         f"prompt tokens, {len(tokens)} out) against the float32 "
         "reference's, the RMS over channels of ||h_e - h_ref,e|| / "
         f"||h_ref,e|| in the first Mamba layer: after its prefill "
         f"{after_prefill:.3g}, after its decode {after_decode:.3g}; by "
         "layer "
         + ", ".join(f"{i}: {a:.3g} / {b:.3g}"
                     for i, (a, b) in layers.items()))]
