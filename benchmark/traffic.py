"""The one general traffic generator: a mix file's parameters + a seed -> inputs.

A mix is data (`benchmark/traffic/<mix>.json`); a later PR adds a mix by
adding a file. Every seed is given the SAME multiset of lengths and of
inter-arrival gaps — the evenly spaced quantiles of the mix's distributions —
and other token ids: the work of a run is fixed. Their ORDER is drawn from
the seed too, unless the mix fixes it with a `schedule_seed`: below capacity
a tail depends on which long requests arrive together, and with 56 requests
in a window the order alone moved `ttft_p95_ms` from 2.2 to 4.4 s between
seeds, against 2-8% between two runs of one seed (my chip runs, PR 24). The
program receives nothing but what is generated here.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due: float            # seconds after the window opens (open loop)
    prompt: np.ndarray    # int64 token ids
    max_new_tokens: int
    client: int = -1      # closed loop: whose request this is


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n evenly spaced quantiles ((k + 0.5) / n) of `dist`."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, float(dist["value"]))
    if kind == "uniform":
        return dist["lo"] + u * (dist["hi"] - dist["lo"])
    if kind == "log_uniform":
        return np.exp(math.log(dist["lo"])
                      + u * (math.log(dist["hi"]) - math.log(dist["lo"])))
    if kind == "exponential":          # mean 1
        return -np.log1p(-u)
    if kind == "gamma":                # mean 1, coefficient of variation cv
        # quantiles of a gamma by sorting one fixed large sample: no scipy
        shape = 1.0 / dist["cv"] ** 2
        sample = np.sort(np.random.default_rng(0).gamma(
            shape, 1.0 / shape, size=64 * n))
        return sample[(u * len(sample)).astype(int)]
    raise ValueError(f"unknown distribution {kind!r}")


def _lengths(dist: dict, n: int, rng) -> np.ndarray:
    return rng.permutation(np.rint(_quantiles(dist, n)).astype(int))


def length_support(dist: dict) -> tuple:
    """(shortest, longest) length the distribution can give."""
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["lo"]), int(dist["hi"])


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   rate: float = None) -> list:
    """The requests of one run of a serving mix.

    Open loop (`arrivals.process` "poisson" or "gamma"): floor(rate x
    seconds) requests whose gaps are the process's quantiles scaled to the
    rate, so every one is due inside the window. Closed loop
    (`arrivals.process` "closed"): `arrivals.pool` requests dealt to
    `arrivals.clients` clients in turn; a client's next request is due when
    its last is answered, so `due` is 0."""
    ids_rng = np.random.default_rng(int(seed))
    rng = np.random.default_rng(int(mix["schedule_seed"])) \
        if "schedule_seed" in mix else ids_rng
    arrivals = mix["arrivals"]
    if arrivals["process"] == "closed":
        n = int(arrivals["pool"])
        due = np.zeros(n)
        clients = np.arange(n) % int(arrivals["clients"])
    else:
        n = int(math.floor(rate * seconds))
        gap_dist = {"dist": "exponential"} \
            if arrivals["process"] == "poisson" \
            else {"dist": "gamma", "cv": arrivals["cv"]}
        gaps = rng.permutation(_quantiles(gap_dist, n)) / rate
        due = np.cumsum(gaps) - gaps[0]
        clients = np.full(n, -1)
    prompts = _lengths(mix["prompt_tokens"], n, rng)
    outputs = _lengths(mix["output_tokens"], n, rng)
    prefix = mix.get("shared_prefix")
    if prefix:
        pool = [ids_rng.integers(0, vocab, int(k)) for k in _lengths(
            prefix["tokens"], int(prefix["pool"]), rng)]
    out = []
    for i in range(n):
        ids = ids_rng.integers(0, vocab, int(prompts[i]))
        if prefix:
            head = pool[int(rng.integers(len(pool)))][:len(ids) - 1]
            ids[:len(head)] = head
        out.append(Request(i, float(due[i]), ids.astype(np.int64),
                           int(outputs[i]), int(clients[i])))
    return out


def train_batch(mix: dict, rng, vocab: int):
    """One fresh batch (inputs, labels) of the training mix, every row
    different: uniform token ids, labels drawn independently."""
    shape = (int(mix["batch_rows"]), int(mix["seq_len"]))
    return (rng.integers(0, vocab, shape).astype(np.int64),
            rng.integers(0, vocab, shape).astype(np.int64))
