"""The prefill programs an engine can be asked for: the scheduler policy's
(batch, token) buckets, and the rounds the engine prefills in. An engine
whose sequences span at most `PAGE_BUCKETS_MAX` pages (the accepted serving
cells' and the default engine among them) meets exactly the buckets it met
before the rule existed; a longer one, as 8 slots x 9,216 tokens at pages
of 256, pads to powers of two of its pages and prefills a prompt a round:
7 prefill programs (at most 32 allowed), whatever max_seq_len / page_size
is."""
import itertools
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.inference import ServingEngine, scheduler  # noqa: E402

from benchmark import manifest, traffic  # noqa: E402
from benchmark.families import afmoe as family  # noqa: E402


def engine_of(cell):
    e = cell.config["engine"]
    return types.SimpleNamespace(max_batch=e["max_batch"],
                                 max_seq_len=e["max_seq_len"],
                                 page_size=e["page_size"])


def group(lengths):
    return [(i, range(n)) for i, n in enumerate(lengths)]


def buckets_met(cell, policy, most_rows=None):
    """Every (batch, token) bucket the cell's prompts can meet: any number
    of requests admitted together (up to `most_rows` a round), the longest
    of any length the mix gives."""
    eng = engine_of(cell)
    lo, hi = traffic.length_support(cell.mix["prompt_tokens"])
    return {policy.prefill_bucket(eng, group([longest] + [lo] * (n - 1)))
            for n in range(1, (most_rows or eng.max_batch) + 1)
            for longest in range(lo, hi + 1)}


def todays(eng, lengths):
    """The bucket pair as it was before this file existed: next power of
    two of the count capped at max_batch, next page multiple of the
    longest."""
    nb = 1
    while nb < len(lengths):
        nb *= 2
    return (min(nb, eng.max_batch),
            -(-max(lengths) // eng.page_size) * eng.page_size)


@pytest.mark.parametrize("name, batches, tokens", [
    ("gpt3-1.3b.chat-open", (1, 2, 4, 8), (256, 512, 768, 1024)),
    ("openpangu-ultra-moe-ep16-l5.decode-closed", (1, 2, 4, 8, 16),
     (256, 512, 768, 1024)),
])
def test_the_accepted_cells_meet_exactly_todays_buckets(name, batches,
                                                        tokens):
    cell = manifest.load_cell(name)
    policy = scheduler.FifoSchedulerPolicy()
    eng = engine_of(cell)
    assert buckets_met(cell, policy) == set(itertools.product(batches,
                                                              tokens))
    lo, hi = traffic.length_support(cell.mix["prompt_tokens"])
    for n in range(1, eng.max_batch + 1):
        for longest in (lo, lo + 1, 700, hi - 1, hi):
            lengths = [longest] + [lo] * (n - 1)
            assert policy.prefill_bucket(eng, group(lengths)) \
                == todays(eng, lengths)


def test_an_engine_of_few_pages_keeps_a_program_a_page_multiple():
    """Up to PAGE_BUCKETS_MAX pages a sequence (the default engine has
    exactly that many): every page multiple, every batch bucket, whatever
    the prompt's length."""
    assert scheduler.PAGE_BUCKETS_MAX == 16
    policy = scheduler.FifoSchedulerPolicy()
    for max_seq_len, page in ((256, 16), (4096, 256), (2048, 128)):
        eng = types.SimpleNamespace(max_batch=8, max_seq_len=max_seq_len,
                                    page_size=page)
        for n in (1, 2, 3, 5, 8):
            for longest in (1, page, page + 1, 5 * page - 1, 9 * page + 1,
                            max_seq_len - 1, max_seq_len):
                lengths = [longest] + [1] * (n - 1)
                assert policy.prefill_bucket(eng, group(lengths)) \
                    == todays(eng, lengths)


def test_a_long_engine_pads_to_powers_of_two_of_its_pages():
    eng = types.SimpleNamespace(max_batch=8, max_seq_len=9216, page_size=256)
    policy = scheduler.FifoSchedulerPolicy()
    want = {1: 256, 256: 256, 257: 512, 513: 1024, 1000: 1024, 1024: 1024,
            1025: 2048, 2048: 2048, 2049: 4096, 4097: 8192, 8192: 8192,
            8193: 9216, 9215: 9216}
    for longest, bucket in want.items():
        assert policy.prefill_bucket(eng, group([longest])) == (1, bucket)
    every = {policy.prefill_bucket(eng, group([n]))[1]
             for n in range(1, eng.max_seq_len)}
    assert sorted(every) == [256, 512, 1024, 2048, 4096, 8192, 9216]
    # the first of the prompts admitted together is the round
    assert policy.prefill_bucket(eng, group([300, 8000, 20])) == (1, 512)
    # a page that is no power of two: the power of two of the PAGES
    eng.page_size, eng.max_seq_len = 96, 9600
    assert policy.prefill_bucket(eng, group([3000])) == (1, 32 * 96)
    # one page more than PAGE_BUCKETS_MAX is a long engine
    eng.page_size, eng.max_seq_len = 16, 17 * 16
    assert policy.prefill_bucket(eng, group([33, 40])) == (1, 64)
    assert policy.prefill_bucket(eng, group([16 * 16 + 1])) == (1, 17 * 16)


def test_the_mixed_cells_engine_has_at_most_32_prefill_programs():
    """36 pages a sequence: a prompt a round, so its programs are its
    token buckets: 7 for this engine, 6 for the cell's prompts."""
    cell = manifest.load_cell("trinity-mini-ep8.mixed-closed")
    policy = scheduler.FifoSchedulerPolicy()
    eng = engine_of(cell)
    assert (eng.max_batch, eng.max_seq_len, eng.page_size) == (8, 9216, 256)
    met = buckets_met(cell, policy)
    assert met == {(1, b) for b in (256, 512, 1024, 2048, 4096, 8192)}
    assert len(met | {(1, 9216)}) <= 32


def _tiny_engine(max_seq_len):
    with open(os.path.join(REPO, "tests", "benchmark_suite", "data",
                           "configs", "tiny-afmoe.json")) as f:
        cfg = json.load(f)
    model = family.build_model(cfg, 3)
    return ServingEngine(model, max_batch=4, max_seq_len=max_seq_len,
                         page_size=8, decode_burst=4)


def test_the_engine_prefills_in_the_rounds_the_policy_sizes():
    """Prompts admitted together, 20 pages a sequence: one program a token
    bucket, the batch bucket always 1. At 16 pages the same layout (rings
    and all) keeps its one batched round."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n) for n in (20, 17, 23, 3, 90)]
    eng = _tiny_engine(160)
    for p in prompts:
        eng.add_request(p, max_new_tokens=3)
    long_out = eng.run()
    assert len(long_out) == 5
    assert sorted(k[:2] for k in eng._prefill_fns) == [(1, 8), (1, 32),
                                                       (1, 128)]
    assert {k[0] for k in eng._page_write_fns} == {1}
    eng = _tiny_engine(128)
    for p in prompts:
        eng.add_request(p, max_new_tokens=3)
    out = eng.run()
    assert sorted(k[:2] for k in eng._prefill_fns) == [(1, 96), (4, 24)]
    # the same tokens whichever way the rounds were cut
    assert sorted(list(r.output_ids) for r in out) \
        == sorted(list(r.output_ids) for r in long_out)
