"""On hand-made traces written with xplane_writer.py: the ratio every
counter reader takes of the counts a burst hands to `serving.emit`
(`program_subscopes.emit_pct`, here on the full-attention layers' page
counts, `attn_pages_read` of `attn_pages_mapped`, which
`cache_attn_decode_roofline` reads the first of), and
`train_sub_ms.flash_attn`, the reader queued behind the no-edit rule since
PR 34 (the train step's device time under `attn/flash`)."""
import pytest

from benchmark_suite_helpers import (MS, REPO, pangu_raw,  # noqa: F401
                                     traced, train_raw)

from benchmark import manifest, program_subscopes, trace_reduce
from benchmark.hostlog import HostLog

FLASH = "train_sub_ms.flash_attn"


def _cells_listing(name):
    entry = next(p for p in manifest.load_manifest(REPO)["per_layer"]
                 if p["name"] == name)
    return entry, [manifest.load_cell(c) for c in entry["workloads"]]


def _with_emits(emits):
    """A traced window with one burst and `emits` as the attributes of its
    `serving.emit` phases."""
    host = [["bench.traced_window", 0, 100 * MS, {}],
            ["serving.decode.sync", 40 * MS, 20 * MS, {}]]
    host += [["serving.emit", (61 + 4 * i) * MS, 2 * MS, attrs]
             for i, attrs in enumerate(emits)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules",
             "events": [["jit_pure_burst(13)", 40 * MS, 20 * MS]]},
            {"name": "XLA Ops",
             "events": [["while.4", 40 * MS, 20 * MS,
                         "jit(pure_burst)/while"]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


LIVE = {"attn_pages_read": 10, "attn_pages_mapped": 64}
EXPERTS = {"expert_pairs": 30, "experts_hit": 24, "experts_held": 64}


@pytest.mark.parametrize("emits, value", [
    # the ratio of the SUMMED counts, not the mean of the ratios
    ([LIVE, {"attn_pages_read": 54, "attn_pages_mapped": 192}],
     100 * 64 / 256),
    ([LIVE, {}], 100 * 10 / 64),          # an emit after a prefill: no counts
    ([dict(LIVE, **EXPERTS), LIVE], 100 * 20 / 128),
    ([dict(LIVE, attn_pages_read=64)], 100.0),  # every mapped page gathered
    ([dict(LIVE, attn_pages_read=0)], 0.0)])    # no live row in the burst
def test_the_share_of_the_mapped_pages_a_decode_step_reads(traced, emits,
                                                           value):
    assert program_subscopes.emit_pct(
        traced(_with_emits(emits)), "attn_pages_read", "attn_pages_mapped") \
        == pytest.approx(value)


@pytest.mark.parametrize("emits", [
    [EXPERTS, EXPERTS],                   # a latent mixer: no paged layer
    [{}], [],
    [{"attn_pages_mapped": 64}]])         # half a count: no value, no raise
def test_a_burst_that_does_not_count_its_pages_reads_none(traced, emits):
    assert program_subscopes.emit_pct(
        traced(_with_emits(emits)), "attn_pages_read",
        "attn_pages_mapped") is None


def test_the_flash_kernels_time_in_a_train_step(traced):
    entry, cells = _cells_listing(FLASH)
    assert cells and all(c.mix["kind"] == "train" for c in cells)
    assert entry["moves"] == "train_tokens_per_s" and entry["unit"] == "ms"
    read = manifest.load_reader(FLASH)
    reduced = traced(train_raw())
    assert read(reduced, HostLog(), cells[0]) == pytest.approx(10.0)
    # a part of `train_ms.attn`, which holds the projections too
    assert manifest.load_reader("train_ms.attn")(
        reduced, HostLog(), cells[0]) == pytest.approx(30.0)
    # two executions of the step: per execution
    raw = train_raw()
    modules, ops = (ln["events"] for ln in raw["planes"][0]["lines"])
    modules[0][2] = 40 * MS
    modules.append(["jit_pure_step(9)", 50 * MS, 40 * MS])
    del ops[2:]
    ops.append(["call.7", 60 * MS, 4 * MS,
                "jit(pure_step)/jvp(attn)/flash/flash_fwd"])
    assert read(traced(raw), HostLog(), cells[0]) == pytest.approx(7.0)


@pytest.mark.parametrize("why", ["no trace", "empty trace",
                                 "attention in XLA's fusions",
                                 "a serving program"])
def test_no_flash_scope_no_value(traced, why):
    _, cells = _cells_listing(FLASH)
    read = manifest.load_reader(FLASH)
    if why == "no trace":
        reduced = None
    elif why == "empty trace":
        reduced = trace_reduce.reduce({"planes": []})
    elif why == "a serving program":
        reduced = traced(pangu_raw())
    else:
        raw = train_raw()
        for ev in raw["planes"][0]["lines"][1]["events"]:
            ev[3] = ev[3].replace("/flash/flash_bwd_dq", "/dot_general")
        reduced = traced(raw)
        assert manifest.load_reader("train_ms.attn")(
            reduced, HostLog(), cells[0]) == pytest.approx(30.0)
    assert read(reduced, HostLog(), cells[0]) is None
