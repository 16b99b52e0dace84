"""`prefill_expert_rows_per_pair` (PR 32): the rows of expert products a
prefill takes over the (live token, held expert) pairs it made, read from
the counts a prefill program puts on the `serving.emit` phase that commits
its first tokens, on hand-made traces written with xplane_writer.py."""
import pytest

from benchmark_suite_helpers import REPO, traced  # noqa: F401

from benchmark import manifest, trace_reduce
from benchmark.hostlog import HostLog

MS = 1_000_000  # ns
NAME = "prefill_expert_rows_per_pair"
CELLS = ["openpangu-ultra-moe-ep16-l5.decode-closed",
         "trinity-mini-ep8.mixed-closed"]


def _raw(emits):
    """A traced window with one prefill, one burst and `emits` as the
    attributes of its `serving.emit` phases."""
    host = [["bench.traced_window", 0, 100 * MS, {}],
            ["serving.prefill.sync", 30 * MS, 5 * MS, {}],
            ["serving.decode.sync", 40 * MS, 20 * MS, {}]]
    host += [["serving.emit", (61 + 4 * i) * MS, 2 * MS, attrs]
             for i, attrs in enumerate(emits)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules",
             "events": [["jit_pure_prefill(11)", 10 * MS, 20 * MS],
                        ["jit_pure_burst(13)", 40 * MS, 20 * MS]]},
            {"name": "XLA Ops",
             "events": [["fusion.2", 10 * MS, 20 * MS,
                         "jit(pure_prefill)/mlp/experts/dot_general"],
                        ["while.4", 40 * MS, 20 * MS,
                         "jit(pure_burst)/while"]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


BURST = {"expert_pairs": 30, "experts_hit": 24, "experts_read": 24,
         "expert_rows": 96, "expert_layer_steps": 8, "experts_held": 64}
GROUPED = {"prefill_expert_pairs": 1024, "prefill_expert_rows": 4096}
DENSE = {"prefill_expert_pairs": 1024, "prefill_expert_rows": 2048 * 16}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("emits, value", [
    ([GROUPED, BURST], 4.0),
    ([DENSE, BURST, BURST], 32.0),
    # the ratio of the SUMMED attributes, not the mean of the ratios
    ([GROUPED, BURST, {"prefill_expert_pairs": 3072,
                       "prefill_expert_rows": 4096}], 2.0),
    # a prefill that made no pair carries nothing to divide by
    ([GROUPED, {"prefill_expert_pairs": 0, "prefill_expert_rows": 0}], 4.0)])
def test_the_rows_a_prefill_takes_for_each_pair(traced, emits, value, cell):
    read = manifest.load_reader(NAME)
    assert read(traced(_raw(emits)), HostLog(), manifest.load_cell(cell)) \
        == pytest.approx(value)


@pytest.mark.parametrize("emits", [
    [BURST, BURST], [{}], [],
    [{"prefill_expert_pairs": 7}]])   # half a count: no value, no raise
def test_a_program_that_does_not_count_it_reads_none(traced, emits):
    """The parent commit's prefill hands nothing on, and its burst's
    phases carry the burst's counts alone: no value, and no raise; the
    burst's own readers read what they read."""
    cell = manifest.load_cell(CELLS[0])
    assert manifest.load_reader(NAME)(
        traced(_raw(emits)), HostLog(), cell) is None
    if emits and emits[0] == BURST:
        assert manifest.load_reader("expert_pairs_per_step")(
            traced(_raw(emits)), HostLog(), cell) == pytest.approx(30 / 8)


def test_the_bursts_readers_do_not_see_the_prefills_counts(traced):
    cell = manifest.load_cell(CELLS[0])
    alone = traced(_raw([BURST]))
    values = {n: manifest.load_reader(n)(alone, HostLog(), cell)
              for n in ("expert_pairs_per_step", "experts_hit_pct",
                        "experts_read_pct")}
    both = traced(_raw([GROUPED, BURST, DENSE]))
    assert values == {n: manifest.load_reader(n)(both, HostLog(), cell)
                      for n in values}


def test_no_trace_reads_none():
    cell = manifest.load_cell(CELLS[1])
    read = manifest.load_reader(NAME)
    assert read(None, HostLog(), cell) is None
    assert read(trace_reduce.reduce({"planes": []}), HostLog(), cell) is None


def test_the_manifest_lists_it_for_the_expert_cells():
    entry = next(p for p in manifest.load_manifest(REPO)["per_layer"]
                 if p["name"] == NAME)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "tpot_p95_ms"}
    assert set(CELLS) <= set(entry["workloads"])
    for cell in CELLS:
        assert NAME in {e["name"]
                        for e in manifest.load_cell(cell).per_layer}
