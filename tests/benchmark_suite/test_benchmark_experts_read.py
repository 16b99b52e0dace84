"""`experts_read_pct` (PR 30): the share of the held experts whose weights
a decode step streams, read from the counts the burst puts on
`serving.emit`, on hand-made traces written with xplane_writer.py."""
import pytest

from benchmark_suite_helpers import REPO, traced  # noqa: F401

from benchmark import manifest, trace_reduce
from benchmark.hostlog import HostLog

MS = 1_000_000  # ns
CELL = "openpangu-ultra-moe-ep16-l5.decode-closed"


def _raw(emits):
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


HIT_PATH = {"expert_pairs": 30, "experts_hit": 24, "experts_read": 24,
            "expert_layer_steps": 8, "experts_held": 64}
DENSE_PATH = dict(HIT_PATH, experts_read=64)
OLDER = {k: v for k, v in HIT_PATH.items() if k != "experts_read"}


@pytest.mark.parametrize("emits, value", [
    # the ratio of the SUMMED attributes, not the mean of the ratios
    ([HIT_PATH, dict(HIT_PATH, experts_read=8, experts_held=192)],
     100 * 32 / 256),
    ([HIT_PATH, {}], 100 * 24 / 64),      # an emit after a prefill: no counts
    ([DENSE_PATH, DENSE_PATH], 100.0),    # every held expert's product
    ([dict(HIT_PATH, experts_read=0, experts_hit=0, expert_pairs=0)], 0.0)])
def test_the_share_of_the_held_experts_that_is_read(traced, emits, value):
    read = manifest.load_reader("experts_read_pct")
    cell = manifest.load_cell(CELL)
    assert read(traced(_raw(emits)), HostLog(), cell) \
        == pytest.approx(value)


@pytest.mark.parametrize("emits", [[OLDER, OLDER], [{}], []])
def test_a_program_that_does_not_count_it_reads_none(traced, emits):
    """The parent commit's burst hands `experts_hit` and `experts_held`
    and no `experts_read`: no value, and no raise."""
    cell = manifest.load_cell(CELL)
    assert manifest.load_reader("experts_read_pct")(
        traced(_raw(emits)), HostLog(), cell) is None
    if emits and emits[0]:
        assert manifest.load_reader("experts_hit_pct")(
            traced(_raw(emits)), HostLog(), cell) \
            == pytest.approx(100 * 24 / 64)


def test_no_trace_reads_none():
    cell = manifest.load_cell(CELL)
    read = manifest.load_reader("experts_read_pct")
    assert read(None, HostLog(), cell) is None
    assert read(trace_reduce.reduce({"planes": []}), HostLog(), cell) is None


def test_the_manifest_lists_it_for_the_cell_that_counted_it_first():
    m = manifest.load_manifest(REPO)
    entry = next(p for p in m["per_layer"] if p["name"] == "experts_read_pct")
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "experts_read_pct", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "tpot_p95_ms"}
    assert CELL in entry["workloads"]
    assert "experts_read_pct" in {
        e["name"] for e in manifest.load_cell(CELL).per_layer}
