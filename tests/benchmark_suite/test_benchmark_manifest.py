"""BENCHMARK.json against the contract's rules that a first benchmark is most
often refused for, and the command's refusal to measure off a TPU.

THE MANIFEST IS THE ONLY LIST of which cell reports which metric. Every
rule here is a function of a loaded manifest (`INVARIANTS`, `check_pair`),
so that it holds for the repo's, for the tests' tiny one and for a copy
with an entry and a cell appended, and it says a PROPERTY of what the
manifest lists: no test of the benchmark asserts a position in, the length
of, or the whole membership of one of its lists. A PR that appends an
entry or a cell breaks none of them."""
import copy
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark_suite_helpers import (DATA, HAND_MADE, REPO, TEST_PEAKS,
                                     hand_made, traced)  # noqa: F401

from benchmark import families, manifest, trace_reduce
from benchmark.hostlog import HostLog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = os.path.join(REPO, "benchmark")


def _bench_dir(root):
    return BENCH if root == REPO else DATA


@pytest.fixture(scope="module", params=[REPO, DATA],
                ids=["BENCHMARK.json", "tests-data"])
def loaded(request):
    root = request.param
    return manifest.load_manifest(root), root, _bench_dir(root)


def the_contracts_keys(m, root, bench_dir):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["paths"]) <= 16 and len(m["command"]) <= 32
    assert len(json.dumps(m)) < 64 * 1024


def names_units_and_lines(m, root, bench_dir):
    entries = m["configs"] + m["workloads"] + m["end_to_end"] + m["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in m[group]]
        assert len(names) == len(set(names))
    metrics = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for e in m["configs"]:
        assert set(e) == {"name", "source", "file", "reduced", "why"}
        assert len(e["reduced"]) <= 16
    for e in m["workloads"]:
        assert set(e) == {"name", "config", "traffic", "chips", "why"}
        assert e["chips"] in (1, 4)
        assert NAME.match(e["traffic"]) and NAME.match(e["config"])
    for e in m["configs"] + m["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] \
            and "\t" not in e["why"], e["name"]
    for e in m["per_layer"]:
        assert 1 <= len(e["layer"]) <= 200 and "\n" not in e["layer"]


def setup_s_is_reported_everywhere_with_the_bound_0_1(m, root, bench_dir):
    setup = next(e for e in m["end_to_end"] if e["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] == 0.1


def every_cell_has_its_files_and_every_config_a_cell(m, root, bench_dir):
    used = set()
    pairs = set()
    for w in m["workloads"]:
        cell = manifest.load_cell(w["name"], root=root, bench_dir=bench_dir)
        used.add(cell.config_name)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "drivers", cell.mix["kind"] + ".py"))
        assert "limits" in cell.params
        # every cell reports setup_s, another end-to-end metric and at
        # least one per-layer metric
        names = {e["name"] for e in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
    assert used == {c["name"] for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))


def config_files_state_their_cut(m, root, bench_dir):
    for c in m["configs"]:
        cfg = manifest._read(os.path.join(root, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        for key in ("source", "assumed", "deployment"):
            assert key in cfg, (c["name"], key)
        for key in c["reduced"]:
            # a width is never cut
            assert not re.search(r"hidden_size|intermediate|_dim$|_rank$|head_dim|head_size",
                                 key), key
            assert cfg[key] != cfg["published"][key]


def listed_cells(metric, m):
    """The cells a per-layer metric is reported in: its `workloads`, or
    without the key every cell that reports the metric it moves."""
    if "workloads" in metric:
        return list(metric["workloads"])
    moved = next(e for e in m["end_to_end"] if e["name"] == metric["moves"])
    return moved.get("workloads", [w["name"] for w in m["workloads"]])


def pairs_of(m):
    """Every (per-layer metric, cell it lists) of a manifest."""
    return [(p, cell) for p in m["per_layer"] for cell in listed_cells(p, m)]


def per_layer_metrics_have_a_layer_and_move_what_their_cells_report(
        m, root, bench_dir):
    e2e = {e["name"]: e for e in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    for p in m["per_layer"]:
        assert isinstance(p["moves"], str) and p["moves"] in e2e
        assert p["layer"]
        for cell in listed_cells(p, m):
            assert cell in cells, (p["name"], cell)
            assert cell in e2e[p["moves"]].get("workloads", cells), \
                (p["name"], cell)


def rooflines_stand_beside_an_mfu_that_moves_the_same_metric(
        m, root, bench_dir):
    for p in m["per_layer"]:
        if p["name"].endswith("_roofline"):
            assert p["unit"] == "%"
            beside = [q for q in m["per_layer"]
                      if "mfu" in re.split(r"[._\-]", q["name"])
                      and q["moves"] == p["moves"]
                      and set(listed_cells(p, m)) <= set(listed_cells(q, m))]
            assert beside, p["name"]


def every_cell_lists_an_mfu(m, root, bench_dir):
    for w in m["workloads"]:
        names = {p["name"] for p in m["per_layer"]
                 if w["name"] in listed_cells(p, m)}
        if any(n.endswith("_roofline") for n in names):
            assert any("mfu" in re.split(r"[._\-]", n) for n in names), \
                w["name"]


def _readers(bench_dir):
    """Where a manifest's readers are: beside its cells, or the repo's
    (the tests' tiny manifest brings none of its own)."""
    return bench_dir if os.path.isdir(
        os.path.join(bench_dir, "metrics")) else BENCH


def every_reader_file_is_a_listed_metrics(m, root, bench_dir):
    """No file under `metrics/` that no entry lists: the pair cases walk
    what the manifest lists, so such a file would be checked by nothing."""
    if _readers(bench_dir) != bench_dir:
        return
    files = {f[:-len(".py")]
             for f in os.listdir(os.path.join(bench_dir, "metrics"))
             if f.endswith(".py")}
    assert files <= {p["name"] for p in m["per_layer"]}


def check_pair(metric, cell_name, root, bench_dir, traced,
               traces=HAND_MADE):
    """One (metric, cell it lists): the reader's file is there; handed no
    trace it reads nothing, and an empty one nothing (never 0); on a
    hand-made trace of THAT cell's family of programs (`traces/<family>
    .py`) it reads a value. A metric listed for a cell whose program does
    not name the scope or hand on the count reads None here and fails."""
    readers = _readers(bench_dir)
    name = metric["name"]
    assert os.path.exists(os.path.join(readers, "metrics", name + ".py"))
    read = manifest.load_reader(name, readers)
    cell = manifest.load_cell(cell_name, root=root, bench_dir=bench_dir)
    cell.peaks = dict(TEST_PEAKS)
    assert name in {e["name"] for e in cell.per_layer}
    assert metric["moves"] in {e["name"] for e in cell.end_to_end}
    reads_on_its_cells_own_trace(name, read, cell, traced, traces)


def reads_on_its_cells_own_trace(name, read, cell, traced,
                                 traces=HAND_MADE):
    assert read(None, HostLog(), cell) is None
    nothing = read(trace_reduce.reduce({"planes": []}), HostLog(), cell)
    assert nothing is None or name == "compiles_in_window"
    raw, host = hand_made(cell, traces)
    value = read(traced(raw), host, cell)
    assert value is not None and value >= 0, (name, cell.name)
    if name.endswith("_roofline") or "mfu" in name:
        assert 0 < value


def at_most_a_quarter_of_the_cells_take_four_chips(m, root, bench_dir):
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)


INVARIANTS = [
    the_contracts_keys, names_units_and_lines,
    setup_s_is_reported_everywhere_with_the_bound_0_1,
    every_cell_has_its_files_and_every_config_a_cell,
    config_files_state_their_cut,
    per_layer_metrics_have_a_layer_and_move_what_their_cells_report,
    rooflines_stand_beside_an_mfu_that_moves_the_same_metric,
    every_cell_lists_an_mfu, every_reader_file_is_a_listed_metrics,
    at_most_a_quarter_of_the_cells_take_four_chips]


@pytest.mark.parametrize("invariant", INVARIANTS,
                         ids=[f.__name__ for f in INVARIANTS])
def test_the_manifest_keeps(invariant, loaded):
    invariant(*loaded)


def _every_pair():
    return [pytest.param(root, p, cell, id=f"{p['name']}-{cell}")
            for root in (REPO, DATA)
            for p, cell in pairs_of(manifest.load_manifest(root))]


@pytest.mark.parametrize("root, metric, cell", _every_pair())
def test_a_listed_metric_reads_a_value_on_its_cells_own_trace(
        root, metric, cell, traced):
    check_pair(metric, cell, root, _bench_dir(root), traced)


@pytest.mark.parametrize("name, family, kind", [
    ("decode_sub_ms.latent_attn", "afmoe", "serve_family"),
    ("decode_sub_ms.shared_expert", "mimo_v2", "serve_family"),
    ("decode_sub_ms.window_attn", "pangu_ultra_moe", "serve_family"),
    ("experts_hit_pct", None, "serve"),
    ("cache_attn_decode_roofline", "pangu_ultra_moe", "serve_family"),
    ("train_sub_ms.flash_attn", None, "serve"),
    ("decode_step_ms", None, "train")])
def test_a_metric_of_another_familys_program_reads_nothing(name, family,
                                                           kind, traced):
    """What the check of a pair catches: a scope that a cell's program does
    not name, a count that it does not hand on, a module that it does not
    run. Listed for such a cell the metric would read None on that
    family's trace, and the pair's case would fail."""
    cells = [manifest.load_cell(w["name"])
             for w in manifest.load_manifest(REPO)["workloads"]]
    cell = next(c for c in cells if c.config.get("family") == family
                and c.mix["kind"] == kind)
    cell.peaks = dict(TEST_PEAKS)
    assert name not in {e["name"] for e in cell.per_layer}
    with pytest.raises(AssertionError, match=re.escape(name)):
        reads_on_its_cells_own_trace(name, manifest.load_reader(name), cell,
                                     traced)


NEW_FAMILY = "appended_family"
NEW_FAMILYS_TABLE = """
def prefill_flops(cfg, prompt_len):
    return 2.0 * cfg["hidden_size"] ** 2 * prompt_len


def decode_flops(cfg, context_len):
    return 2.0 * cfg["hidden_size"] ** 2


def decode_bytes(cfg, kv_tokens, rows=None):
    return 2.0 * cfg["hidden_size"] ** 2
"""
NEW_FAMILYS_TRACE = """
from benchmark_suite_helpers import MS, planes, serving
from benchmark_suite_helpers import gpt_host as host  # noqa: F401


def raw():
    return serving(planes(
        [["jit_pure_prefill(11)", 10 * MS, 20 * MS],
         ["jit_pure_burst(13)", 40 * MS, 20 * MS]],
        [["fusion.1", 10 * MS, 20 * MS, "jit(pure_prefill)/mlp/dot_general"],
         ["fusion.2", 40 * MS, 20 * MS, "jit(pure_burst)/attn/dot_general"]],
        [["bench.traced_window", 0, 100 * MS, {}],
         ["serving.decode.sync", 40 * MS, 20 * MS, {}],
         ["serving.emit", 61 * MS, 2 * MS, {}]]))
"""


def _append_a_cell(m, like, config, why):
    """A cell like `like` at the END of `m`'s workloads, under `config`,
    reporting what `like` reports end to end, its `mfu.*` metrics and one
    per-layer entry of its own at the END of `per_layer`."""
    cell = config + ".appended"
    m["workloads"].append(dict(like, name=cell, config=config,
                               traffic="appended", why=why))
    for e in m["end_to_end"]:
        if like["name"] in e.get("workloads", []):
            e["workloads"].append(cell)
    moved = next(e["name"] for e in m["end_to_end"]
                 if cell in e.get("workloads", []))
    m["per_layer"].append({
        "name": "appended_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": moved,
        "workloads": [cell]})
    for p in m["per_layer"]:
        if like["name"] in p["workloads"] and p["name"].startswith("mfu"):
            p["workloads"].append(cell)
    return cell


@pytest.mark.parametrize("family", ["a family the benchmark has",
                                    "a new family"])
def test_an_entry_and_a_cell_appended_at_the_end_break_nothing(
        family, tmp_path, traced, monkeypatch):
    """The door: a copy of the repo's manifest with one per-layer entry and
    one cell appended at the END satisfies every invariant, with NEW files
    alone (a reader, a cell file and a traffic file written beside copies
    of the benchmark's own) and no edit to a file that is there. A test
    that pins a position (these are the LAST two entries of `per_layer`,
    this is the LAST cell of `workloads`) would fail here, as PR 33's did
    on PR 34's entry. The cell of a NEW family brings three files more,
    each found by the family's name: its configuration, its table
    (`benchmark/families/<family>.py`) and its hand-made trace
    (`hand_made/<family>.py`); a closed list of families anywhere in the
    tests would fail it, as it would every `model_config` PR."""
    m = copy.deepcopy(manifest.load_manifest(REPO))
    configs = {c["name"]: c for c in m["configs"]}
    like = next(w for w in m["workloads"] if "family" in manifest._read(
        os.path.join(REPO, configs[w["config"]]["file"])))
    bench_dir, traces = str(tmp_path / "benchmark"), HAND_MADE
    for part in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(os.path.join(BENCH, part),
                        os.path.join(bench_dir, part))
    config = like["config"]
    if family == "a new family":
        config, traces = "appended-config", str(tmp_path / "hand_made")
        entry = dict(configs[like["config"]], name=config,
                     file="benchmark/configs/appended-config.json")
        m["configs"].append(entry)
        cfg = dict(manifest._read(os.path.join(
            REPO, configs[like["config"]]["file"])), family=NEW_FAMILY)
        with open(tmp_path / entry["file"], "w") as f:
            json.dump(cfg, f)
        # the family's table where a PR would put it: a new file in the
        # package `benchmark.families` (here a second directory of it)
        os.makedirs(tmp_path / "families")
        os.makedirs(traces)
        (tmp_path / "families" / (NEW_FAMILY + ".py")).write_text(
            NEW_FAMILYS_TABLE)
        (tmp_path / "hand_made" / (NEW_FAMILY + ".py")).write_text(
            NEW_FAMILYS_TRACE)
        monkeypatch.setattr(families, "__path__", list(families.__path__)
                            + [str(tmp_path / "families")])
    cell = _append_a_cell(m, like, config, "a cell a later PR appends")
    for part, suffix in (("traffic", like["traffic"]),
                         ("cells", like["name"])):
        shutil.copy(os.path.join(bench_dir, part, suffix + ".json"),
                    os.path.join(bench_dir, part, (
                        "appended" if part == "traffic" else cell) + ".json"))
    with open(os.path.join(bench_dir, "metrics", "appended_ms.py"),
              "w") as f:
        f.write("def read(trace, host, cell):\n"
                "    if trace is None or not trace['devices']:\n"
                "        return None\n"
                "    return 1e3 * trace['window_s']\n")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(m, f)
    loaded = manifest.load_manifest(str(tmp_path))
    assert loaded == m
    for invariant in INVARIANTS:
        invariant(loaded, str(tmp_path), bench_dir)
    checked = 0
    for metric, name in pairs_of(loaded):
        if name == cell or metric["name"] == "appended_ms":
            check_pair(metric, name, str(tmp_path), bench_dir, traced,
                       traces)
            checked += 1
    assert checked >= 3
    sys.modules.pop("benchmark.families." + NEW_FAMILY, None)


def test_no_branch_on_a_workloads_name_in_the_harness():
    names = [w["name"] for w in manifest.load_manifest(REPO)["workloads"]] \
        + [c["name"] for c in manifest.load_manifest(REPO)["configs"]]
    bench = os.path.join(REPO, "benchmark")
    for dirpath, _, files in os.walk(bench):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(dirpath, f)).read()
                for n in names:
                    assert f'"{n}"' not in text and f"'{n}'" not in text, \
                        (f, n)
                assert "FLAGS_" not in text.replace("`FLAGS_*`", "") \
                    .replace("no `FLAGS_", ""), f


def test_command_refuses_to_measure_without_a_tpu():
    m = manifest.load_manifest(REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload",
         next(iter(m["workloads"]))["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "metrics" not in out.stdout and "no TPU" in out.stderr
