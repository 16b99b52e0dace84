"""BENCHMARK.json against the contract's rules that a first benchmark is most
often refused for, and the command's refusal to measure off a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark_suite_helpers import DATA, REPO

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=[REPO, DATA],
                ids=["BENCHMARK.json", "tests-data"])
def loaded(request):
    root = request.param
    bench_dir = os.path.join(REPO, "benchmark") if root == REPO else DATA
    return manifest.load_manifest(root), root, bench_dir


def test_manifest_has_exactly_the_contracts_keys(loaded):
    m, _, _ = loaded
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["paths"]) <= 16 and len(m["command"]) <= 32
    assert len(json.dumps(m)) < 64 * 1024


def test_names_units_and_lines(loaded):
    m, _, _ = loaded
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


def test_setup_s_is_reported_everywhere_with_the_bound_0_1(loaded):
    m, _, _ = loaded
    setup = next(e for e in m["end_to_end"] if e["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] == 0.1


def test_every_cell_has_its_files_and_every_config_a_cell(loaded):
    m, root, bench_dir = loaded
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


def test_config_files_state_their_cut(loaded):
    m, root, _ = loaded
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


def test_per_layer_metrics_have_a_reader_a_layer_and_one_moves(loaded):
    m, root, bench_dir = loaded
    e2e = {e["name"]: e for e in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]

    def reporting(metric):
        return metric.get("workloads", cells)

    for p in m["per_layer"]:
        assert callable(manifest.load_reader(
            p["name"], os.path.join(REPO, "benchmark")))
        assert isinstance(p["moves"], str) and p["moves"] in e2e
        assert p["layer"]
        for cell in p.get("workloads", []):
            assert cell in cells
            assert cell in reporting(e2e[p["moves"]]), (p["name"], cell)


def test_rooflines_stand_beside_an_mfu_that_moves_the_same_metric(loaded):
    m, _, _ = loaded
    for p in m["per_layer"]:
        if p["name"].endswith("_roofline"):
            assert p["unit"] == "%"
            beside = [q for q in m["per_layer"]
                      if "mfu" in re.split(r"[._\-]", q["name"])
                      and q["moves"] == p["moves"]
                      and set(p.get("workloads", [])) <=
                      set(q.get("workloads", p.get("workloads", [])))]
            assert beside, p["name"]


def test_at_most_a_quarter_of_the_cells_take_four_chips(loaded):
    m, _, _ = loaded
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)


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
         m["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "metrics" not in out.stdout and "no TPU" in out.stderr
