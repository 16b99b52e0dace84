"""Bench regression gate (tools/bench_compare.py) + the
BENCH_HISTORY.jsonl trajectory ledger bench.py appends."""
import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bc():
    return _load("tools/bench_compare.py", "_t_bench_compare")


def _row(value=1000.0, loss=6.0, backend="cpu", smoke=True,
         compiles=2, peak=1_000_000, **extra_over):
    extra = {"backend": backend, "batch": 4, "seq": 128,
             "loss_last": loss, "compiles": compiles,
             "peak_hbm_bytes": peak}
    extra.update(extra_over)
    row = {"metric": "llama_train_tokens_per_sec_per_chip",
           "value": value, "unit": "tokens/s/chip", "vs_baseline": 0.0,
           "extra": extra, "commit": "abc1234", "date": "2026-08-04"}
    if smoke:
        row["smoke"] = True
    return row


def _files(tmp_path, fresh, baselines=None, history=None):
    fp = tmp_path / "fresh.json"
    fp.write_text(json.dumps(fresh) if fresh is not None else "garbage")
    cp = tmp_path / "cache.json"
    cp.write_text(json.dumps(
        {f"k{i}": b for i, b in enumerate(baselines or [])}))
    hp = tmp_path / "history.jsonl"
    hp.write_text("".join(json.dumps(r) + "\n" for r in history or []))
    return str(fp), str(cp), str(hp)


def _run(bc, tmp_path, fresh, baselines=None, history=None, args=()):
    fp, cp, hp = _files(tmp_path, fresh, baselines, history)
    return bc.main(["--fresh", fp, "--baseline", cp, "--history", hp,
                    *args])


class TestGate:
    def test_within_tolerance_passes(self, bc, tmp_path):
        assert _run(bc, tmp_path, _row(value=950.0),
                    baselines=[_row(value=1000.0)]) == 0

    def test_injected_regression_over_10pct_fails(self, bc, tmp_path):
        # the ISSUE acceptance criterion: a synthetic >10% throughput
        # regression must exit 1 at the default tolerance
        assert _run(bc, tmp_path, _row(value=850.0),
                    baselines=[_row(value=1000.0)]) == 1

    def test_loss_jump_is_a_regression(self, bc, tmp_path):
        assert _run(bc, tmp_path, _row(loss=6.6),
                    baselines=[_row(loss=6.0)]) == 1

    def test_compile_count_storm_is_a_regression(self, bc, tmp_path):
        # +50% and +2 absolute slack: 2 -> 5 is fine, 2 -> 6 regresses
        assert _run(bc, tmp_path, _row(compiles=5),
                    baselines=[_row(compiles=2)]) == 0
        assert _run(bc, tmp_path, _row(compiles=6),
                    baselines=[_row(compiles=2)]) == 1

    def test_tolerance_override(self, bc, tmp_path):
        assert _run(bc, tmp_path, _row(value=700.0),
                    baselines=[_row(value=1000.0)],
                    args=["--tolerance", "0.35"]) == 0

    def test_missing_or_unparseable_is_exit_2(self, bc, tmp_path):
        assert _run(bc, tmp_path, None,
                    baselines=[_row()]) == 2  # garbage fresh
        assert bc.main(["--fresh", str(tmp_path / "nope.json"),
                        "--baseline", str(tmp_path / "cache.json"),
                        "--history", str(tmp_path / "h.jsonl")]) == 2

    def test_no_comparable_row_is_exit_2(self, bc, tmp_path):
        # backend mismatch: a CPU smoke is never judged vs on-chip rows
        assert _run(bc, tmp_path, _row(backend="cpu"),
                    baselines=[_row(backend="tpu")]) == 2
        # smoke-ness mismatch
        assert _run(bc, tmp_path, _row(smoke=True),
                    baselines=[_row(smoke=False)]) == 2
        # geometry mismatch
        assert _run(bc, tmp_path, _row(),
                    baselines=[_row(batch=8)]) == 2
        # tuning-knob mismatch: mfu_sweep variants (scan/remat/fused_ce
        # at the SAME geometry) must never baseline a canonical run
        assert _run(bc, tmp_path, _row(scan_layers=True),
                    baselines=[_row(scan_layers=False)]) == 2
        # rows predating the knob columns stay comparable (key absent
        # on one side is not compared)
        assert _run(bc, tmp_path, _row(scan_layers=True),
                    baselines=[_row()]) == 0

    def test_error_artifact_is_exit_2(self, bc, tmp_path):
        bad = _row()
        bad["error"] = "TimeoutExpired: ..."
        assert _run(bc, tmp_path, bad, baselines=[_row()]) == 2

    def test_most_recent_history_row_wins(self, bc, tmp_path):
        # cache says 2000 (would regress); the newer history row says
        # 1000 — the trajectory is the baseline that counts
        assert _run(bc, tmp_path, _row(value=980.0),
                    baselines=[_row(value=2000.0)],
                    history=[_row(value=1000.0)]) == 0

    def test_newer_dated_cache_row_beats_stale_history(self, bc,
                                                       tmp_path):
        # "most recent comparable wins" is by DATE, not by file order:
        # a cache row re-banked AFTER the history tail (a deliberate
        # perf trade accepted on another machine) must be the baseline,
        # even though cache rows load before history rows
        stale = _row(value=2000.0)
        stale["date"] = "2026-08-01T00:00:00Z"
        rebanked = _row(value=1000.0)
        rebanked["date"] = "2026-08-03T00:00:00Z"
        assert _run(bc, tmp_path, _row(value=980.0),
                    baselines=[rebanked], history=[stale]) == 0

    def test_self_row_in_history_is_skipped(self, bc, tmp_path,
                                            capsys):
        # bench.py banks the fresh run into the history BEFORE the gate
        # runs; the gate must judge against the PREVIOUS run, not the
        # fresh run's own echo (which would always pass)
        fresh = _row(value=800.0)
        rc = _run(bc, tmp_path, fresh,
                  history=[_row(value=1000.0), _row(value=800.0)])
        assert rc == 1  # judged vs 1000, not vs its own 800 echo
        capsys.readouterr()

    def test_self_row_only_is_exit_2_not_vacuous_pass(self, bc,
                                                      tmp_path):
        # first run of a new config: bench.py banked the fresh row
        # before the gate ran, so the run's own echo is the ONLY
        # comparable baseline — the gate must report itself unarmed
        # (exit 2, red in CI), never self-compare to a green 0
        fresh = _row(value=800.0)
        assert _run(bc, tmp_path, fresh,
                    history=[_row(value=800.0)]) == 2

    def test_tolerance_override_only_widens_noisy_metrics(self, bc,
                                                          tmp_path):
        # --tolerance 0.35 loosens the 10% throughput check but must
        # NOT tighten the 50% peak-HBM ceiling: a +40% peak (inside
        # the per-metric table) stays ok (GB-scale rows so the 32 MiB
        # absolute floor is negligible)
        gb = 1_000_000_000
        assert _run(bc, tmp_path, _row(peak=int(1.4 * gb)),
                    baselines=[_row(peak=gb)],
                    args=["--tolerance", "0.35"]) == 0
        # and the per-metric ceiling still fires beyond 50% (+ floor)
        assert _run(bc, tmp_path, _row(peak=int(1.6 * gb)),
                    baselines=[_row(peak=gb)],
                    args=["--tolerance", "0.35"]) == 1
        # the 32 MiB floor absorbs small ABSOLUTE growth on tiny CPU
        # smoke baselines (a few MB peak) where 50% relative is noise
        assert _run(bc, tmp_path, _row(peak=9_000_000),
                    baselines=[_row(peak=5_000_000)]) == 0
        # nor does the noise margin loosen DETERMINISTIC metrics: a
        # +10% loss jump on a seeded run is a correctness smell and
        # must fail even under the CI's 0.35 throughput margin
        assert _run(bc, tmp_path, _row(loss=6.6),
                    baselines=[_row(loss=6.0)],
                    args=["--tolerance", "0.35"]) == 1

    def test_fresh_reads_last_parseable_line(self, bc, tmp_path):
        fp = tmp_path / "fresh.json"
        fp.write_text("log noise\n" + json.dumps(_row(value=990.0))
                      + "\n")
        _, cp, hp = _files(tmp_path, _row(), [_row(value=1000.0)])
        assert bc.main(["--fresh", str(fp), "--baseline", cp,
                        "--history", hp]) == 0


class TestCommittedHistory:
    def test_history_ledger_seeded(self):
        path = os.path.join(REPO, "BENCH_HISTORY.jsonl")
        assert os.path.exists(path), \
            "BENCH_HISTORY.jsonl trajectory not committed"
        rows = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert rows and all("commit" in r and "date" in r
                            for r in rows)


class TestHistoryAppend:
    def test_bench_append_history(self, tmp_path, monkeypatch):
        bench = _load("bench.py", "_t_bench_mod")
        monkeypatch.setattr(bench, "__file__",
                            str(tmp_path / "bench.py"))
        result = _row(value=123.0)
        bench._append_history(result)
        bench._append_history(result)
        path = tmp_path / "BENCH_HISTORY.jsonl"
        rows = [json.loads(ln) for ln in
                open(path).read().splitlines()]
        assert len(rows) == 2
        assert rows[0]["value"] == 123.0
        assert "commit" in rows[0] and "date" in rows[0]


def test_bench_fails_without_a_chip_and_prints_no_metric():
    """bench.py measures on a TPU: on the CPU, without --smoke, it exits
    non-zero and stdout carries no metric line (no CPU fallback, no
    cached row, no exit-0 error row)."""
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no CPU fallback" in r.stderr


def test_bench_smoke_row_has_checks_and_no_rate():
    """--smoke is the CPU correctness run: its row carries checks and
    the device, and nothing under a device metric's name."""
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BENCH_CONFIG": "serving"})
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["smoke"] is True and row["ok"] is True
    assert not {"metric", "value", "unit", "vs_baseline"} & set(row)
    checks = row["checks"]
    assert checks["platform"] == "cpu"
    assert checks["generated_tokens"] == \
        checks["requests"] * checks["new_tokens"]
    assert checks["decode_recompiles"] == 0
