"""Fleet telemetry (observability/fleet.py, ISSUE 4): rank-sharded
export, cross-rank aggregation, dead-rank detection, and collective
straggler alignment.

The multi-process test spawns REAL processes (multiprocessing spawn,
JAX_PLATFORMS=cpu) so each rank gets its own registry/tracer/flags —
which is why this module does NOT import paddle_tpu at import time: the
spawn children import this module BEFORE their rank env is set, and the
flags registry seeds from env at first import.
"""
import json
import multiprocessing as mp
import os
import time

import pytest

# ---------------------------------------------------------------------------
# spawn worker (module-level for picklability; heavy imports inside)
# ---------------------------------------------------------------------------

_N_STEPS = 6
_STEP_S = 0.25


def _fleet_worker(rank, world, tdir, straggler_rank, dead_rank,
                  dead_after, barrier):
    """One synthetic rank: staggered eager collectives + heartbeats.

    Everyone has the same per-step period; the straggler sleeps BEFORE
    the collective (late in), the others AFTER (on time in) — so enter
    times skew by ~_STEP_S while all ranks finish together. The dead
    rank stops beating after `dead_after` steps but keeps computing, so
    only its heartbeat goes stale."""
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(world)
    os.environ["FLAGS_telemetry_dir"] = tdir
    os.environ["FLAGS_telemetry_flush_s"] = "0.2"
    os.environ["FLAGS_trace_sample"] = "1"
    os.environ["JAX_PLATFORMS"] = "cpu"

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.observability import fleet

    x = paddle.to_tensor(np.ones((512,), np.float32))
    barrier.wait(timeout=180)
    for step in range(_N_STEPS):
        if rank == straggler_rank:
            time.sleep(_STEP_S)
        coll.all_reduce(x)
        if rank != dead_rank or step < dead_after:
            fleet.heartbeat(step)
        if rank != straggler_rank:
            time.sleep(_STEP_S)
    fleet.flush_now()


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture
def fleet_mod():
    from paddle_tpu.observability import fleet

    fleet._reset_for_tests()
    yield fleet
    from paddle_tpu.framework import config

    config.set_flags({"FLAGS_telemetry_dir": ""})
    fleet._reset_for_tests()


@pytest.fixture
def telemetry_dir(fleet_mod, tmp_path):
    from paddle_tpu.framework import config

    config.set_flags({"FLAGS_telemetry_dir": str(tmp_path)})
    yield str(tmp_path)


# ---------------------------------------------------------------------------
# exporter unit tests (single process, injected sources)
# ---------------------------------------------------------------------------


class TestFleetExporter:
    def _sources(self):
        from paddle_tpu import observability as obs

        reg = obs.Registry()
        reg.counter("demo_total", "Demo.").inc(7)
        tracer = obs.Tracer()
        recorder = obs.FlightRecorder()
        recorder.record("demo.event", step=1)
        from paddle_tpu.observability import fleet

        log = fleet.CollectiveLog()
        log.record("all_reduce", 100.0, 0.002, 64.0)
        return reg, tracer, recorder, log

    def test_shard_layout_and_contents(self, fleet_mod, tmp_path):
        reg, tracer, recorder, log = self._sources()
        exp = fleet_mod.FleetExporter(
            str(tmp_path), rank=2, world_size=4, interval=60,
            registry=reg, tracer=tracer, recorder=recorder, log=log)
        exp.flush()
        shard = tmp_path / "rank_2"
        for f in fleet_mod.SHARD_FILES:
            assert (shard / f).exists(), f
        # metrics: the exporter's OWN rank stamped, not the env's
        text = (shard / "metrics.prom").read_text()
        assert 'demo_total{rank="2",world_size="4"} 7' in text
        # events.jsonl: flight-recorder breadcrumbs
        rows = [json.loads(ln) for ln in
                (shard / "events.jsonl").read_text().splitlines()]
        assert rows[0]["kind"] == "demo.event" and rows[0]["step"] == 1
        # collectives.jsonl: the sequence ring
        rows = [json.loads(ln) for ln in
                (shard / "collectives.jsonl").read_text().splitlines()]
        assert rows == [{"op": "all_reduce", "seq": 0, "t": 100.0,
                         "dur": 0.002, "nbytes": 64.0}]
        # trace.json: pid = RANK + process metadata (one lane per rank)
        events = json.loads((shard / "trace.json").read_text())
        assert all(e["pid"] == 2 for e in events)
        assert events[0]["name"] == "process_name"
        assert events[0]["args"]["name"] == "rank 2"
        # heartbeat: no beats yet -> beat_time None, write_time set
        hb = json.loads((shard / "heartbeat.json").read_text())
        assert hb["rank"] == 2 and hb["world_size"] == 4
        assert hb["beat_time"] is None and hb["write_time"] > 0

    def test_background_flusher_and_stop(self, fleet_mod, tmp_path):
        reg, tracer, recorder, log = self._sources()
        exp = fleet_mod.FleetExporter(
            str(tmp_path), rank=0, world_size=1, interval=0.05,
            registry=reg, tracer=tracer, recorder=recorder, log=log)
        exp.start()
        deadline = time.time() + 5.0
        hb_path = tmp_path / "rank_0" / "heartbeat.json"
        while not hb_path.exists() and time.time() < deadline:
            time.sleep(0.02)
        assert hb_path.exists(), "flusher thread never wrote the shard"
        exp.stop()
        flushes = exp.flushes
        time.sleep(0.15)
        assert exp.flushes == flushes, "flusher still running after stop"

    def test_lazy_start_via_collective(self, telemetry_dir, fleet_mod):
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu.distributed import collective as coll

        assert fleet_mod.exporter() is None
        x = paddle.to_tensor(np.ones((16,), np.float32))
        coll.all_reduce(x)
        coll.all_reduce(x)
        assert fleet_mod.exporter() is not None  # auto-started
        tail = fleet_mod.collective_log().tail()
        assert [r[:2] for r in tail[-2:]] == [("all_reduce", 0),
                                              ("all_reduce", 1)]
        assert tail[-1][3] >= 0  # real duration
        # online wait counter materialized in the default registry
        from paddle_tpu import observability as obs

        reg = obs.default_registry()
        assert reg.value("collective_wait_seconds_total",
                         op="all_reduce") >= 0.0
        fleet_mod.flush_now()
        shard = os.path.join(telemetry_dir, "rank_0")
        assert sorted(os.listdir(shard)) == sorted(fleet_mod.SHARD_FILES)

    def test_heartbeat_step_tracking(self, telemetry_dir, fleet_mod):
        fleet_mod.heartbeat(41)
        fleet_mod.heartbeat()  # self-incrementing (serving path)
        fleet_mod.flush_now()
        hb = json.load(open(os.path.join(telemetry_dir, "rank_0",
                                         "heartbeat.json")))
        assert hb["step"] == 42 and hb["beats"] == 2
        assert hb["beat_time"] is not None

    def test_zero_overhead_when_disabled(self, fleet_mod):
        """The acceptance guard: FLAGS_telemetry_dir unset -> zero
        fleet-layer records/allocations per collective call, no exporter
        thread, no wait-counter family (same discipline as the
        FLAGS_trace_sample=0 span guard)."""
        import numpy as np

        import paddle_tpu as paddle
        from paddle_tpu import observability as obs
        from paddle_tpu.distributed import collective as coll

        assert not fleet_mod.enabled()
        x = paddle.to_tensor(np.ones((16,), np.float32))
        coll.all_reduce(x)  # warm the metrics handle caches
        coll.broadcast(x)
        reg = obs.default_registry()
        r0 = fleet_mod.records_created()
        a0 = reg.allocations
        n0 = len(fleet_mod.collective_log())

        def _wait_total():
            fam = reg.get("collective_wait_seconds_total")
            return None if fam is None else sum(
                cell.value for _, cell in fam.samples())

        w0 = _wait_total()  # family may exist from an earlier enabled
        for _ in range(50):  # test in the process registry — value must
            coll.all_reduce(x)  # not move while disabled
            coll.broadcast(x)
        assert fleet_mod.records_created() == r0
        assert len(fleet_mod.collective_log()) == n0
        assert reg.allocations == a0
        assert fleet_mod.exporter() is None
        assert _wait_total() == w0


# ---------------------------------------------------------------------------
# aggregation on synthetic shards (pure functions, no processes)
# ---------------------------------------------------------------------------


def _write_shard(root, rank, world=3, beat_time=None, step=0,
                 colls=(), prom="", trace=(), interval=0.2,
                 write_time=None):
    d = os.path.join(root, f"rank_{rank}")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "heartbeat.json"), "w") as f:
        json.dump({"rank": rank, "world_size": world, "pid": 1,
                   "step": step, "beats": 1 if beat_time else 0,
                   "beat_time": beat_time,
                   "write_time": write_time
                   if write_time is not None
                   else (beat_time or 0) + 0.01,
                   "flushes": 1, "flush_interval_s": interval}, f)
    with open(os.path.join(d, "collectives.jsonl"), "w") as f:
        for c in colls:
            f.write(json.dumps(c) + "\n")
    with open(os.path.join(d, "metrics.prom"), "w") as f:
        f.write(prom)
    with open(os.path.join(d, "trace.json"), "w") as f:
        json.dump(list(trace), f)
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        f.write("")
    return d


class TestAggregation:
    def test_discover_shards(self, fleet_mod, tmp_path):
        _write_shard(tmp_path, 0)
        _write_shard(tmp_path, 2)
        os.makedirs(tmp_path / "rank_bogus")
        (tmp_path / "rank_7").write_text("a file, not a shard")
        assert list(fleet_mod.discover_shards(str(tmp_path))) == [0, 2]

    def test_merge_prometheus_one_header_all_ranks(self, fleet_mod,
                                                   tmp_path):
        p0 = ('# HELP x_total X.\n# TYPE x_total counter\n'
              'x_total{rank="0",world_size="2"} 1\n')
        p1 = ('# HELP x_total X.\n# TYPE x_total counter\n'
              'x_total{rank="1",world_size="2"} 5\n')
        _write_shard(tmp_path, 0, prom=p0)
        _write_shard(tmp_path, 1, prom=p1)
        merged = fleet_mod.merge_prometheus(
            fleet_mod.discover_shards(str(tmp_path)))
        assert merged.count("# HELP x_total") == 1
        assert merged.count("# TYPE x_total") == 1
        assert 'x_total{rank="0",world_size="2"} 1' in merged
        assert 'x_total{rank="1",world_size="2"} 5' in merged

    def test_dead_rank_relative_staleness(self, fleet_mod, tmp_path):
        now = 1000.0
        _write_shard(tmp_path, 0, beat_time=now, step=1900)
        _write_shard(tmp_path, 1, beat_time=now - 42.1, step=1840)
        _write_shard(tmp_path, 2, beat_time=now - 0.3, step=1899)
        shards = fleet_mod.discover_shards(str(tmp_path))
        dead = fleet_mod.dead_ranks(fleet_mod.load_heartbeats(shards),
                                    stale_s=5.0)
        assert [d["rank"] for d in dead] == [1]
        assert dead[0]["step"] == 1840
        assert dead[0]["age_s"] == pytest.approx(42.1, abs=0.01)

    def test_never_beat_rank_not_inverted(self, fleet_mod, tmp_path):
        """A hung rank whose daemon flusher keeps REWRITING
        heartbeat.json (fresh write_time, zero beats) must be the one
        flagged — never its healthy peers. A write_time fallback would
        invert this (code-review finding)."""
        now = 1000.0
        # rank 1 hung before its first step: no beats, but its flusher
        # wrote heartbeat.json 60 s after the healthy ranks' last beat
        _write_shard(tmp_path, 0, beat_time=now - 60.0, step=500)
        _write_shard(tmp_path, 1, beat_time=None, step=-1,
                     write_time=now)
        _write_shard(tmp_path, 2, beat_time=now - 60.5, step=499)
        shards = fleet_mod.discover_shards(str(tmp_path))
        dead = fleet_mod.dead_ranks(fleet_mod.load_heartbeats(shards),
                                    stale_s=5.0)
        assert [d["rank"] for d in dead] == [1]
        assert dead[0]["never_beat"] and dead[0]["age_s"] is None
        text = fleet_mod.format_report(
            fleet_mod.aggregate(str(tmp_path), stale_s=5.0))
        assert "rank 1 never beat" in text

    def test_no_dead_ranks_when_nobody_beats(self, fleet_mod, tmp_path):
        """A job that never touches the heartbeat call sites (pure
        eager collectives) has no liveness baseline: flagging all N
        ranks 'never beat' on a healthy run would be a false alarm."""
        for r in range(3):
            _write_shard(tmp_path, r, beat_time=None, write_time=100.0)
        shards = fleet_mod.discover_shards(str(tmp_path))
        assert fleet_mod.dead_ranks(
            fleet_mod.load_heartbeats(shards), stale_s=1.0) == []

    def test_merge_traces_rebases_to_wall_clock(self, fleet_mod,
                                                tmp_path):
        """Span ts are per-process perf_counter µs; the merger must
        rebase each rank's lane via its heartbeat clock anchor so the
        lanes line up on one wall timeline."""
        ev = {"name": "s", "ph": "X", "ts": 1_000_000.0, "dur": 5.0,
              "tid": 1, "args": {}}
        for r, perf_s in ((0, 1.0), (1, 501.0)):  # epochs 500 s apart
            _write_shard(tmp_path, r, beat_time=2000.0,
                         trace=[{**ev, "pid": r}])
            hb_path = os.path.join(tmp_path, f"rank_{r}",
                                   "heartbeat.json")
            hb = json.load(open(hb_path))
            # both anchors sampled at the same wall instant
            hb["clock"] = {"perf_s": perf_s, "wall_s": 2000.0}
            json.dump(hb, open(hb_path, "w"))
        merged = fleet_mod.merge_traces(
            fleet_mod.discover_shards(str(tmp_path)))
        ts = {e["pid"]: e["ts"] for e in merged}
        # rank 0 booted 500 s earlier -> same perf ts is 500 s earlier
        # in wall terms; after rebasing the lanes differ by exactly that
        assert ts[0] - ts[1] == pytest.approx(500e6, abs=1.0)
        assert ts[0] == pytest.approx((2000.0 - 1.0) * 1e6 + 1e6,
                                      abs=1.0)

    def test_missing_rank_detection(self, fleet_mod, tmp_path):
        _write_shard(tmp_path, 0, world=3, beat_time=1.0)
        _write_shard(tmp_path, 2, world=3, beat_time=1.0)
        shards = fleet_mod.discover_shards(str(tmp_path))
        assert fleet_mod.missing_ranks(
            shards, fleet_mod.load_heartbeats(shards)) == [1]

    def test_straggler_alignment_and_report_text(self, fleet_mod,
                                                 tmp_path):
        base = 5000.0

        def rows(rank_delay):
            return [{"op": "all_reduce", "seq": s,
                     "t": base + s + rank_delay, "dur": 0.001,
                     "nbytes": 64} for s in range(3)] + \
                   [{"op": "all_reduce", "seq": 1842,
                     "t": base + 99 + (0.18 if rank_delay else 0.0),
                     "dur": 0.001, "nbytes": 64}]

        _write_shard(tmp_path, 0, beat_time=base, colls=rows(0.0))
        _write_shard(tmp_path, 1, beat_time=base, colls=rows(0.0))
        _write_shard(tmp_path, 2, beat_time=base,
                     colls=[{**r, "t": r["t"] + (0.18 if r["seq"] == 1842
                                                 else 0.002)}
                            for r in rows(0.0)])
        shards = fleet_mod.discover_shards(str(tmp_path))
        table = fleet_mod.straggler_table(
            fleet_mod.load_collectives(shards))
        top = table[0]
        assert (top["op"], top["seq"], top["last_rank"]) == \
            ("all_reduce", 1842, 2)
        assert top["skew_s"] == pytest.approx(0.18, abs=0.001)
        summary = fleet_mod.straggler_summary(table)
        assert summary[0]["rank"] == 2
        report = fleet_mod.aggregate(str(tmp_path), stale_s=60.0)
        text = fleet_mod.format_report(report)
        assert "rank 2 was last into all_reduce #1842" in text
        assert "straggler summary" in text

    def test_aggregate_artifacts_and_trace_lanes(self, fleet_mod,
                                                 tmp_path):
        for r in range(2):
            _write_shard(
                tmp_path, r, world=2, beat_time=10.0,
                trace=[{"name": "process_name", "ph": "M", "pid": r,
                        "tid": 0, "args": {"name": f"rank {r}"}},
                       {"name": "collective.all_reduce", "ph": "X",
                        "ts": 1.0, "dur": 2.0, "pid": r, "tid": 1,
                        "args": {}}])
        rep = fleet_mod.aggregate(str(tmp_path), stale_s=60.0)
        assert os.path.exists(rep["artifacts"]["prom"])
        events = json.load(open(rep["artifacts"]["trace"]))
        assert sorted({e["pid"] for e in events}) == [0, 1]
        assert rep["artifacts"]["trace_pids"] == [0, 1]
        assert rep["artifacts"]["n_trace_events"] == 2

    def test_aggregate_empty_root(self, fleet_mod, tmp_path):
        rep = fleet_mod.aggregate(str(tmp_path))
        assert rep["shards"] == {} and rep["stragglers"] == []

    def test_trace_report_accepts_shard_dirs(self, fleet_mod, tmp_path):
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import trace_report

        ev = [{"name": "train.step_compute", "ph": "X", "ts": 0.0,
               "dur": 5.0, "pid": 0, "tid": 1,
               "args": {"trace_id": 0}}]
        _write_shard(tmp_path, 0, beat_time=1.0, trace=ev)
        _write_shard(tmp_path, 1, beat_time=1.0,
                     trace=[{**ev[0], "pid": 1}])
        # telemetry root -> both shards merged
        events = trace_report.load_events(str(tmp_path))
        assert sorted(e["pid"] for e in events) == [0, 1]
        # single rank shard dir -> that shard's trace.json
        events = trace_report.load_events(str(tmp_path / "rank_1"))
        assert [e["pid"] for e in events] == [1]


# ---------------------------------------------------------------------------
# watchdog rank identity (satellite)
# ---------------------------------------------------------------------------


class TestWatchdogRankIdentity:
    def test_dump_filename_and_content_carry_rank(self, tmp_path,
                                                  monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
        monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
        from paddle_tpu import observability as obs

        wd = obs.Watchdog(deadline=60.0, dump_dir=str(tmp_path),
                          name="t")
        path = wd.dump()
        base = os.path.basename(path)
        assert f"_r3_{os.getpid()}_" in base
        text = open(path).read()
        assert "rank: 3" in text and "world_size: 4" in text

    def test_dump_filename_no_rank_when_unknown(self, tmp_path,
                                                monkeypatch):
        monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
        monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
        from paddle_tpu import observability as obs

        wd = obs.Watchdog(deadline=60.0, dump_dir=str(tmp_path),
                          name="t")
        base = os.path.basename(wd.dump())
        assert "_r" not in base  # single-process: pid disambiguates
        assert f"_{os.getpid()}_" in base


# ---------------------------------------------------------------------------
# launcher wiring: --telemetry_dir env per Container + aggregation at end
# ---------------------------------------------------------------------------

_LAUNCH_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed import collective as coll
from paddle_tpu.observability import fleet
assert os.environ["FLAGS_telemetry_dir"], "controller must set the env"
rank = int(os.environ["PADDLE_TRAINER_ID"])
x = paddle.to_tensor(np.ones((64,), np.float32))
# the two processes start some tenths of a second apart, either first,
# and these collectives do not wait for each other: meet here, or the
# start-up skew decides who is "last", not rank 1's sleep
open(os.path.join({sync!r}, f"ready_{{rank}}"), "w").close()
deadline = time.time() + 60
while not all(os.path.exists(os.path.join({sync!r}, f"ready_{{r}}"))
              for r in (0, 1)):
    assert time.time() < deadline, "the other rank never arrived"
    time.sleep(0.002)
for step in range(3):
    if rank == 1:
        time.sleep(0.1)
    coll.all_reduce(x)
    fleet.heartbeat(step)
fleet.flush_now()
"""


class TestLauncherWiring:
    def test_controller_sets_env_and_aggregates(self, tmp_path):
        from paddle_tpu.distributed.launch.context import JobContext
        from paddle_tpu.distributed.launch.controller import (
            CollectiveController,
        )

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "worker.py"
        script.write_text(_LAUNCH_WORKER.format(repo=repo,
                                                sync=str(tmp_path)))
        tdir = tmp_path / "telemetry"
        ctx = JobContext(script=str(script), nproc_per_node=2,
                         log_dir=str(tmp_path / "log"),
                         telemetry_dir=str(tdir))
        rc = CollectiveController(ctx).run(poll_interval=0.1)
        assert rc == 0
        # each Container exported its shard; the controller merged them
        from paddle_tpu.observability import fleet

        assert list(fleet.discover_shards(str(tdir))) == [0, 1]
        for artifact in ("fleet.prom", "fleet_trace.json",
                         "fleet_report.txt"):
            assert (tdir / artifact).exists(), artifact
        text = (tdir / "fleet_report.txt").read_text()
        assert "rank 1 was last into all_reduce" in text


# ---------------------------------------------------------------------------
# the real thing: 3 ranks, one delayed, one that stops beating
# ---------------------------------------------------------------------------


class TestMultiProcessFleet:
    def test_three_rank_straggler_and_dead_rank(self, tmp_path):
        """Acceptance scenario: a 3-rank synthetic run with rank 2
        delayed into every collective and rank 1 going silent after 2
        steps. The aggregator must (a) lay out one complete shard per
        rank, (b) name rank 2 the straggler from aligned sequence
        numbers, (c) flag rank 1 dead from its stale heartbeat, (d)
        produce a merged Chrome trace with one pid lane per rank and a
        fleet exposition labeled per rank."""
        world, straggler, dead = 3, 2, 1
        ctx = mp.get_context("spawn")
        barrier = ctx.Barrier(world)
        procs = [
            ctx.Process(target=_fleet_worker,
                        args=(r, world, str(tmp_path), straggler, dead,
                              2, barrier))
            for r in range(world)
        ]
        for p in procs:
            p.start()
        for p in procs:
            p.join(timeout=240)
        codes = [p.exitcode for p in procs]
        assert codes == [0, 0, 0], f"worker exit codes {codes}"

        from paddle_tpu.observability import fleet

        shards = fleet.discover_shards(str(tmp_path))
        assert list(shards) == [0, 1, 2]
        for path in shards.values():
            for f in fleet.SHARD_FILES:
                assert os.path.exists(os.path.join(path, f)), (path, f)

        report = fleet.aggregate(str(tmp_path),
                                 stale_s=2.5 * _STEP_S, top=0)
        # (b) straggler: every aligned seq should name rank 2 last
        rows = report["stragglers"]
        assert rows, "no aligned collective sequences"
        last_ranks = [r["last_rank"] for r in rows]
        assert last_ranks.count(straggler) > len(rows) / 2, rows
        assert rows[0]["last_rank"] == straggler
        assert rows[0]["skew_s"] >= _STEP_S * 0.5
        assert report["straggler_summary"][0]["rank"] == straggler
        # (c) dead rank: stale heartbeat, correct last step
        dead_rows = report["dead"]
        assert [d["rank"] for d in dead_rows] == [dead], (
            dead_rows, report["heartbeats"])
        assert dead_rows[0]["step"] == 1  # froze after step index 1
        # (d) merged artifacts
        assert report["artifacts"]["trace_pids"] == [0, 1, 2]
        events = json.load(open(report["artifacts"]["trace"]))
        assert {e.get("pid") for e in events} == {0, 1, 2}
        assert all(isinstance(e, dict) for e in events)
        prom = open(report["artifacts"]["prom"]).read()
        for r in range(world):
            assert f'collective_calls_total{{op="all_reduce",rank="{r}"'\
                   f',world_size="3"}}' in prom
        # per-rank table has a row per rank with its step
        steps = {r["rank"]: r["step"] for r in report["ranks"]}
        assert steps[0] == _N_STEPS - 1 and steps[2] == _N_STEPS - 1
        assert steps[dead] == 1
        # the formatted report names both findings
        text = fleet.format_report(report)
        assert "DEAD RANK: rank 1 stopped beating at step 1" in text
        assert f"rank {straggler} was last into all_reduce" in text
