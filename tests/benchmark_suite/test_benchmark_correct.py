"""What decides `correct`, shown to fail ("How correct is decided", steps 2
and 3), at a size a test run can hold.

The CONTROL — the plain reference computed one precision step below the
one the tiny configuration states (bfloat16 below float32), put in the
program's place — must come out as not correct by at least one of the
cell's numbers. And each fault the cells can have, planted UNDER the rest
of a run (everything after the look for a chip), must make `correct` come
out false: a step that returns its state unchanged; half of the batch left
out, the mean taken over the rest; a token altered where it is produced.
(The exchange between chips belongs to no cell yet.)
"""
import numpy as np
import pytest

from benchmark_suite_helpers import tiny_cell, tiny_measure

from benchmark import program, weights
from benchmark.drivers import train
from benchmark.hostlog import HostLog
from benchmark.reference import gpt as reference


def _passes(rows):
    return all(row[2] is None or row[1] <= row[2] for row in rows)


def test_serving_control_in_lower_precision_is_not_correct():
    cell = tiny_cell("tiny-gpt.tiny-open")
    cfg, limit = cell.config, cell.params["limits"]["logit_gap_max"]
    tree = weights.make(cfg, 21, cfg["dtype"])
    rng = np.random.default_rng(21)
    widest = {"bf16": 0.0, "int8": 0.0}
    for _ in range(12):
        ids = rng.integers(0, cfg["vocab_size"], 128)
        ref = np.asarray(reference.logits_at(tree, cfg, ids, np.arange(128)))
        for mode in widest:
            low = np.asarray(reference.logits_at(tree, cfg, ids,
                                                 np.arange(128), mode))
            picked = ref[np.arange(128), low.argmax(-1)]
            widest[mode] = max(widest[mode], float((ref.max(-1)
                                                    - picked).max()))
    # the token a lower precision puts first lies below the reference's
    # best by more than the limit somewhere in 1,536 positions
    assert widest["bf16"] > limit and widest["int8"] > limit, widest


def test_training_control_in_lower_precision_is_not_correct():
    cell = tiny_cell("tiny-gpt.tiny-train")
    cfg, trainer = cell.config, cell.config["trainer"]
    rng = np.random.default_rng(22)
    batches = [(rng.integers(0, cfg["vocab_size"], (2, 32)),
                rng.integers(0, cfg["vocab_size"], (2, 32)))
               for _ in range(3)]
    ref = train.reference_readings(cfg, trainer, 22, batches)
    same = train.compare(ref, ref, cell.params["limits"])
    assert _passes(same) and all(row[1] == 0 for row in same)
    for mode in ("bf16", "int8"):
        control = train.reference_readings(cfg, trainer, 22, batches,
                                           mode=mode)
        rows = train.compare(control, ref, cell.params["limits"])
        assert not _passes(rows), (mode, rows)


def test_planted_faults_read_far_from_the_reference():
    cell = tiny_cell("tiny-gpt.tiny-train")
    cfg, trainer = cell.config, cell.config["trainer"]
    rng = np.random.default_rng(23)
    batches = [(rng.integers(0, cfg["vocab_size"], (2, 32)),
                rng.integers(0, cfg["vocab_size"], (2, 32)))
               for _ in range(3)]
    ref = train.reference_readings(cfg, trainer, 23, batches)
    frozen = dict(row[:2] for row in train.compare(
        train.reference_readings(cfg, trainer, 23, batches, frozen=True),
        ref, {}))
    # a state left unchanged reads 1 by the worst-leaf measure
    assert frozen["param_change_gap_worst_leaf"] == pytest.approx(1.0)
    half = dict(row[:2] for row in train.compare(
        train.reference_readings(cfg, trainer, 23, batches, rows=[0]),
        ref, {}))
    assert half["grad_norm_gap_worst_leaf"] > 0.05


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    want = {"a": 1.0, "b": 10.0, "c": 1e-9}
    got = {"a": 1.1, "b": 10.5, "c": 0.05}
    gap, leaf = train.worst_leaf_gap(got, want)
    # c's own norm is all but zero: measured against the median leaf (1.0)
    assert (round(gap, 6), leaf) == (0.1, "a")


def test_what_moves_by_round_off_alone_is_left_out_of_the_change():
    # leaf "dead" has no gradient at all; vector leaf "fused" has a dead
    # third (a key's bias under softmax) that the program moved by noise
    ref = {"grad_norms": {"w": 2.0, "fused": 1.0, "v": 1.0, "dead": 1e-9},
           "grad_vectors": {"fused": np.array([.7, .7, 1e-12, 1e-12]),
                            "v": np.array([.5, .5, .5, .5])},
           "change_norms": {"w": 4.0, "fused": 1.0, "v": 1.0, "dead": 0.0},
           "change_vectors": {"fused": np.array([.6, .8, 0., 0.]),
                              "v": np.array([.5, .5, .5, .5])}}
    got = {"change_norms": {"w": 4.0, "fused": 1.4, "v": 1.0, "dead": 3.0},
           "change_vectors": {"fused": np.array([.6, .8, .7, .7]),
                              "v": np.array([.5, .5, .5, .5])}}
    mine, theirs = train.moving_change_norms(got, ref)
    assert set(mine) == {"w", "fused", "v"}
    assert mine["fused"] == pytest.approx(1.0) == theirs["fused"]
    assert train.worst_leaf_gap(mine, theirs)[0] == pytest.approx(0.0)


# --- the timed path broken underneath the rest of a run ---------------------


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    real = program.build_trainer

    def broken(model, trainer_cfg):
        step, opt = real(model, trainer_cfg)

        def frozen_step(x, y):
            import jax.numpy as jnp

            before = {n: jnp.array(p._data, copy=True)
                      for n, p in model.named_parameters()}
            loss = step(x, y)
            state = program.optimizer_state(step)
            for n, p in model.named_parameters():
                p._rebind(before[n])
                if "master_weight" in state[n]:
                    state[n]["master_weight"] = before[n].astype(
                        jnp.float32)
            return loss

        frozen_step._opt_state_holder = step._opt_state_holder
        return frozen_step, opt

    monkeypatch.setattr(program, "build_trainer", broken)
    result = tiny_measure("tiny-gpt.tiny-train")
    assert result["correct"] is False
    assert result["checks"]["param_change_gap_worst_leaf"]["value"] \
        == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    real = program.build_trainer

    def broken(model, trainer_cfg):
        step, opt = real(model, trainer_cfg)

        def half_step(x, y):
            n = x.shape[0] // 2
            return step(program.to_tensor(np.asarray(x._data)[:n]),
                        program.to_tensor(np.asarray(y._data)[:n]))

        half_step._opt_state_holder = step._opt_state_holder
        return half_step, opt

    monkeypatch.setattr(program, "build_trainer", broken)
    result = tiny_measure("tiny-gpt.tiny-train")
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["grad_norm_gap_worst_leaf"]["value"] \
        > checks["grad_norm_gap_worst_leaf"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    real = program.build_engine

    def broken(model, engine_cfg):
        engine = real(model, engine_cfg)
        stream, vocab = engine._stream, model.config.vocab_size
        count = [0]

        def altered(rid, token):
            count[0] += 1
            stream(rid, (token + 1) % vocab if count[0] % 5 == 0 else token)

        engine._stream = altered
        return engine

    monkeypatch.setattr(program, "build_engine", broken)
    result = tiny_measure("tiny-gpt.tiny-open")
    assert result["correct"] is False
    gap = result["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_an_unanswered_request_is_not_correct(monkeypatch):
    real = program.build_engine

    def broken(model, engine_cfg):
        engine = real(model, engine_cfg)
        add, warm = engine.add_request, [0]

        def dropping(prompt, max_new_tokens, on_token=None, **kw):
            warm[0] += on_token is not None
            if on_token is not None and warm[0] == 3:
                # swallowed: it gets an id and never an answer
                engine._next_rid += 1
                return engine._next_rid - 1
            return add(prompt, max_new_tokens=max_new_tokens,
                       on_token=on_token, **kw)

        engine.add_request = dropping
        return engine

    monkeypatch.setattr(program, "build_engine", broken)
    cell_mix = tiny_cell("tiny-gpt.tiny-open").mix
    assert cell_mix["drain_seconds"] >= 1
    result = tiny_measure("tiny-gpt.tiny-open")
    assert result["failed"] == 1 and result["correct"] is False
