"""chip_smoke.py off the chip: the device gate, and the phase code at a
tiny size on the CPU's eight host devices — so the script the driver runs
on the TPU cannot rot between chip runs. What only a chip can show (Mosaic
execution, HBM, the sync check) is chip_smoke.py's own business;
tests/test_kernels_compile_tpu.py compiles its kernel table for a v5e."""
import gc
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed.mesh as mesh_mod
from conftest import load_repo_script

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    return load_repo_script("chip_smoke.py")


@pytest.fixture
def compilewatch_on():
    from paddle_tpu.observability import compilewatch

    paddle.set_flags({"FLAGS_compilewatch": True})
    yield
    paddle.set_flags({"FLAGS_compilewatch": False})
    # the compile records are process-wide; later tests read them
    compilewatch._reset_for_tests()


def test_exits_nonzero_and_reports_nothing_without_a_tpu(tmp_path):
    """Under JAX_PLATFORMS=cpu: non-zero, before any model is built, and
    no result line. Run from another directory: nothing depends on cwd."""
    r = subprocess.run([sys.executable, SCRIPT], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "jax found no TPU" in r.stderr
    assert "== serve" not in r.stdout and "ok:" not in r.stdout
    for line in r.stdout.splitlines():
        assert not line.startswith("{"), line


def test_phases_at_tiny_size(cs, compilewatch_on, monkeypatch):
    """serve and train on one device, then tp=4 over four of the CPU's host
    devices: the train run against the one-device losses, all layers, the
    engine on the XLA decode path, and the spread check. The CPU allocator reports nothing, so the
    per-device bytes come from the live arrays' shards here."""
    def live_bytes(jax_):
        # collected first: what an earlier phase of THIS test left as
        # garbage (a one-device model in a reference cycle) would read as
        # bytes resident on the first device, or not, by when the
        # collector last happened to run in this process
        gc.collect()
        held = {d: 0 for d in jax_.local_devices()}
        for a in jax_.live_arrays():
            for shard in a.addressable_shards:
                held[shard.device] += shard.data.nbytes
        return [held[d] for d in jax_.local_devices()]

    # other tests of this process may have left arrays alive, and models
    # (reference cycles) stay until a collection: chip_smoke's `release`
    # collects between phases, so the baseline is taken collected too,
    # or what an earlier file left as garbage reads as bytes given back
    gc.collect()
    # ... and what they left ALIVE is held to the end of this test: an
    # array of the baseline that went away meanwhile (a cache another file
    # filled, dropped when this test replaces the mesh) would read as bytes
    # given back on the devices it lay on, and skew the spread (one whole
    # run in three of PR 32's read device 0 at 62.5 %)
    baseline = jax.live_arrays()
    before = live_bytes(jax)
    monkeypatch.setattr(cs, "device_bytes_in_use", lambda jax_, _=baseline: [
        now - was for now, was in zip(live_bytes(jax_), before)])
    sizes = cs.Sizes.tiny()
    prompts, streams = cs.serve_phase(jax, paddle, sizes)
    assert len(streams) == sizes.n_requests > sizes.serve_batch
    losses = cs.train_phase(jax, paddle, sizes)
    assert len(losses) == sizes.train_steps
    mesh = mesh_mod.init_mesh(tp=4)
    try:
        cs.tp_train_phase(jax, paddle, sizes, mesh, losses)
        cs.tp_serve_phase(jax, paddle, sizes, mesh, prompts, streams)
    finally:
        mesh_mod.set_mesh(None)


@pytest.mark.parametrize("phase,knob", [("serve", "LOGIT_TOL"),
                                        ("train", "FIRST_LOSS_TOL")])
def test_a_failed_check_ends_the_run(cs, compilewatch_on, monkeypatch,
                                     phase, knob):
    """Any single check made to fail is a SystemExit with a message — the
    run ends there, non-zero."""
    monkeypatch.setattr(cs, knob, -1.0)
    run = cs.serve_phase if phase == "serve" else cs.train_phase
    with pytest.raises(SystemExit) as e:
        run(jax, paddle, cs.Sizes.tiny())
    assert "chip_smoke: FAILED" in str(e.value)


def test_kernel_references_agree_with_the_kernels_in_interpret_mode(cs):
    """The chip phase compares each Pallas kernel with a plain XLA
    reference written in chip_smoke.py. Here the same table runs small,
    kernels interpreted on the CPU: a reference that masks, drops, packs
    or scales differently from its kernel fails here, not on the chip."""
    import jax.numpy as jnp
    import numpy as np

    cases = cs.kernel_cases(heads=2, head_dim=128, hidden=256, ffn=512,
                            flash_seq=256, tokens=64, decode_batch=2,
                            pages_per_seq=8, wide=512, expert_hidden=128,
                            expert_width=256, experts_held=8, gqa_seq=512,
                            gqa_kv_heads=1, gqa_group=2)
    assert len(cases) == len(cs.kernel_cases())
    for case in cases:
        args = tuple(jnp.asarray(a) for a in
                     case.make_args(np.random.default_rng(7)))
        worst = cs.reference_error(jax, case, args)
        assert worst <= cs.KERNEL_TOL, (case.name, worst)
