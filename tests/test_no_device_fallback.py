"""A run without a chip cannot pass for one with it: the fallbacks that
used to hide the device are gone, and these tests keep them gone.
(bench.py's share is in tests/test_bench_compare.py, chip_smoke.py's in
tests/test_chip_smoke.py.)"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import kernels
from paddle_tpu.framework import compile_cache, device
from paddle_tpu.nn import functional as F

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPlaces:
    def test_asking_for_a_tpu_on_the_cpu_raises(self):
        with pytest.raises(RuntimeError):
            paddle.set_device("tpu")
        with pytest.raises(RuntimeError):
            paddle.set_device("gpu:0")  # legacy names mean the TPU
        with pytest.raises(RuntimeError):
            device.TPUPlace(0).jax_device()
        with pytest.raises(RuntimeError):
            device.device_count("tpu")

    def test_what_is_there_is_reported_by_its_own_name(self):
        assert paddle.get_device() == "cpu:0"
        assert device.current_place().is_cpu_place()
        assert paddle.device_count() == len(jax.devices())
        assert paddle.device.cuda.device_count() == 0
        assert paddle.device.get_available_device()[0] == "cpu:0"
        assert paddle.set_device("cpu") == "cpu"


class TestKernelBoundary:
    def test_interpret_mode_is_for_the_cpu_only(self, monkeypatch):
        assert kernels.interpret() is True
        for other in ("tpu", "gpu", "some-new-plugin"):
            monkeypatch.setattr(jax, "default_backend", lambda o=other: o)
            assert kernels.interpret() is False

    def test_rms_norm_kernel_failure_is_not_swallowed(self, monkeypatch):
        """supports() is the one gate: a shape it accepts and the kernel
        then rejects raises out of F.rms_norm instead of quietly becoming
        the XLA expression."""
        from paddle_tpu.kernels import rms_norm as krms

        def refuse(*a, **k):
            raise RuntimeError("Mosaic refused this kernel")

        x = paddle.to_tensor(np.ones((16, 256), np.float32))
        w = paddle.to_tensor(np.ones((256,), np.float32))
        assert krms.supports(16, 256)
        F.rms_norm(x, w)  # the kernel path itself works
        monkeypatch.setattr(krms, "rms_norm", refuse)
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            F.rms_norm(x, w)

    def test_flash_kernel_failure_is_not_swallowed(self, monkeypatch):
        from paddle_tpu.kernels import flash_attention as fa

        def refuse(*a, **k):
            raise RuntimeError("Mosaic refused this kernel")

        q = paddle.to_tensor(np.ones((1, 128, 1, 128), np.float32))
        assert fa.supports(128, 128, 128)
        # the choice takes the kernel at this small shape
        monkeypatch.setattr(fa, "_min_seq", lambda blocks: 128)
        monkeypatch.setattr(fa, "flash_attention_bshd", refuse)
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                           training=False)


class TestCompileCache:
    """One function, called at package import: JAX_COMPILATION_CACHE_DIR
    when set (the code then sets nothing), else ONE fixed directory in the
    checkout, the same from any working directory."""

    PROBE = ("import sys; sys.path.insert(0, {repo!r}); import jax; "
             "import paddle_tpu; "
             "from paddle_tpu.framework import compile_cache as cc; "
             "print(cc.cache_dir()); print(cc.configure())")

    def _probe(self, cwd, env_dir=None):
        env = {k: v for k, v in os.environ.items()
               if k != compile_cache.ENV_VAR}
        if env_dir is not None:
            env[compile_cache.ENV_VAR] = env_dir
        r = subprocess.run(
            [sys.executable, "-c", self.PROBE.format(repo=REPO)], cwd=cwd,
            env=env, capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    def test_fixed_path_from_another_working_directory(self, tmp_path):
        """A fresh process started somewhere else, variable unset: the
        path is the checkout's, as it is for this process (started from
        the repo root or wherever pytest was)."""
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.DEFAULT_DIR == want
        assert self._probe(str(tmp_path)) == [want, want]

    def test_environment_variable_wins(self, tmp_path):
        """jax itself reads the variable; the package leaves it alone."""
        placed = str(tmp_path / "placed-from-outside")
        assert self._probe(REPO, env_dir=placed) == [placed, placed]

    def test_configure_sets_nothing_when_the_variable_is_set(
            self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        assert compile_cache.configure() == "/somewhere/else"
        assert calls == []

    def test_gitignore_lists_the_default_directory(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestOneProcessPerHost:
    def test_launcher_refuses_several_tpu_workers_on_one_host(self):
        from paddle_tpu.distributed.launch.context import \
            check_one_process_per_host as check

        check(4, 0, {})                          # no chips: CPU workers
        check(1, 4, {})                          # one process, four chips
        check(4, 4, {"JAX_PLATFORMS": "cpu"})    # workers held to the CPU
        with pytest.raises(SystemExit, match="one mesh"):
            check(4, 4, {})
        with pytest.raises(SystemExit, match="one mesh"):
            check(2, 4, {"JAX_PLATFORMS": "tpu,cpu"})

    def test_workers_get_no_cuda_pinning(self):
        from paddle_tpu.distributed.launch.context import (JobContext,
                                                           rank_env)

        env = rank_env(JobContext(script="x.py", nproc_per_node=2), 1)
        assert "CUDA_VISIBLE_DEVICES" not in env or \
            env["CUDA_VISIBLE_DEVICES"] == os.environ.get(
                "CUDA_VISIBLE_DEVICES")

    def test_chip_count_is_read_without_starting_a_backend(self):
        from paddle_tpu.framework import jax_compat

        assert jax_compat.tpu_chips_on_host() >= 0  # 0 in a chipless sandbox
