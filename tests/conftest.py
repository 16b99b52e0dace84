"""Test harness config (SURVEY.md §4.3): CPU backend with 8 fake devices so
every parallelism axis is testable without a TPU (the reference's
multi-process single-host trick, collapsed into one process)."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compile cache under test (here and in the worker processes
# tests start): every run compiles what it runs, like the seed suite did,
# and XLA:CPU logs machine-feature errors when it reloads a cached program
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

# this is a CPU suite whatever the environment names: force it (jax honours
# JAX_PLATFORMS=cpu too; the config knob also overrides a stray other value)
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture
def mesh8():
    """An 8-device mesh (dp=2, tp=4) torn down after the test."""
    import paddle_tpu.distributed.mesh as mesh_mod

    m = mesh_mod.init_mesh(dp=2, tp=4)
    yield m
    mesh_mod.set_mesh(None)


def load_repo_script(relpath):
    """Import a script that is not in a package (chip_smoke.py, bench.py,
    tools/*.py) by its path from the repo root — once per process, so test
    modules share one instance. Registered in sys.modules first: a script's
    dataclasses look their module up by name."""
    import importlib.util
    import sys

    name = "_script_" + relpath.replace("/", "_").removesuffix(".py")
    if name not in sys.modules:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(repo, relpath))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def check_padded_prefill(model, prompts, nb, bucket):
    """`model.forward_prefill` of `prompts` padded to `[nb, bucket]` (rows
    beyond them of length 0) against each prompt alone and unpadded: the
    first token's logits and the cache rows at live positions agree, and
    the expert layers' pairs are the unpadded prompts' own (padding makes
    none). Returns the padded call's counts."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.observability import tracing
    from paddle_tpu.tensor import as_array

    ids = np.zeros((nb, bucket), np.int64)
    for row, p in enumerate(prompts):
        ids[row, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts]
                       + [0] * (nb - len(prompts)), jnp.int32)
    with tracing.device_counts() as counts, paddle.no_grad():
        last, caches = model.forward_prefill(
            paddle.to_tensor(ids), model.init_kv_caches(nb, bucket), lens)
    pairs = 0
    for row, p in enumerate(prompts):
        with tracing.device_counts() as alone, paddle.no_grad():
            last1, caches1 = model.forward_prefill(
                paddle.to_tensor(p[None]), model.init_kv_caches(1, len(p)),
                jnp.asarray([len(p)], jnp.int32))
        pairs += int(alone["expert_pairs"])
        np.testing.assert_allclose(np.asarray(last[row]),
                                   np.asarray(last1[0]), atol=2e-5)
        assert int(np.argmax(last[row])) == int(np.argmax(last1[0]))
        for c, c1 in zip(caches, caches1):
            for a, a1 in zip(c, c1):
                np.testing.assert_allclose(
                    np.asarray(as_array(a))[row, :len(p)],
                    np.asarray(as_array(a1))[0], atol=2e-5)
    assert int(counts["expert_pairs"]) == pairs > 0
    return counts
