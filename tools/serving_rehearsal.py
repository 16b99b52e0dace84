"""Serving compile-scale dress rehearsal (BASELINE row 5's v5p story):
AOT-lower + compile the ENGINE's burst-decode program at LLaMA-2-7B
geometry, TP-sharded over a virtual CPU mesh — no step executed. XLA's
per-device memory analysis shows whether the tp8 serving factoring fits
a v5p/v5e chip (weights/tp + kv-head-sharded page pools + temps), and
the compile catches partitioner pathologies in the shard_map decode on
free CPU time instead of chip time.

Run: python tools/serving_rehearsal.py [--devices 8] [--geometry 7b]
Outputs one JSON line + SERVING_REHEARSAL.json.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
try:
    N_DEV = int(sys.argv[sys.argv.index("--devices") + 1]) \
        if "--devices" in sys.argv else 8
except (IndexError, ValueError):
    raise SystemExit("--devices takes an integer")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_DEV}").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")


def main():
    geometry = "7b"
    if "--geometry" in sys.argv:
        try:
            geometry = sys.argv[sys.argv.index("--geometry") + 1]
        except IndexError:
            raise SystemExit("--geometry takes a value: 7b, 13b or smoke")
    if geometry not in ("7b", "13b", "smoke", "router"):
        raise SystemExit(f"unknown --geometry {geometry!r}: 7b, 13b, "
                         "smoke or router (a typo here would bank a "
                         "smoke-sized run under a real-looking key)")

    import paddle_tpu as paddle
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if geometry == "7b":
        cfg = LlamaConfig.llama2_7b()
    elif geometry == "13b":
        cfg = LlamaConfig.llama2_13b()
    else:  # smoke geometry for CI-speed runs; the router geometry
        # reuses it per replica (2 x smoke_tp8 behind the Router)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=8,
                          num_attention_heads=8, num_key_value_heads=8)
    cfg.dtype = "bfloat16"
    cfg.max_position_embeddings = 2048

    from _rehearsal_common import patch_zero_init

    patch_zero_init()

    t0 = time.perf_counter()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    mesh = mesh_mod.set_mesh(mesh_mod.build_mesh(
        devices=np.asarray(jax.devices("cpu")[:N_DEV]), tp=N_DEV))
    burst = 16
    max_batch, max_seq_len = 8, 2048
    engine = ServingEngine(model, max_batch=max_batch,
                           max_seq_len=max_seq_len, page_size=16,
                           decode_burst=burst, mesh=mesh,
                           decode_strategy="greedy_search")
    t_build = time.perf_counter() - t0

    fn = engine._get_burst_fn(True, burst)
    params, buffers = engine._cached_params()
    b = engine.max_batch
    tokens = jnp.zeros((b,), jnp.int64)
    tables = jnp.asarray(engine.block_tables)
    lens = jnp.zeros((b,), jnp.int32)
    act = jnp.ones((b,), bool)
    rem = jnp.full((b,), burst, jnp.int32)
    eos = jnp.full((b,), -1, jnp.int32)
    seed = jax.random.key_data(jax.random.PRNGKey(0))
    greedy = jnp.ones((b,), bool)
    temp = jnp.ones((b,), jnp.float32)
    tk = jnp.zeros((b,), jnp.int32)
    tp_ = jnp.ones((b,), jnp.float32)

    t0 = time.perf_counter()
    lowered = fn.lower(params, buffers, tuple(engine.k_pages),
                       tuple(engine.v_pages), (), (), tokens, tables,
                       lens, act, rem, eos, seed, greedy, temp, tk, tp_)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    from _rehearsal_common import memory_fields

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    kv_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                   for p in engine.k_pages + engine.v_pages)
    result = {
        "geometry": geometry,
        "model": {"hidden": cfg.hidden_size,
                  "layers": cfg.num_hidden_layers,
                  "params_b": round(n_params / 1e9, 3), "dtype": "bf16"},
        "mesh": f"tp{N_DEV} ({N_DEV} virtual CPU devices)",
        "engine": {"max_batch": max_batch, "max_seq_len": max_seq_len,
                   "page_size": 16, "decode_burst": burst,
                   "kv_pool_gb_total": round(kv_bytes / 2**30, 2)},
        "build_s": round(t_build, 1),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device_bytes": memory_fields(compiled),
    }
    pd = result["per_device_bytes"]
    result["per_device_gb"] = round(
        (pd["arguments"] + pd["outputs"] + pd["temps"]) / 2**30, 2)

    if geometry == "router":
        # Router-plane rehearsal: 2 replicas of the smoke_tp8 engine
        # behind the serving Router. Replica 0's compiled program above
        # IS each replica's per-device story (deployed replicas are
        # identical processes); what this branch adds is the fleet
        # aggregate (2x KV pool / per-device bytes) and proof the
        # router constructs over both replicas and enumerates them —
        # no decode step runs, same contract as the other geometries.
        from paddle_tpu.inference import Router
        from paddle_tpu.inference.replica import ReplicaServer
        from paddle_tpu.inference.router import LocalReplica

        t0 = time.perf_counter()
        paddle.seed(1)
        engine2 = ServingEngine(model.__class__(cfg), max_batch=max_batch,
                                max_seq_len=max_seq_len, page_size=16,
                                decode_burst=burst, mesh=mesh,
                                decode_strategy="greedy_search")
        replicas = [
            LocalReplica(ReplicaServer(engine), name="r0"),
            LocalReplica(ReplicaServer(engine2), name="r1"),
        ]
        router = Router(replicas)
        stats = router.stats()
        t_router = time.perf_counter() - t0
        assert [r["name"] for r in stats["replicas"]] == ["r0", "r1"]
        result["router"] = {
            "replicas": 2,
            "policy": stats["policy"],
            "admission": stats["admission"],
            "router_build_s": round(t_router, 1),
            "fleet_kv_pool_gb_total": round(2 * kv_bytes / 2**30, 2),
            "fleet_per_device_gb": round(
                2 * (pd["arguments"] + pd["outputs"] + pd["temps"])
                / 2**30, 2),
        }
    # merge by config key so a smoke run never clobbers the 7b row
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SERVING_REHEARSAL.json")
    runs = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            runs = prev if isinstance(prev, dict) and "geometry" not in prev \
                else {f"{prev['geometry']}_{prev['mesh'].split()[0]}": prev}
        except Exception:
            pass
    runs[f"{geometry}_tp{N_DEV}"] = result
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(runs, f, indent=1)
    os.replace(tmp, path)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
