"""Compile-scale dress rehearsal (round-3 verdict item 4; SURVEY.md §6
config 4): AOT-lower + compile the FULL 13B-geometry hybrid train step
(LLaMA-2-13B shapes: hidden 5120, 40 layers) for 1F1B x TP x ZeRO-stage-2
on an 8-device CPU mesh, WITHOUT running a step. Catches SPMD-partitioner
pathologies and per-device HBM blowups on free CPU time instead of scarce
chip time.

Outputs one JSON line + SCALE_REHEARSAL.json with compile wall-times and
XLA's per-device memory analysis; BASELINE.json's rehearsal table is
maintained from those numbers.

Memory strategy on this host (125 GB, no accelerator): params are
ZERO-initialized (np.zeros is lazy; values are irrelevant to lowering) and
the AdamW state is abstract (jax.eval_shape over init_state_pytree with
the trainer's zero-extended specs attached), so only the bf16 weights +
their stacked copy materialize (~2 x 26 GB peak).

Run: python tools/scale_rehearsal.py [--geometry 13b|1b]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# parse the device budget BEFORE jax initializes its backend
try:
    N_DEV = int(sys.argv[sys.argv.index("--devices") + 1]) \
        if "--devices" in sys.argv else 8
except (IndexError, ValueError):
    raise SystemExit("--devices takes an integer: 8, 16 or 32")
MESH_KW = {8: dict(pp=2, dp=2, tp=2),
           16: dict(pp=2, dp=2, tp=4),   # v5p-16-class factoring
           32: dict(pp=4, dp=2, tp=4)}.get(N_DEV)
if MESH_KW is None:
    raise SystemExit("--devices must be 8, 16 or 32")
# append (not overwrite): user flags like --xla_dump_to must survive
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_DEV}").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

jax.config.update("jax_platforms", "cpu")


def main():
    geometry = "13b"
    if "--geometry" in sys.argv:
        geometry = sys.argv[sys.argv.index("--geometry") + 1]
    n_dev, mesh_kw = N_DEV, MESH_KW

    import paddle_tpu as paddle
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel.sharding. \
        sharding_optimizer import zero_axis_for, zero_extend_spec
    from paddle_tpu.distributed.sharding_utils import clean_spec
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, \
        build_train_step

    if geometry == "13b":
        cfg = LlamaConfig.llama2_13b()
        cfg.dtype = "bfloat16"
        # standard practice at 13B scale: per-layer activation remat
        # (jax.checkpoint via use_recompute) — without it the first
        # rehearsal measured 70 GB/device of backward temps at seq 4096
        cfg.use_recompute = "--no-remat" not in sys.argv
        batch, seq, microbatches = 8, 4096, 4
    else:  # quick mode for CI-style smoke
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=12,
                          num_attention_heads=16, num_key_value_heads=16,
                          max_position_embeddings=2048, dtype="bfloat16")
        batch, seq, microbatches = 8, 2048, 4
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seq)

    # values never run: zero-init params (np.zeros = lazy calloc pages)
    from _rehearsal_common import patch_zero_init

    patch_zero_init()

    t_build0 = time.perf_counter()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    mesh = mesh_mod.set_mesh(mesh_mod.build_mesh(
        devices=np.asarray(jax.devices("cpu")[:n_dev]), **mesh_kw))
    step = build_train_step(model, opt, mesh=mesh, sharding_stage=2,
                            num_microbatches=microbatches)
    t_build = time.perf_counter() - t_build0

    holder = step._holder
    params_sds = {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=a.sharding)
                  for n, a in holder["params"].items()}
    buffers_sds = {n: jax.ShapeDtypeStruct(b._data.shape, b._data.dtype,
                                           sharding=b._data.sharding)
                   for n, b in model.named_buffers()}
    layer_bufs_sds = {n: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=a.sharding)
                      for n, a in holder["layer_bufs"].items()}

    # abstract AdamW state with the trainer's ZeRO layout attached
    opt_shapes = jax.eval_shape(opt.init_state_pytree, params_sds)
    zaxis = zero_axis_for(mesh)
    opt_sds = {}
    for pname, state in opt_shapes.items():
        pspec = tuple(clean_spec(step._flat_specs[pname], mesh))
        out = {}
        for k, v in state.items():
            if v.ndim == 0:
                out[k] = jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(mesh, P()))
            else:
                spec = zero_extend_spec(v.shape, pspec, mesh, axis=zaxis)
                out[k] = jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=NamedSharding(mesh, P(*spec)))
        opt_sds[pname] = out

    dspec = clean_spec(("dp", None), mesh)
    x_sds = jax.ShapeDtypeStruct((batch, seq), jnp.int64,
                                 sharding=NamedSharding(mesh, dspec))
    lr_sds = jax.ShapeDtypeStruct((), jnp.float32)
    seed_arr = jax.random.key_data(jax.random.PRNGKey(0))
    seed_sds = jax.ShapeDtypeStruct(seed_arr.shape, seed_arr.dtype)

    t0 = time.perf_counter()
    lowered = step._jitted.lower(params_sds, buffers_sds, layer_bufs_sds,
                                 opt_sds, lr_sds, seed_sds, x_sds, x_sds)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    from _rehearsal_common import memory_fields
    n_params = sum(int(np.prod(a.shape)) for a in holder["params"].values())
    result = {
        "geometry": geometry,
        "remat": bool(cfg.use_recompute),
        "model": {"hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
                  "vocab": cfg.vocab_size, "params_b": round(n_params / 1e9, 3),
                  "dtype": cfg.dtype},
        "mesh": "x".join(f"{k}{v}" for k, v in mesh_kw.items())
                + f" ({n_dev} virtual CPU devices)",
        "schedule": "1f1b", "sharding_stage": 2,
        "batch": batch, "seq": seq, "microbatches": microbatches,
        "build_s": round(t_build, 1),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device_bytes": memory_fields(compiled),
    }
    args_gb = result["per_device_bytes"]["arguments"] / 2**30
    temps_gb = result["per_device_bytes"]["temps"] / 2**30
    result["per_device_gb_total"] = round(args_gb + temps_gb, 2)
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", "SCALE_REHEARSAL.json")
    all_results = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                all_results = json.load(f)
            if "geometry" in all_results:  # pre-merge single-entry format
                old = all_results
                all_results = {old["geometry"] + (
                    "_remat" if old.get("remat") else ""): old}
        except (OSError, json.JSONDecodeError):
            all_results = {}
    key = geometry + ("_remat" if cfg.use_recompute else "") \
        + (f"_{n_dev}dev" if n_dev != 8 else "")
    all_results[key] = result
    with open(path, "w") as f:
        json.dump(all_results, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
