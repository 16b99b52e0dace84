"""Minimal repro: XLA SPMD partitioner CHECK-failure on a data-dependent
gather over a sharded class axis inside a partial-manual shard_map.

Fatal: spmd_partitioner_util.cc:495
  Check failed: partition_group_list.num_replica_groups()
      * partition_group_list.num_devices_per_group()
      == device_groups.num_devices_per_group()

Trigger conditions (all required — remove any one and it compiles):
  - a shard_map manual over one mesh axis ("pp"),
  - TWO further GSPMD-auto axes live inside the body ("dp" shards the
    batch rows, "tp" shards the class dim),
  - a `take_along_axis` (data-dependent gather) whose gathered axis is
    the tp-sharded class dim.

This is why paddle_tpu's cross-entropy paths use a select-reduce
(`nn/functional/loss.py _pick_class`) instead of a gather: the masked
reduction partitions cleanly (each class shard contributes its local
range and the partitioner inserts the psum).

Run: python tools/xla_gather_spmd_repro.py [gather|select]
  gather -> crashes the process with the CHECK (default)
  select -> same math via select-reduce, compiles and prints the value
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.config.update("jax_platforms", "cpu")

MODE = sys.argv[1] if len(sys.argv) > 1 else "gather"

mesh = Mesh(np.asarray(jax.devices("cpu")).reshape(2, 2, 2),
            ("pp", "dp", "tp"))
N, C = 8, 16
logits = jax.device_put(
    np.random.RandomState(0).randn(N, C).astype(np.float32),
    NamedSharding(mesh, P("dp", "tp")))
labels = jax.device_put(
    np.random.RandomState(1).randint(0, C, (N,)),
    NamedSharding(mesh, P("dp")))


def inner(lg, lb):
    if MODE == "gather":
        picked = jnp.take_along_axis(lg, lb[:, None], axis=-1)[:, 0]
    else:
        cls = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
        picked = jnp.sum(jnp.where(cls == lb[:, None], lg, 0.0), axis=-1)
    return jax.lax.psum(jnp.sum(picked), "pp")


# manual over "pp" only; "dp" and "tp" stay GSPMD-auto inside
fn = jax.shard_map(inner, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   axis_names=frozenset({"pp"}), check_vma=False)
print(MODE, "->", float(jax.jit(fn)(logits, labels)))
