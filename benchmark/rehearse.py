#!/usr/bin/env python3
"""Compile a cell's programs at the real size for a DESCRIBED v5e, without
the chip, and print what each needs of a device's memory.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py --workload <cell>

Run it before chip time is spent (on-chip-measurement guide §2): what the
TPU's compiler refuses here — a program that does not fit 16 GB, a kernel
Mosaic rejects — costs no chip time. It compiles; nothing runs, so it says
nothing about results or times, and a compile that passes is not a chip
run. Not imported by any test at module level: it loads libtpu, which one
process at a time may do.

It reaches into the program further than the benchmark's run does (the
step's pure function, the engine's program getters), because a described
device cannot hold the arrays the public entry points would place on it.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import manifest, program, traffic  # noqa: E402

GB = 1e9


def described(tree, sharding):
    """The tree's shapes and dtypes, placed on the described device."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def report(label, compiled):
    m = compiled.memory_analysis()
    args, temps, out = (m.argument_size_in_bytes, m.temp_size_in_bytes,
                        m.output_size_in_bytes)
    alias = m.alias_size_in_bytes
    print(f"{label}: arguments {args / GB:.2f} GB + temporaries "
          f"{temps / GB:.2f} GB + outputs {out / GB:.2f} GB - aliased "
          f"{alias / GB:.2f} GB = {(args + temps + out - alias) / GB:.2f} GB "
          f"on the device", flush=True)


def rehearse_train(cell, one_chip):
    from paddle_tpu.jit.api import flatten_call
    from paddle_tpu.tensor import Tensor

    system_model = program.build_model(
        cell.config, 0, train=True,
        recompute=cell.config["trainer"]["recompute"])
    step, opt = program.build_trainer(system_model, cell.config["trainer"])
    params = system_model.parameters_pytree()
    state = jax.eval_shape(opt.init_state_pytree, params)
    x, y = traffic.train_batch(cell.mix, np.random.default_rng(0),
                               cell.config["vocab_size"])
    leaves, structure = flatten_call((Tensor(jnp.asarray(x)),
                                      Tensor(jnp.asarray(y))), {})
    pure = getattr(step, "_pure_step", None) or step._raw_step._pure_step
    lowered = jax.jit(pure, static_argnames=("structure",),
                      donate_argnums=(0, 2)).lower(
        described(params, one_chip),
        described(system_model.buffers_pytree(), one_chip),
        described(state, one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        described(jax.random.key_data(jax.random.key(0)), one_chip),
        described(leaves, one_chip), structure=structure)
    report(f"{cell.name}: train step", lowered.compile())


def rehearse_serve(cell, one_chip):
    model = program.build_model(cell.config, 0, train=False)
    engine = program.build_engine(model, cell.config["engine"])
    lo, hi = traffic.length_support(cell.mix["prompt_tokens"])
    rounds = program.prefill_rounds(engine, lo, hi)
    print(f"{cell.name}: {len(rounds)} prefill rounds to warm, "
          f"{len({(nb, b) for (_, nb, b), _ in rounds})} prefill programs")
    params, buffers = engine._cached_params()
    p, b = described(params, one_chip), described(buffers, one_chip)
    (_, nb, bucket), _ = rounds[-1]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = described(jax.random.key_data(jax.random.key(0)), one_chip)
    row = lambda dt: sds((nb,), dt)  # noqa: E731
    prefill = engine._get_prefill_fn(nb, bucket, True)
    prefill = getattr(prefill, "_fn", prefill)
    report(f"{cell.name}: prefill {nb} x {bucket}", prefill.lower(
        p, b, sds((nb, bucket), jnp.int64), row(jnp.int32), key,
        row(jnp.bool_), row(jnp.float32), row(jnp.int32),
        row(jnp.float32)).compile())
    mb, burst = engine.max_batch, engine.decode_burst
    slot = lambda dt: sds((mb,), dt)  # noqa: E731
    pages = tuple(described(list(engine.k_pages), one_chip))
    fn = engine._get_burst_fn(True, burst)
    fn = getattr(fn, "_fn", fn)
    report(f"{cell.name}: burst decode {mb} x {burst}", fn.lower(
        p, b, pages, pages, (), (), slot(jnp.int64),
        sds((mb, engine.pages_per_seq), jnp.int32), slot(jnp.int32),
        slot(jnp.bool_), slot(jnp.int32), slot(jnp.int32), key,
        slot(jnp.bool_), slot(jnp.float32), slot(jnp.int32),
        slot(jnp.float32)).compile())


REHEARSALS = {"train": rehearse_train, "serve": rehearse_serve}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    cell = manifest.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    import paddle_tpu  # noqa: F401
    jax.config.update("jax_enable_compilation_cache", False)
    REHEARSALS[cell.mix["kind"]](cell, one_chip)


if __name__ == "__main__":
    main()
