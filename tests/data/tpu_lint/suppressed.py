"""Fixture: every hazard suppressed per line — the lint must report
nothing here. Exercises named and bare `disable` spellings."""
import jax

from paddle_tpu.distributed.collective import all_reduce


def guarded(x, rank):
    with jax.experimental.enable_x64(False):  # tpu-lint: disable=jax-compat
        pass
    if rank == 0:
        all_reduce(x)  # tpu-lint: disable=rank-divergent-collective
    return x


def _suppressed_kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:] * 2.0  # tpu-lint: disable
