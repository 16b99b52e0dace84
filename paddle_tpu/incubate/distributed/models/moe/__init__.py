"""Expert-parallel MoE (reference:
python/paddle/incubate/distributed/models/moe — SURVEY.md §2.2 "EP").

`global_scatter`/`global_gather` keep the reference's op names as shard_map
helpers over `lax.all_to_all` with *static equal splits* — the jit-safe
contract (the reference's uneven, count-driven NCCL a2a is replaced by
capacity-padded dense routing; see moe_layer.py docstring).
"""
from __future__ import annotations

import jax

from .gate import BaseGate, GShardGate, NaiveGate, SwitchGate
from .moe_layer import ExpertFFN, MoELayer
from .expert_share import ExpertShareLayer, SigmoidTopKGate
from . import routing

__all__ = [
    "MoELayer", "ExpertFFN", "BaseGate", "NaiveGate", "SwitchGate",
    "GShardGate", "SigmoidTopKGate", "ExpertShareLayer", "routing", "global_scatter", "global_gather",
]


def _exchange(x4, axis_name):
    """[ep, A, C, d] -> a2a over the leading (peer) axis -> transpose so the
    receiver's view is A-major: [A, ep, C, d]."""
    out = jax.lax.all_to_all(x4, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    return out.transpose(1, 0, 2, 3)


def global_scatter(x, capacity: int, axis_name: str = "ep"):
    """Inside shard_map: exchange capacity-padded token blocks so each rank
    holds the tokens destined for its local experts.

    x: [E_global * capacity, d] per rank, *destination-expert-major* (block
    e holds up to `capacity` tokens for global expert e). Returns
    [E_local * ep * capacity, d], *local-expert-major*: expert e's tokens
    from every source rank are contiguous ([e, source, slot] order).

    Maps the reference op paddle/fluid/operators/collective/global_scatter_op
    onto `lax.all_to_all` (SURVEY.md §5 mapping table)."""
    ep = jax.lax.axis_size(axis_name)
    e_g, d = x.shape[0] // capacity, x.shape[1]
    x4 = x.reshape(ep, e_g // ep, capacity, d)  # [dest_rank, E_local, C, d]
    return _exchange(x4, axis_name).reshape(-1, d)


def global_gather(x, capacity: int, axis_name: str = "ep"):
    """Inverse of global_scatter (reference global_gather_op): takes the
    local-expert-major [E_local * ep * capacity, d] buffer back to the
    destination-expert-major [E_global * capacity, d] layout on each
    source rank."""
    ep = jax.lax.axis_size(axis_name)
    n, d = x.shape
    e_l = n // (ep * capacity)
    x4 = x.reshape(e_l, ep, capacity, d).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(x4, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    # out: [source_rank=dest-of-return, E_local-of-peer, C, d] == the
    # original [dest_rank, E_local, C, d] blocks
    return out.reshape(-1, d)
