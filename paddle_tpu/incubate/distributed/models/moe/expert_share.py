"""One chip's share of a wide expert-parallel MoE layer, without drops.

`MoELayer` routes with a capacity (a full expert drops tokens) and holds
every expert. A deployment that spreads 256 experts over 16 chips asks for
something else of each chip: a router that keeps its published width, its
picks and the denominator over ALL picks; the experts that live here
(`ep_rank` of `ep_degree`, a contiguous block); and, for every token, the
part of the layer's result that the picked experts held here give,

    y_here = sum_{e in S(x) & H} w_e(x) E_e(x),

with no token dropped. What the absent experts would add is left out; the
exchange that would bring other chips' tokens here, and send this share
back, is not stood in for. The shares of all `ep_degree` ranks add up to
the whole layer's routed result (tests/test_latent_moe.py holds that).

`SigmoidTopKGate` scores with a sigmoid in float32 and picks the `top_k`
largest (no groups; with `pick_bias` the router's `expert_bias` is added to
the scores for the pick alone); `ExpertShareLayer` owns the gate
and the held experts' stacked gated-SiLU weights, and takes the sum above
in one of three forms, chosen from what the call can see (its token count,
shapes and types, whether a gradient may be recorded, `_interpret()`):

- `share_ffn`, the dense form and the reference: every held expert's
  product for every token, weighted by `w_e` (0 where the token did not
  pick it). The CPU, a call that may record a gradient and a call between
  the two kernels' token counts take it;
- `kernels/expert_hit.py`'s `hit_ffn` at decode-sized token counts (at most
  `expert_hit._HIT_MAX_TOKENS`): a decode step is bound by reading the
  experts' weights, and its few tokens pick few of the held experts, so it
  reads the weights of the experts some live token picked and no other;
- `kernels/expert_grouped.py`'s `grouped_ffn` from
  `expert_grouped._GROUPED_MIN_TOKENS` tokens on (a prefill): with 8 picks
  of 128 or 256 experts a token meets one held expert or none, so it takes
  the products of the (live token, held expert it picked) pairs alone,
  grouped by expert, whatever the skew.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .....autograd import tape as _tape
from .....kernels import expert_grouped as _grouped
from .....kernels import expert_hit as _hit
from .....nn import initializer as I
from .....nn.layer_base import Layer
from .....observability import tracing as _trace
from .....tensor import _apply_op

# tokens of one block of `share_ffn`: bounds the [block, held x width]
# intermediates of a prefill (16,384 tokens x 16 x 2,048 would be 1 GB each)
TOKEN_BLOCK = 2048


def over_token_blocks(fn, block, *arrays):
    """`fn(*arrays)` on arrays whose leading axis counts tokens, `block`
    tokens at a time (`lax.map` over zero-padded blocks) once there are
    more than that: bounds what `fn` holds between its products."""
    n = arrays[0].shape[0]
    if n <= block:
        return fn(*arrays)
    pad = -n % block
    blocks = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            (-1, block) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda args: fn(*args), blocks)
    return out.reshape((-1,) + out.shape[2:])[:n]


def sigmoid_topk(x, w_router, top_k, scale=1.0, norm_topk=True, bias=None):
    """x [n, d], w_router [d, E] -> (picks [n, k] int32, weights [n, k]
    float32): the `top_k` largest of sigmoid(x W_r), scored in float32;
    weights are the scores, over their sum where `norm_topk`, times
    `scale`. `bias` [E] (a router's `expert_bias`) is added for the PICK
    alone: the weights stay the scores of the experts picked."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x, w_router.astype(x.dtype), preferred_element_type=jnp.float32))
    if bias is None:
        top, picks = jax.lax.top_k(scores, top_k)
    else:
        _, picks = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, picks, axis=-1)
    if norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return picks.astype(jnp.int32), top * scale


def held_weights(picks, weights, first, held):
    """[n, held] float32: the routing weight of each expert held here
    (`first` .. `first + held - 1`) for each token, 0 where the token did
    not pick it."""
    local = picks[..., None] - first == jnp.arange(held, dtype=picks.dtype)
    return jnp.sum(jnp.where(local, weights[..., None], 0.0), axis=1)


def share_ffn(x, dense_w, w_gate, w_up, w_down):
    """sum_e dense_w[:, e] * E_e(x) over the held experts: x [n, d],
    dense_w [n, held], w_gate / w_up [held, d, f], w_down [held, f, d].
    The weight goes onto the hidden activations, so the sum over experts
    is the down projection's own float32 accumulation."""
    held, _, f = w_gate.shape

    def block(xb, wb):
        gate = jnp.einsum("nd,edf->nef", xb, w_gate.astype(xb.dtype),
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("nd,edf->nef", xb, w_up.astype(xb.dtype),
                        preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(gate) * up * wb[..., None]).astype(xb.dtype)
        return jnp.matmul(hidden.reshape(-1, held * f),
                          w_down.astype(xb.dtype).reshape(held * f, -1),
                          preferred_element_type=jnp.float32
                          ).astype(xb.dtype)

    return over_token_blocks(block, TOKEN_BLOCK, x, dense_w)


class SigmoidTopKGate(Layer):
    """The router: `weight` [d_model, num_experts] over ALL experts of the
    deployment, and with `pick_bias` an `expert_bias` [num_experts] (zeros
    until loaded) that enters the pick alone. forward(x [n, d]) -> (picks,
    weights), see `sigmoid_topk`."""

    def __init__(self, d_model, num_experts, top_k, routed_scaling_factor=1.0,
                 norm_topk_prob=True, pick_bias=False):
        super().__init__()
        self.num_experts, self.top_k = num_experts, top_k
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.weight = self.create_parameter(
            shape=[d_model, num_experts],
            default_initializer=I.XavierUniform())
        self.expert_bias = self.create_parameter(
            shape=[num_experts], default_initializer=I.Constant(0.0)) \
            if pick_bias else None

    def forward(self, x):
        kw = dict(top_k=self.top_k, scale=self.routed_scaling_factor,
                  norm_topk=self.norm_topk_prob)
        if self.expert_bias is None:
            return _apply_op(sigmoid_topk, x, self.weight,
                             _name="moe_sigmoid_gate", **kw)
        return _apply_op(
            lambda x, w, b: sigmoid_topk(x, w, bias=b, **kw), x, self.weight,
            self.expert_bias, _name="moe_sigmoid_gate")


class ExpertShareLayer(Layer):
    """Routed experts `ep_rank * held .. + held - 1` of `num_experts`
    (`held = num_experts // ep_degree`) and the router over all of them.
    forward(x [..., d_model]) -> this chip's share of the routed result,
    the same shape. `live` ([tokens] bool, optional) says which tokens
    are real: it enters the counts, and on the hit path the list of experts
    whose weights are read. A live token's result is the same with it and
    without; a token that is not live gets the sum over the experts that
    live tokens hit, which nothing reads."""

    def __init__(self, d_model, d_hidden, num_experts, top_k, ep_rank=0,
                 ep_degree=1, routed_scaling_factor=1.0,
                 norm_topk_prob=True, pick_bias=False):
        super().__init__()
        if num_experts % ep_degree or not 0 <= ep_rank < ep_degree:
            raise ValueError(
                f"ep_rank {ep_rank} of ep_degree {ep_degree} does not "
                f"name a share of {num_experts} experts")
        self.d_model = d_model
        self.held = num_experts // ep_degree
        self.first = ep_rank * self.held
        self.gate = SigmoidTopKGate(d_model, num_experts, top_k,
                                    routed_scaling_factor, norm_topk_prob,
                                    pick_bias)
        for name, shape in (("w_gate", [self.held, d_model, d_hidden]),
                            ("w_up", [self.held, d_model, d_hidden]),
                            ("w_down", [self.held, d_hidden, d_model])):
            setattr(self, name, self.create_parameter(
                shape=shape, default_initializer=I.XavierUniform()))

    def forward(self, x, live=None):
        shape = [int(s) for s in x.shape]
        tokens = x.reshape([-1, self.d_model])
        with _trace.scope("router"):
            picks, weights = self.gate(tokens)
            dense_w = _apply_op(held_weights, picks, weights,
                                _name="moe_held_weights", first=self.first,
                                held=self.held)
            ffn = self._form(tokens)
            self._count(dense_w, live, ffn)
        with _trace.scope("experts"):
            kw = {} if ffn is share_ffn else {"live": live}
            y = _apply_op(ffn, tokens, dense_w, self.w_gate, self.w_up,
                          self.w_down, _name="moe_share_ffn", **kw)
        return y.reshape(shape)

    def _form(self, tokens):
        """The one choice: the kernels have no backward, so a call that
        may record a gradient keeps the dense products (serving never
        records); else `hit_ffn` or `grouped_ffn` where its module says the
        call is its own (never both: the token count parts them), else the
        dense reference."""
        call = (int(tokens.shape[0]), self.d_model,
                int(self.w_gate.shape[2]), tokens._data.dtype,
                self.w_gate._data.dtype)
        if _tape.grad_enabled():
            return share_ffn
        if _hit.use_hit_path(*call):
            return _hit.hit_ffn
        if _grouped.use_grouped_path(*call):
            return _grouped.grouped_ffn
        return share_ffn

    def _count(self, dense_w, live, ffn):
        """`expert_pairs`: (live token, held expert it picked) pairs;
        `experts_hit`: held experts some live token picked; `experts_read`:
        held experts whose weights the call streams (those hit on the hit
        and grouped paths, all held on the dense one); `expert_rows`: rows
        of expert products the call takes (tokens x held on the dense
        form, tokens x experts hit on the hit path, the pairs' rows with
        their tiles' padding on the grouped one); and what the first three
        are a share of, `expert_layer_steps` (1 for a call with a live
        token) and `experts_held`. Nothing is computed where nobody
        collects (`tracing.device_counts`)."""
        if not _trace.counting():
            return
        picked = dense_w._data > 0
        if live is not None:
            picked = picked & jnp.reshape(live, (-1, 1))
        any_live = jnp.int32(1) if live is None \
            else jnp.any(live).astype(jnp.int32)
        _trace.count("expert_pairs", jnp.sum(picked, dtype=jnp.int32))
        hit = jnp.sum(jnp.any(picked, axis=0), dtype=jnp.int32)
        _trace.count("experts_hit", hit)
        read = any_live * self.held if ffn is share_ffn else hit
        _trace.count("experts_read", read)
        _trace.count("expert_rows",
                     _grouped.grouped_rows(dense_w._data, live)
                     if ffn is _grouped.grouped_ffn
                     else int(picked.shape[0]) * read)
        _trace.count("expert_layer_steps", any_live)
        _trace.count("experts_held", any_live * self.held)
