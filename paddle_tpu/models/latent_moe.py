"""The one decoder stack of the serving families: latent, grouped-query
and Mamba mixers, dense and expert FFNs, one residual stream or four
(openPangu-Ultra-MoE: `model_type` pangu_ultra_moe; Trinity-Mini: afmoe;
MiMo-V2.5: mimo_v2; AI21-Jamba2-3B: jamba; Motif-3-Beta: Motif).

ONE decoder stack over a per-layer list of kinds (a config's
`layer_kinds()`): the mixer (`latent`: multi-head latent attention;
`gqa_window` / `gqa_full`: gated grouped-query attention over the last
`sliding_window` positions with rope, or over every position with no
position encoding at all, as Trinity-Mini publishes it: `model_type`
afmoe; `sink_window` / `sink_full`: ungated grouped-query attention whose
keys are wider than its values, roped on the first dims of a head alone,
with a kv-head count and a rope base of the kind's own, scaled values
and, where the config says so, a learned sink in the softmax, as MiMo-V2.5
publishes it: `model_type` mimo_v2; `mamba`: Mamba-1's selective
state-space mixer, which caches no K/V but a state of fixed size a
sequence, as AI21-Jamba2-3B publishes it beside `sink_full` layers with
every option off; `gdla_window` / `gdla_full`: grouped differential
attention on latent rows, as Motif-3-Beta publishes it, over a window or
every position), the FFN (`dense`; `routed+shared`:
this chip's share of the routed experts plus the shared expert; `routed`:
the share alone; the activation SiLU, or PolyNorm where the config's
`moe_spec()` names one), where the norms stand (`sandwich`: a norm before
AND after each sublayer, the residual added outside both; `pre`) and, as
an optional fourth entry, the residual (`plain`, the default: one stream
and an add; `hyper`: manifold-constrained hyper-connections over n
streams). The stack's classes take any config that gives `layer_kinds()`,
`moe_spec()` and the sizes a kind reads: `LatentMoEConfig`, `AfmoeConfig`,
`MiMoV2Config`, `JambaConfig` and `MotifConfig` here. `gpt.py` and
`llama.py` keep their own stacks (ROADMAP D6).

The equations (`N(x; g) = x / sqrt(mean(x^2) + eps) * g`, no biases but a
Mamba mixer's two):

    block    x' = x + N(Attn(N(x; g_in)); g_post_attn)
             y  = x' + N(FFN(N(x'; g_pre_mlp)); g_post_mlp)
    latent   c_q = N(x W_qa; g_qa); q = c_q W_qb -> heads x (nope + rope)
             [c_kv | k_r] = x W_kva; c = N(c_kv; g_kva)
             rope on q's rope dims and on k_r (ONE rope key for all heads),
             rotate-half pairing (dimension i with i + rope/2)
             [k_nope | v] = c W_kvb -> heads x (nope + v)
             scores_h = (q_nope,h . k_nope,h + q_rope,h . k_r) / sqrt(nope + rope)
             o = concat_h(softmax_h v_h) W_o
    gqa      q = x W_q -> heads x d; k = x W_k, v = x W_v -> kv heads x d
             q = N(q; g_qn), k = N(k; g_kn) over the d of each head
             gqa_window: rope on q and k (all d dims, rotate-half) and
             position i sees j only if 0 <= i - j < sliding_window;
             gqa_full: causal, no position encoding
             query head h reads kv head h // (heads / kv heads)
             o = (concat_h(softmax_h v) * sigmoid(x W_gate)) W_o
    sink     q = x W_q -> heads x d_k; k = x W_k -> kv heads x d_k;
             v = value_scale (x W_v) -> kv heads x d_v  (d_k 192, d_v 128)
             rope (rotate-half) on the first `rope` dims of q and k, with
             the kind's own base; no norm on q or k, no gate
             sink_window: 0 <= i - j < sliding_window, and where the kind
             has a sink b_h: P_hj = exp(s_hj) / (sum_j' exp(s_hj') +
             exp(b_h)), the sink carrying no value; sink_full: causal
             o = concat_h(P_h v) W_o
    experts  s = sigmoid(x W_r) in float32; S = the top-k (of s + b where
             the router has an `expert_bias` b: the pick alone);
             w_e = scale s_e / sum_S s
             y = sum_{e in S & held here} w_e E_e(x) + E_shared(x)
    mamba    [u | z] = x W_in (d_inner E each); a depthwise causal conv of
             d_conv K taps with a bias: u'_t = silu(b_c + sum_k w_c[k] *
             u_{t-K+1+k}), u_{<0} = 0; [d | B | C] = u' W_x, each RMS-normed
             (dt_rank R, d_state N, N); D_t = softplus(d W_dt + b_dt) [E];
             A = -exp(A_log) [E, N];
             h_t = exp(D_t (x) A) h_{t-1} + (D_t u'_t) (x) B_t,  h_{-1} = 0
             y_t = h_t C_t + D_skip u'_t;  o = (y * silu(z)) W_out
    hyper    (mHC, n streams X [n, C] a token; each sublayer F, attention
             and FFN, has its own phi [nC, 2n + n^2], alpha [3], b [2n +
             n^2]): x~ = N(vec X) over n C with no gain; h = x~ phi;
             H_pre = sigmoid(alpha_0 h[:n] + b[:n])             [1, n]
             H_post = 2 sigmoid(alpha_1 h[n:2n] + b[n:2n])      [1, n]
             H_res = Sinkhorn_I(alpha_2 mat(h[2n:]) + b[2n:])   [n, n]
             (exp, then rows and columns normalised in turn, I times,
             float32); X' = clip(H_res X + H_post^T F(H_pre X), +-clamp),
             F holding the sublayer's pre-norm. The embedding's row is
             copied into every stream; the final norm takes their sum
    gdla     latent projections as above with G = heads / kv heads query
             heads a kv head: [k_nope | v] = c W_kvb -> kv heads x (nope +
             v); query head h reads kv head h // G; in each group of G the
             first G - 1 heads are signal and the last noise;
             A_h = softmax(q_h . k_{h//G} / sqrt(nope + rope)) v_{h//G},
             causal (gdla_window: 0 <= i - j < window);
             lambda = sigmoid(x W_lambda) [(G - 1) kv heads];
             o_{g,k} = A_{Gg+k} - lambda_{(G-1)g+k} A_{Gg+G-1}, k < G - 1;
             out = (concat o * sigmoid(x W_gate)) W_o; gdla_full ropes with
             YaRN, gdla_window with plain rope at its own base
    polynorm PolyNorm(u) = s (w_0 N(u^3) + w_1 N(u^2) + w_2 N(u))
             + clip(b, +-c), N over the FFN's width (an expert's 1,280),
             w and b an FFN's (an expert's) own; an FFN is then
             W_down(PolyNorm(x W_gate) * x W_up)
    yarn     DeepSeek-V3's ramp over the rope's frequency pairs: pair i
             keeps theta^(-2i/d) below the correction dim of beta_fast
             rotations over the original length, takes it over `factor`
             above that of beta_slow, and blends linearly between; the
             cos and sin are not rescaled (mscale 1)

What a layer caches is its mixer's to say (`cache_layout()`, gathered a
layer an entry by `kv_cache_layouts()`; the serving engine shapes each
layer's pools from its own entry): a latent layer holds `(c | rope(k_r))`,
`kv_lora_rank + qk_rope_head_dim` numbers a token in ONE pool, never K or
V; a grouped-query layer holds K and V of its own kv heads, a key stored
`_pool_width` wide where it is wider than a value; a Mamba layer holds
two `StatePool`s, the conv's last K - 1 inputs in the served type and the
scan's [N, E] state in float32, whatever the context. Prefill and
`forward` decompress K and V from the latent and attend in blocks (a row
and a group of heads at a time: float32 scores of 16 x 128 x 1024 x 1024
would be 8.6 GB); decode
over pages uses the absorbed form, multi-query attention over the one
latent key head: `q~_h = q_nope,h W_kvb[K,h]^T`, `scores_h = (q~_h . c +
q_rope,h . k_r) / sqrt(nope + rope)`, `o_h = (P_h c) W_kvb[V,h]`. The same
mathematics; tests/test_latent_moe.py holds the two forms to each other.
A `gdla` layer caches the same `(c | rope(k_r))` rows, in pages where it
keeps every position and in a ring of pages a slot where it keeps a window
(`kernels/paged_attention.py`); its decode takes the absorbed form with
`q~_h = q_nope,h W_kvb[K, h // G]^T`, its prefill the grouped-query kernel
over K and V decompressed a kv head at a time.

Config fields carry the source's key names; `n_routed_experts` is the
deployment's count (the router's width) and `ep_rank` / `ep_degree` say
which contiguous block of them lives here.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..incubate.distributed.models.moe.expert_share import (
    ExpertShareLayer, activate, over_token_blocks)
from ..kernels import flash_attention as _fa
from ..kernels import paged_attention as _pa
from ..kernels import selective_scan as _ss
from ..nn import initializer as I
from ..nn.functional.rope import apply_rope
from ..observability import tracing as _trace
from ..observability.tracing import scope
from ..tensor import Tensor, _apply_op, as_array
from .causal_lm import CausalLMBase, StatePool
from .paged_step import paged_attention_step, window_attention_step

F32 = jnp.float32
# float32 scores one block of the decompressed attention may hold
SCORE_BLOCK_BYTES = 128 << 20
# tokens of one block of the dense FFN ([block, intermediate] activations)
TOKEN_BLOCK = 4096


@dataclass
class LatentMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 3
    sandwich_norm: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    # this chip's share of the routed experts: block `ep_rank` of
    # `ep_degree` equal contiguous blocks
    ep_rank: int = 0
    ep_degree: int = 1
    dtype: str = "float32"

    @property
    def cache_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    def layer_kinds(self):
        """[(mixer, ffn, norms)] per layer: what the stack is built from."""
        norms = "sandwich" if self.sandwich_norm else "pre"
        return [("latent",
                 "dense" if i < self.first_k_dense_replace
                 else "routed+shared", norms)
                for i in range(self.num_hidden_layers)]

    def moe_spec(self):
        """What a `routed+shared` FFN is built from."""
        return dict(width=self.moe_intermediate_size,
                    num_experts=self.n_routed_experts,
                    top_k=self.num_experts_per_tok,
                    scale=self.routed_scaling_factor,
                    norm_topk=self.norm_topk_prob, pick_bias=False,
                    shared=self.n_shared_experts)

    @staticmethod
    def tiny(vocab=96, layers=3, ep_rank=0, ep_degree=4):
        """Every width shrunk, the kinds and ratios kept: 1 dense + 2
        expert layers, 16 experts top-4 of which 4 held."""
        return LatentMoEConfig(
            vocab_size=vocab, hidden_size=48, intermediate_size=96,
            moe_intermediate_size=24, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
            first_k_dense_replace=1, max_position_embeddings=128,
            ep_rank=ep_rank, ep_degree=ep_degree)


@dataclass
class AfmoeConfig:
    """Trinity-Mini's config.json keys (`model_type` afmoe). `num_experts`
    is the deployment's count (the router's width); `ep_rank` /
    `ep_degree` say which contiguous block of them lives here."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: tuple = None   # None: full iff (i + 1) % every == 0
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    ep_rank: int = 0
    ep_degree: int = 1
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            every = self.global_attn_every_n_layers
            self.layer_types = tuple(
                "full_attention" if (i + 1) % every == 0
                else "sliding_attention"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {"full_attention", "sliding_attention"}
        if bad or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                "full_attention or sliding_attention, got "
                f"{bad or len(self.layer_types)}")

    @property
    def embed_scale(self):
        return math.sqrt(self.hidden_size) if self.mup_enabled else None

    def layer_kinds(self):
        return [("gqa_full" if t == "full_attention" else "gqa_window",
                 "dense" if i < self.num_dense_layers else "routed+shared",
                 "sandwich") for i, t in enumerate(self.layer_types)]

    def moe_spec(self):
        return dict(width=self.moe_intermediate_size,
                    num_experts=self.num_experts,
                    top_k=self.num_experts_per_tok, scale=self.route_scale,
                    norm_topk=self.route_norm, pick_bias=True,
                    shared=self.num_shared_experts)

    @staticmethod
    def tiny(vocab=96, layers=9, window=16, ep_rank=0, ep_degree=4):
        """Every width shrunk, the kinds and ratios kept: 1 dense + 8
        expert layers over two periods of three window layers and a full
        one, 16 experts top-4 of which 4 held, 4 query heads on 2 kv
        heads."""
        return AfmoeConfig(
            vocab_size=vocab, hidden_size=48, intermediate_size=96,
            moe_intermediate_size=24, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=window, num_dense_layers=1, num_experts=16,
            num_experts_per_tok=4, max_position_embeddings=512,
            ep_rank=ep_rank, ep_degree=ep_degree)


@dataclass
class MiMoV2Config:
    """MiMo-V2.5's config.json keys (`model_type` mimo_v2): the language
    model alone (the published vision and audio towers and MTP layers have
    no key here and are not built). `hybrid_layer_pattern[i]` 1 makes layer
    i a window layer (the `swa_*` sizes, a sink where
    `add_swa_attention_sink_bias`), 0 a full one; `moe_layer_freq[i]` 1
    gives it experts, 0 a dense FFN. `n_routed_experts` is the
    deployment's count (the router's width); `ep_rank` / `ep_degree` say
    which contiguous block of them lives here."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    hybrid_layer_pattern: tuple = None   # None: full iff i == 0 or i % 6 == 5
    moe_layer_freq: tuple = None         # None: dense iff i == 0
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 10000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    add_full_attention_sink_bias: bool = False
    add_swa_attention_sink_bias: bool = True
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = None
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    ep_rank: int = 0
    ep_degree: int = 1
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = tuple(
                0 if i == 0 or i % 6 == 5 else 1 for i in range(n))
        if self.moe_layer_freq is None:
            self.moe_layer_freq = tuple(int(i > 0) for i in range(n))
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(self.moe_layer_freq)
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            given = getattr(self, name)
            if len(given) != n or set(given) - {0, 1}:
                raise ValueError(
                    f"{name} must give {n} layers a 0 or a 1 each, got "
                    f"{given}")
        if self.n_shared_experts:
            raise ValueError(
                "mimo_v2 publishes no shared expert (n_shared_experts "
                f"null), got {self.n_shared_experts}")

    @property
    def rms_norm_eps(self):
        return self.layernorm_epsilon

    def attention(self, window):
        """What a `sink_window` (`window` true) or `sink_full` mixer is
        built from."""
        pre = "swa_" if window else ""
        d_k = getattr(self, pre + "head_dim")
        return dict(
            heads=getattr(self, pre + "num_attention_heads"),
            kv_heads=getattr(self, pre + "num_key_value_heads"),
            key_dim=d_k, value_dim=getattr(self, pre + "v_head_dim"),
            # the dims of a head that carry a position, an even count
            rope_dims=int(d_k * self.partial_rotary_factor) // 2 * 2,
            rope_theta=self.swa_rope_theta if window else self.rope_theta,
            window=self.sliding_window if window else None,
            sink=self.add_swa_attention_sink_bias if window
            else self.add_full_attention_sink_bias,
            value_scale=self.attention_value_scale)

    def layer_kinds(self):
        return [("sink_window" if w else "sink_full",
                 "routed" if e else "dense", "pre")
                for w, e in zip(self.hybrid_layer_pattern,
                                self.moe_layer_freq)]

    def moe_spec(self):
        return dict(width=self.moe_intermediate_size,
                    num_experts=self.n_routed_experts,
                    top_k=self.num_experts_per_tok,
                    scale=1.0 if self.routed_scaling_factor is None
                    else self.routed_scaling_factor,
                    norm_topk=self.norm_topk_prob, pick_bias=True, shared=0)

    @staticmethod
    def tiny(vocab=96, layers=12, window=16, ep_rank=0, ep_degree=4):
        """Every width shrunk, the kinds and ratios kept: 1 dense + 11
        expert layers over two periods of the published pattern (a full
        layer, 4 window layers, a full one, 5 window layers, a full one),
        16 experts top-4 of which 4 held, 4 query heads on 1 kv head in a
        full layer and on 2 in a window layer, keys 24 wide (8 of them
        roped) over values of 16."""
        return MiMoV2Config(
            vocab_size=vocab, hidden_size=48, intermediate_size=96,
            moe_intermediate_size=24, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=1, head_dim=24,
            v_head_dim=16, swa_num_attention_heads=4,
            swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
            sliding_window=window, n_routed_experts=16,
            num_experts_per_tok=4, max_position_embeddings=512,
            ep_rank=ep_rank, ep_degree=ep_degree)


@dataclass
class JambaConfig:
    """AI21-Jamba2-3B's config.json keys (`model_type` jamba): layer i is
    attention where `i % attn_layer_period == attn_layer_offset` and a
    Mamba-1 mixer everywhere else; every layer's FFN is the dense SwiGLU
    (`num_experts` 1: the published modelling code builds a layer of one
    expert as that dense MLP). Attention is grouped-query over every
    position with no position encoding, gate, q/k norm, sink or value
    scale (the Mamba layers carry position); the head is tied."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    sliding_window: int = None
    tie_word_embeddings: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        refused = {"num_experts": (self.num_experts, 1),
                   "num_experts_per_tok": (self.num_experts_per_tok, 1),
                   "sliding_window": (self.sliding_window, None),
                   "mamba_conv_bias": (self.mamba_conv_bias, True),
                   "mamba_proj_bias": (self.mamba_proj_bias, False)}
        for key, (given, built) in refused.items():
            if given != built:
                raise NotImplementedError(
                    f"jamba is built for {key}={built!r} (AI21-Jamba2-3B's "
                    f"value), got {given!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    def attention(self, window):
        """What a `sink_full` mixer is built from: every option off."""
        d = self.head_dim
        return dict(heads=self.num_attention_heads,
                    kv_heads=self.num_key_value_heads, key_dim=d,
                    value_dim=d, rope_dims=0, rope_theta=None, window=None,
                    sink=False, value_scale=1.0)

    def mamba(self):
        """What a `mamba` mixer is built from."""
        return dict(d_inner=self.mamba_expand * self.hidden_size,
                    d_state=self.mamba_d_state, d_conv=self.mamba_d_conv,
                    dt_rank=self.mamba_dt_rank)

    def layer_kinds(self):
        return [("sink_full" if i % self.attn_layer_period
                 == self.attn_layer_offset else "mamba", "dense", "pre")
                for i in range(self.num_hidden_layers)]

    def moe_spec(self):
        return dict(shared=0)

    @staticmethod
    def tiny(vocab=96, layers=8):
        """Every width shrunk, the kinds kept: a period of 4 with attention
        at offset 2 (layers 2 and 6), 2 query heads on 1 kv head of 32,
        d_inner 128 (one lane tile of the scan's kernel), d_state 8,
        dt_rank 6, d_conv 4."""
        return JambaConfig(
            vocab_size=vocab, hidden_size=64, intermediate_size=64,
            num_hidden_layers=layers, num_attention_heads=2,
            num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
            mamba_d_state=8, mamba_dt_rank=6, max_position_embeddings=512)


@dataclass
class MotifConfig:
    """Motif-3-Beta's config.json keys (`model_type` Motif): GDLA, grouped
    differential attention on latent rows (`num_noise_heads` of the
    `num_attention_heads` query heads are noise heads, one a kv head),
    with an elementwise sigmoid gate on its output; a window of
    `sliding_window` on every layer but the last of each
    `sliding_window_period`, which is full; `n_dense_first_layers` dense
    FFNs, then `num_experts` routed experts (the deployment's count, the
    router's width; `ep_rank` / `ep_degree` say which block lives here),
    top-`experts_top_k` by sigmoid with the shared expert beside them;
    PolyNorm the activation of every FFN; `mhc_expansion_rate` residual
    streams mixed by mHC. `first_layer` is the published index of this
    stack's first layer (a pipeline stage's): it places the dense layers
    and the window pattern. Full layers rope with YaRN (`rope_scaling`),
    window layers with plain rope at `swa_rope_theta`."""
    vocab_size: int = 220160
    hidden_size: int = 4096
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 53
    num_attention_heads: int = 80
    num_key_value_heads: int = 16
    num_noise_heads: int = 16
    head_dim: int = 192
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_layer: int = 0
    sliding_window: int = 128
    sliding_window_period: int = 4
    n_dense_first_layers: int = 2
    num_experts: int = 384
    experts_top_k: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.0
    mhc_expansion_rate: int = 4
    mhc_sinkhorn_iters: int = 20
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    polynorm_eps: float = 1e-6
    hidden_clamp: float = 1e6
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    swa_rope_theta: float = 10000.0
    rope_scaling: dict = None   # None: YaRN as published
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    ep_rank: int = 0
    ep_degree: int = 1
    dtype: str = "float32"

    def __post_init__(self):
        if self.rope_scaling is None:
            self.rope_scaling = dict(factor=64.0,
                                     original_max_position_embeddings=4096,
                                     beta_fast=32.0, beta_slow=1.0)
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_noise_heads != self.num_key_value_heads:
            raise NotImplementedError(
                "gdla is built for one noise head a kv head (the last of "
                "its group): got "
                f"{self.num_attention_heads}/{self.num_key_value_heads} "
                f"heads and {self.num_noise_heads} noise heads")

    @property
    def qk_nope_head_dim(self):
        return self.head_dim - self.qk_rope_head_dim

    @property
    def cache_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    def yarn(self):
        """The full layers' frequency ramp (`_rope`'s `yarn`)."""
        r = self.rope_scaling
        return (float(r["factor"]),
                int(r["original_max_position_embeddings"]),
                float(r["beta_fast"]), float(r["beta_slow"]))

    def layer_kinds(self):
        period, first = self.sliding_window_period, self.first_layer
        return [("gdla_full" if (first + i) % period == period - 1
                 else "gdla_window",
                 "dense" if first + i < self.n_dense_first_layers
                 else "routed+shared", "pre", "hyper")
                for i in range(self.num_hidden_layers)]

    def moe_spec(self):
        return dict(width=self.moe_intermediate_size,
                    num_experts=self.num_experts, top_k=self.experts_top_k,
                    scale=self.route_scale, norm_topk=self.route_norm,
                    pick_bias=False, shared=self.num_shared_experts,
                    act=dict(scale=self.polynorm_output_scale,
                             clamp=self.polynorm_bias_clamp,
                             eps=self.polynorm_eps))

    @staticmethod
    def tiny(vocab=96, layers=7, window=8, first_layer=1, ep_rank=0,
             ep_degree=4):
        """Every width shrunk, the kinds and ratios kept: from published
        layer 1 on, a dense window layer, then expert layers window, full,
        window, window, window, full; 10 query heads on 2 kv heads (4
        signal and 1 noise head each), heads of 16 + 8 rope over values of
        16, 16 experts top-4 of which 4 held, 4 streams; YaRN's factor 4
        over 16 original positions, so that its ramp bites at test
        lengths."""
        return MotifConfig(
            vocab_size=vocab, hidden_size=48, intermediate_size=96,
            moe_intermediate_size=24, num_hidden_layers=layers,
            num_attention_heads=10, num_key_value_heads=2,
            num_noise_heads=2, head_dim=24, q_lora_rank=24,
            kv_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
            first_layer=first_layer, sliding_window=window, num_experts=16,
            experts_top_k=4, max_position_embeddings=512,
            rope_scaling=dict(factor=4, original_max_position_embeddings=16,
                              beta_fast=32, beta_slow=1),
            ep_rank=ep_rank, ep_degree=ep_degree)


# ---------------------------------------------------------------------------
# the mathematics, on plain arrays
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * gain.astype(F32)).astype(x.dtype)


def _mm(x, w):
    return jnp.matmul(x, w.astype(x.dtype),
                      preferred_element_type=F32).astype(x.dtype)


def gated_ffn(x, w_gate, w_up, w_down, act=None):
    """W_down(act(x W_gate) * x W_up) on [n, d] tokens, a block of tokens
    at a time beyond TOKEN_BLOCK; `act` None is SiLU, else PolyNorm's (w
    [3], b [1], scale, clamp, eps)."""
    def block(xb):
        gate = jnp.matmul(xb, w_gate.astype(xb.dtype),
                          preferred_element_type=F32)
        up = jnp.matmul(xb, w_up.astype(xb.dtype),
                        preferred_element_type=F32)
        return _mm((activate(gate, act) * up).astype(xb.dtype), w_down)

    return over_token_blocks(block, TOKEN_BLOCK, x)


def yarn_inv_freq(inv_freq, d, theta, yarn):
    """DeepSeek-V3's YaRN ramp over `inv_freq` [d / 2]: `yarn` = (factor,
    original length, beta_fast, beta_slow)."""
    factor, original, fast, slow = yarn

    def dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim(fast)), 0)
    hi = min(math.ceil(dim(slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)


def _rope(x, positions, theta, yarn=None):
    """x [b, s, heads, d] rotated at `positions` [b, s], rotate-half; with
    `yarn` (factor, original length, beta_fast, beta_slow) the frequencies
    take YaRN's ramp."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if yarn is not None:
        inv_freq = yarn_inv_freq(inv_freq, d, theta, yarn)
    freqs = positions.astype(F32)[..., None] * inv_freq
    return apply_rope(x.astype(F32), jnp.cos(freqs), jnp.sin(freqs),
                      neox=True).astype(x.dtype)


def latent_projections(x, p, cfg, positions, rotate=None):
    """x [b, s, hidden] (normed) -> (q_nope [b, s, h, nope], q_rope [b, s,
    h, rope], latent [b, s, rank + rope]): the queries, and the row the
    cache keeps for each token, `(c | rope(k_r))`; `rotate(t)` ropes [b,
    s, heads, rope] at `positions` (plain rope at `cfg.rope_theta` where
    None)."""
    if rotate is None:
        def rotate(t):
            return _rope(t, positions, cfg.rope_theta)
    b, s, _ = x.shape
    h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    c_q = rms_norm(_mm(x, p["q_a_proj"]), p["q_a_layernorm"],
                   cfg.rms_norm_eps)
    q = _mm(c_q, p["q_b_proj"]).reshape(
        b, s, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    kv = _mm(x, p["kv_a_proj_with_mqa"])
    c = rms_norm(kv[..., :rank], p["kv_a_layernorm"], cfg.rms_norm_eps)
    k_r = rotate(kv[..., None, rank:])[:, :, 0]
    q_rope = rotate(q_rope)
    return q_nope, q_rope, jnp.concatenate([c, k_r], axis=-1)


def _split_kv_b(w_kv_b, cfg, heads=None):
    """W_kvb [rank, h x (nope + v)] -> (W_K [rank, h, nope], W_V [rank, h,
    v]), h `heads` (the query heads where None)."""
    w = w_kv_b.reshape(cfg.kv_lora_rank, heads or cfg.num_attention_heads,
                       cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def decompressed_attention(q_nope, q_rope, latent, w_kv_b, cfg, offset=0):
    """Causal attention with K and V decompressed from the latent rows.

    q_* [b, s, h, .] are the queries of positions offset .. offset + s - 1,
    latent [b, t, rank + rope] the rows of positions 0 .. t - 1; a query
    sees the keys at or before its own position. Returns [b, s, h x v]. One
    batch row and one group of heads at a time, so the float32 scores of a
    block stay under SCORE_BLOCK_BYTES."""
    b, s, h, _ = q_nope.shape
    t, rank = latent.shape[1], cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    w_k, w_v = _split_kv_b(w_kv_b.astype(latent.dtype), cfg)
    group = h
    while group > 1 and group * s * t * 4 > SCORE_BLOCK_BYTES \
            and group % 2 == 0:
        group //= 2
    visible = (jnp.arange(t)[None, :] <= offset + jnp.arange(s)[:, None])

    def heads(args):
        qn, qr, wk, wv, c, k_r = args   # qn [s, g, nope], wk [rank, g, nope]
        k_nope = jnp.einsum("tr,rgn->tgn", c, wk,
                            preferred_element_type=F32).astype(c.dtype)
        v = jnp.einsum("tr,rgv->tgv", c, wv,
                       preferred_element_type=F32).astype(c.dtype)
        scores = (jnp.einsum("sgn,tgn->gst", qn, k_nope,
                             preferred_element_type=F32)
                  + jnp.einsum("sgr,tr->gst", qr, k_r,
                               preferred_element_type=F32)) * scale
        probs = jax.nn.softmax(
            jnp.where(visible[None], scores, _pa.NEG_INF), axis=-1)
        return jnp.einsum("gst,tgv->sgv", probs.astype(v.dtype), v,
                          preferred_element_type=F32).astype(qn.dtype)

    def row(args):
        qn, qr, lat = args
        c, k_r = lat[:, :rank], lat[:, rank:]
        if group == h:
            return heads((qn, qr, w_k, w_v, c, k_r)).reshape(s, -1)
        n = h // group
        split = lambda a, axis: jnp.moveaxis(  # noqa: E731
            a.reshape(a.shape[:axis] + (n, group) + a.shape[axis + 1:]),
            axis, 0)
        out = jax.lax.map(
            lambda g: heads(g + (c, k_r)),
            (split(qn, 1), split(qr, 1), split(w_k, 1), split(w_v, 1)))
        return jnp.moveaxis(out, 0, 1).reshape(s, -1)   # [n, s, g, v]

    if b == 1:
        return row((q_nope[0], q_rope[0], latent[0]))[None]
    return jax.lax.map(row, (q_nope, q_rope, latent))


def absorbed_queries(q_nope, q_rope, w_kv_b, cfg):
    """[b, h, rank + rope]: `q~_h = q_nope,h W_K,h^T` beside the rope
    query, the query of multi-query attention over latent rows."""
    w_k, _ = _split_kv_b(w_kv_b.astype(q_nope.dtype), cfg)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope, w_k,
                       preferred_element_type=F32).astype(q_nope.dtype)
    return jnp.concatenate([q_abs, q_rope], axis=-1)


def absorbed_values(o_latent, w_kv_b, cfg):
    """[b, h x v]: `o_h = (P_h c) W_V,h` from the attention's [b, h,
    rank]."""
    _, w_v = _split_kv_b(w_kv_b.astype(o_latent.dtype), cfg)
    return jnp.einsum("bhr,rhv->bhv", o_latent, w_v,
                      preferred_element_type=F32
                      ).astype(o_latent.dtype).reshape(o_latent.shape[0], -1)


def gqa_projections(x, p, cfg, positions, rope):
    """x [b, s, hidden] (normed) -> (q [b, s, h, d], k, v [b, s, kv, d],
    gate [b, s, h x d]): per-head norms on q and k, then rope at
    `positions` [b, s] where the layer has positions at all."""
    b, s, _ = x.shape
    d = cfg.head_dim
    q = _mm(x, p["q_proj"]).reshape(b, s, cfg.num_attention_heads, d)
    k = _mm(x, p["k_proj"]).reshape(b, s, cfg.num_key_value_heads, d)
    v = _mm(x, p["v_proj"]).reshape(b, s, cfg.num_key_value_heads, d)
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    if rope:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    gate = jax.nn.sigmoid(jnp.matmul(
        x, p["gate_proj"].astype(x.dtype), preferred_element_type=F32))
    return q, k, v, gate


def _pool_width(width):
    """How wide a cache row of `width` numbers is STORED: itself where it
    fills whole lane tiles (or less than one), else the next multiple of
    128, zeros behind it. A pool of 192-wide keys at pages of 256 is laid
    out by XLA:TPU with the page's positions minor, and copied whole to
    the width-minor layout the decode kernel reads, every program that
    touches it (AOT for a v5e, PR 33: 285 MB of temporaries beside a 214 MB
    pool); in that layout a row of 192 occupies 256 lanes anyway."""
    return width if width <= 128 or width % 128 == 0 \
        else -(-width // 128) * 128


def _widen(x, width):
    """x [..., d] with zeros behind it to `width` (scores do not move)."""
    d = x.shape[-1]
    return x if d == width else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, width - d)])


def sink_projections(x, p, a, positions):
    """x [b, s, hidden] (normed) -> (q [b, s, h, d_k], k [b, s, kv, d_k],
    v [b, s, kv, d_v]) of a `sink_*` mixer built from `a`
    (`MiMoV2Config.attention`, `JambaConfig.attention`): the values scaled
    where the kind scales them, the first `rope_dims` of each head of q and
    k rotated at `positions` [b, s], the rest of the head (all of it where
    `rope_dims` is 0) carrying no position."""
    b, s, _ = x.shape
    q = _mm(x, p["q_proj"]).reshape(b, s, a["heads"], a["key_dim"])
    k = _mm(x, p["k_proj"]).reshape(b, s, a["kv_heads"], a["key_dim"])
    v = _mm(x, p["v_proj"]).reshape(b, s, a["kv_heads"], a["value_dim"])
    if a["value_scale"] != 1.0:
        v = (v.astype(F32) * a["value_scale"]).astype(v.dtype)
    r = a["rope_dims"]
    if not r:
        return q, k, v

    def rotate(t):
        return jnp.concatenate(
            [_rope(t[..., :r], positions, a["rope_theta"]), t[..., r:]], -1)

    return rotate(q), rotate(k), v


def gqa_attention(q, k, v, window=None, offset=0, sink=None):
    """Causal grouped-query attention in plain XLA: q [b, s, h, d] are the
    queries of positions offset .. offset + s - 1, k [b, t, kv, d] and v
    [b, t, kv, d_v] the rows of positions 0 .. t - 1; with `window`, a
    query at i sees j only if i - j < window; with `sink` [h], one more
    column of the softmax, a logit a head that carries no value. One batch
    row and one block of queries at a time, so the float32 scores of a
    block stay under SCORE_BLOCK_BYTES. Returns [b, s, h x d_v]."""
    b, s, h, d = q.shape
    t, kv, d_v = k.shape[1], k.shape[2], v.shape[3]
    scale = 1.0 / math.sqrt(d)
    bq = s
    while bq > 8 and h * bq * t * 4 > SCORE_BLOCK_BYTES and bq % 2 == 0:
        bq //= 2
    n = s // bq
    column = None if sink is None else jnp.broadcast_to(
        sink.astype(F32).reshape(kv, h // kv, 1, 1), (kv, h // kv, bq, 1))

    def block(qb, kk, vv, q0):   # qb [bq, kv, g, d]; kk [t, kv, d]
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, kk,
                            preferred_element_type=F32) * scale
        qpos = (offset + q0 + jnp.arange(bq))[:, None]
        kpos = jnp.arange(t)[None, :]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (qpos - kpos < window)
        scores = jnp.where(seen, scores, _pa.NEG_INF)
        if column is None:
            probs = jax.nn.softmax(scores, axis=-1)
        else:
            probs = jax.nn.softmax(
                jnp.concatenate([scores, column], -1), axis=-1)[..., :t]
        return jnp.einsum("kgqt,tkd->qkgd", probs.astype(vv.dtype), vv,
                          preferred_element_type=F32
                          ).astype(qb.dtype).reshape(bq, h * d_v)

    qg = q.reshape(b, n, bq, kv, h // kv, d)
    if b * n == 1:
        return block(qg[0, 0], k[0], v[0], 0)[None]
    items = (qg.reshape((b * n,) + qg.shape[2:]),
             jnp.repeat(jnp.arange(b), n), jnp.tile(jnp.arange(n) * bq, b))
    out = jax.lax.map(lambda a: block(a[0], k[a[1]], v[a[1]], a[2]), items)
    return out.reshape(b, s, h * d_v)


def diff_combine(ctx, lam, kv_heads, value_dim):
    """The differential combination: ctx [..., h x v] of `kv_heads` groups
    of G heads (G - 1 signal, the last noise), lam [..., kv_heads x (G -
    1)] -> [..., kv_heads x (G - 1) x v] float32, each signal head less
    its lambda times its group's noise head."""
    lead = ctx.shape[:-1]
    a = ctx.astype(F32).reshape(lead + (kv_heads, -1, value_dim))
    g = a.shape[-2]
    lam = lam.astype(F32).reshape(lead + (kv_heads, g - 1, 1))
    return (a[..., :g - 1, :] - lam * a[..., g - 1:, :]).reshape(
        lead + (-1,))


def sinkhorn(logits, iters):
    """[..., n, n] float32 logits -> a doubly stochastic [..., n, n]: exp,
    then rows and columns normalised in turn, `iters` times each."""
    m = jnp.exp(logits - jnp.max(logits, axis=(-2, -1), keepdims=True))
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def hyper_in(streams, phi, alpha, bias, iters, eps):
    """mHC's read of a sublayer's input: streams [..., n, C] -> (x [..., C]
    in the streams' type, H_res [..., n, n], H_post [..., n], both
    float32)."""
    n, c = streams.shape[-2:]
    flat = streams.reshape(streams.shape[:-2] + (n * c,)).astype(F32)
    flat = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1,
                                         keepdims=True) + eps)
    h = jnp.matmul(flat.astype(streams.dtype), phi.astype(streams.dtype),
                   preferred_element_type=F32)
    a, b = alpha.astype(F32), bias.astype(F32)
    pre = jax.nn.sigmoid(a[0] * h[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[..., n:2 * n] + b[n:2 * n])
    res = sinkhorn((a[2] * h[..., 2 * n:] + b[2 * n:]).reshape(
        h.shape[:-1] + (n, n)), iters)
    x = jnp.einsum("...n,...nc->...c", pre, streams.astype(F32))
    return x.astype(streams.dtype), res, post


def hyper_out(streams, res, post, y, clamp):
    """mHC's write: clip(H_res X + H_post^T y, +-clamp), y [..., C] the
    sublayer's output, in the streams' type."""
    out = jnp.einsum("...nm,...mc->...nc", res, streams.astype(F32)) \
        + post[..., :, None] * y.astype(F32)[..., None, :]
    return jnp.clip(out, -clamp, clamp).astype(streams.dtype)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


class _Weight(nn.Layer):
    """One `weight` leaf under a sublayer's name, [in, out] for a matrix
    (no bias anywhere in this family), [n] of ones for a norm's gain."""

    def __init__(self, *shape):
        super().__init__()
        self.weight = self.create_parameter(
            shape=list(shape),
            default_initializer=I.Constant(1.0) if len(shape) == 1
            else I.Normal(0.0, 0.02))


def _true_lens(live):
    """[b] int32 real tokens a row from `live` ([b, s] bool, a prefill's
    real tokens first), or None where every position is real."""
    return None if live is None else jnp.sum(as_array(live), axis=-1,
                                             dtype=jnp.int32)


class _Mixer(nn.Layer):
    """A mixer's leaves are `_Weight`s (or bare parameters) named by
    LEAVES. Each `forward_cached` is handed `live` ([b, s] bool, a
    prefill's real tokens first, or None): attention, causal, need not read
    it, though the prefill kernel skips the padding by it; a mixer that
    carries a state from token to token must."""
    LEAVES = ()

    def _run(self, fn, *inputs, name):
        """`fn(p, *arrays)` as one op, `p` the layer's leaves by name."""
        leaves = [leaf.weight if isinstance(leaf, _Weight) else leaf
                  for leaf in (getattr(self, n) for n in self.LEAVES)]

        def f(*arrays):
            p = dict(zip(self.LEAVES, arrays[:len(leaves)]))
            return fn(p, *arrays[len(leaves):])

        return _apply_op(f, *leaves, *inputs, _name=name)


class LatentAttention(_Mixer):
    LEAVES = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
              "kv_a_layernorm", "kv_b_proj", "o_proj")
    window = None   # every position is kept

    def cache_layout(self):
        """One pool: one head of `(c | rope(k_r))` rows."""
        return ((1, self.config.cache_width),)

    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        c = self.config = config
        h = c.num_attention_heads
        self.q_a_proj = _Weight(c.hidden_size, c.q_lora_rank)
        self.q_a_layernorm = _Weight(c.q_lora_rank)
        self.q_b_proj = _Weight(
            c.q_lora_rank, h * (c.qk_nope_head_dim + c.qk_rope_head_dim))
        self.kv_a_proj_with_mqa = _Weight(c.hidden_size, c.cache_width)
        self.kv_a_layernorm = _Weight(c.kv_lora_rank)
        self.kv_b_proj = _Weight(
            c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = _Weight(h * c.v_head_dim, c.hidden_size)

    def forward_cached(self, x, cache, cur_len, live=None):
        """x [b, s, hidden] at positions cur_len .. cur_len + s - 1; cache
        `(latent rows [b, t, 1, width],)`. Decompressed attention over the
        whole cache. Returns (out, new cache)."""
        cfg = self.config
        (rows,) = cache
        rows = as_array(rows)
        start = as_array(cur_len) if hasattr(cur_len, "_data") else cur_len

        def f(p, x, rows):
            b, s, _ = x.shape
            positions = jnp.broadcast_to(start + jnp.arange(s), (b, s))
            q_nope, q_rope, latent = latent_projections(x, p, cfg, positions)
            with scope("kv_write"):
                zero = jnp.zeros((), jnp.int32)
                rows = jax.lax.dynamic_update_slice(
                    rows, latent[:, :, None].astype(rows.dtype),
                    (zero, jnp.asarray(start, jnp.int32), zero, zero))
            with scope("latent"):
                ctx = decompressed_attention(
                    q_nope, q_rope, rows[:, :, 0].astype(x.dtype),
                    p["kv_b_proj"], cfg, offset=start)
            return _mm(ctx, p["o_proj"]), rows

        out, rows = self._run(f, x, rows, name="latent_attention")
        return out, (as_array(rows),)

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        empty = jnp.zeros((b, s, 1, self.config.cache_width),
                          as_array(x).dtype)
        return self.forward_cached(x, (empty,), 0)[0]

    def forward_paged(self, x, cache, block_tables, context_lens,
                      active=None):
        """One new token a row (x [b, 1, hidden]) over latent pages `(pool
        [1, n_pages, page, width],)`: the row is written at context_lens[b],
        then absorbed-form attention over the row's pages."""
        cfg = self.config
        (pool,) = cache
        tables, lens = as_array(block_tables), as_array(context_lens)
        act = None if active is None else as_array(active)
        scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

        def f(p, x, pool):
            q_nope, q_rope, latent = latent_projections(
                x, p, cfg, lens[:, None])
            with scope("kv_write"):
                pool = _pa.update_paged_pool(pool, latent, tables, lens,
                                             active=act)
            q = absorbed_queries(q_nope[:, 0], q_rope[:, 0],
                                 p["kv_b_proj"], cfg)
            with scope("latent"):
                o_latent = _pa.paged_latent_attention_xla(
                    q, pool, tables, lens + 1, cfg.kv_lora_rank, scale)
            ctx = absorbed_values(o_latent, p["kv_b_proj"], cfg)
            return _mm(ctx, p["o_proj"])[:, None], pool

        out, pool = self._run(f, x, as_array(pool), name="latent_attention")
        return out, (as_array(pool),)


class DiffLatentAttention(LatentAttention):
    """GDLA, grouped differential attention on latent rows, as Motif-3-Beta
    publishes it (the module docstring's `gdla` equations): the latent
    projections of `LatentAttention` with `num_key_value_heads` kv heads
    decompressed from a row, a group of query heads a kv head whose last
    is a noise head that each signal head subtracts, scaled by a lambda a
    token and signal head, and a sigmoid gate on the combined output. A
    window layer (`gdla_window`) keeps its rows in a ring of pages a slot,
    ropes at `swa_rope_theta` and runs its attention under `attn/window`;
    a full one (`gdla_full`) keeps every row in pages, ropes with YaRN and
    runs under `attn/latent`."""
    LEAVES = LatentAttention.LEAVES + ("lambda_proj", "gate_proj")

    def __init__(self, config, window=None):
        _Mixer.__init__(self)
        c = self.config = config
        h, kvh = c.num_attention_heads, c.num_key_value_heads
        signal = h - c.num_noise_heads
        self.window, self.kv_heads = window, kvh
        self.q_a_proj = _Weight(c.hidden_size, c.q_lora_rank)
        self.q_a_layernorm = _Weight(c.q_lora_rank)
        self.q_b_proj = _Weight(c.q_lora_rank, h * c.head_dim)
        self.kv_a_proj_with_mqa = _Weight(c.hidden_size, c.cache_width)
        self.kv_a_layernorm = _Weight(c.kv_lora_rank)
        self.kv_b_proj = _Weight(
            c.kv_lora_rank, kvh * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = _Weight(signal * c.v_head_dim, c.hidden_size)
        self.lambda_proj = _Weight(c.hidden_size, signal)
        self.gate_proj = _Weight(c.hidden_size, signal * c.v_head_dim)

    @property
    def scope_name(self):
        return "latent" if self.window is None else "window"

    def _rotate(self, positions):
        c = self.config
        if self.window is None:
            return lambda t: _rope(t, positions, c.rope_theta, c.yarn())
        return lambda t: _rope(t, positions, c.swa_rope_theta)

    def _finish(self, p, x, ctx):
        """out = (diff(ctx) * sigmoid(x W_gate)) W_o, x the normed input."""
        def sig(w):
            return jax.nn.sigmoid(jnp.matmul(
                x, w.astype(x.dtype), preferred_element_type=F32))

        o = diff_combine(ctx, sig(p["lambda_proj"]), self.kv_heads,
                         self.config.v_head_dim)
        return _mm((o * sig(p["gate_proj"])).astype(x.dtype), p["o_proj"])

    def forward_cached(self, x, cache, cur_len, live=None):
        """x [b, s, hidden] at positions cur_len .. cur_len + s - 1; cache
        `(latent rows [b, t, 1, width],)` of EVERY position. K and V are
        decompressed a kv head at a time; a prefill from position 0 that
        fills the cache takes the grouped-query kernel where the length
        asks (a window layer's key blocks outside the band are neither
        read nor multiplied). Returns (out, new cache)."""
        cfg = self.config
        (rows,) = cache
        rows = as_array(rows)
        traced = hasattr(cur_len, "_data")
        start = as_array(cur_len) if traced else cur_len
        whole = not traced and isinstance(start, int) and start == 0 \
            and int(x.shape[1]) == rows.shape[1]
        nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        lens = _true_lens(live)

        def f(p, x, rows):
            b, s, _ = x.shape
            positions = jnp.broadcast_to(start + jnp.arange(s), (b, s))
            q_nope, q_rope, latent = latent_projections(
                x, p, cfg, positions, self._rotate(positions))
            with scope("kv_write"):
                zero = jnp.zeros((), jnp.int32)
                rows = jax.lax.dynamic_update_slice(
                    rows, latent[:, :, None].astype(rows.dtype),
                    (zero, jnp.asarray(start, jnp.int32), zero, zero))
            lat = latent if whole else rows[:, :, 0].astype(x.dtype)
            t = lat.shape[1]
            kv = _mm(lat[..., :rank], p["kv_b_proj"]).reshape(
                b, t, self.kv_heads, -1)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                lat[:, :, None, rank:], (b, t, self.kv_heads,
                                         cfg.qk_rope_head_dim))], -1)
            v = kv[..., nope:]
            q = jnp.concatenate([q_nope, q_rope], -1)
            with scope(self.scope_name):
                if whole and _fa.use_gqa_flash(s, cfg.head_dim,
                                               cfg.v_head_dim):
                    ctx = _fa.flash_attention_gqa_bshd(
                        q, k, v, window=self.window, lens=lens
                    ).reshape(b, s, -1)
                else:
                    ctx = gqa_attention(q, k, v, self.window, offset=start)
            return self._finish(p, x, ctx), rows

        out, rows = self._run(f, x, rows, name="gdla_attention")
        return out, (as_array(rows),)

    def forward_paged(self, x, cache, block_tables, context_lens,
                      active=None, mesh=None):
        """One new token a row (x [b, 1, hidden]) over `(pool,)` of latent
        rows: the allocator's pages through the block tables in a full
        layer, the rows' rings in a window layer (`[1, b x ring, page,
        width]`, row b's ring its own). The row is written at
        context_lens[b], then absorbed-form attention over what the row
        sees, query head h against kv head h // G."""
        cfg = self.config
        (pool,) = cache
        pool = as_array(pool)
        lens = as_array(context_lens)
        b, page = lens.shape[0], pool.shape[2]
        act = jnp.broadcast_to(jnp.asarray(
            True if active is None else as_array(active), bool), (b,))
        rank, kvh = cfg.kv_lora_rank, self.kv_heads
        g = cfg.num_attention_heads // kvh
        scale = 1.0 / math.sqrt(cfg.head_dim)
        if self.window is None:
            tables = as_array(block_tables)
            write_tables, write_at = tables, lens
            tables, read, first = tables, lens + 1, None
        else:
            rows = jnp.arange(b, dtype=jnp.int32)
            ring = pool.shape[1] // b
            write_tables = _pa.ring_tables(rows, ring)
            write_at = lens % (ring * page)
            seen = jnp.where(act, lens + 1, 0).astype(jnp.int32)
            tables, read, first = _pa.ring_view(rows, ring, page, seen,
                                                self.window)
            if _trace.counting():
                # the dense gather reads every page of a live row's ring
                _trace.count("attn_window_pages_live", _pa.ring_pages_live(
                    seen, self.window, page))
                _trace.count("attn_window_pages_read",
                             jnp.sum(act, dtype=jnp.int32) * ring)
                _trace.count("attn_window_pages_context", jnp.sum(
                    (seen + page - 1) // page, dtype=jnp.int32))

        def f(p, x, pool):
            positions = lens[:, None]
            q_nope, q_rope, latent = latent_projections(
                x, p, cfg, positions, self._rotate(positions))
            with scope("kv_write"):
                pool = _pa.update_paged_pool(pool, latent, write_tables,
                                             write_at, active=act)
            w_k, w_v = _split_kv_b(p["kv_b_proj"].astype(x.dtype), cfg, kvh)
            q_abs = jnp.einsum(
                "bkgn,rkn->bkgr", q_nope[:, 0].reshape(b, kvh, g, -1), w_k,
                preferred_element_type=F32).astype(x.dtype)
            q = jnp.concatenate([q_abs.reshape(b, kvh * g, rank),
                                 q_rope[:, 0]], axis=-1)
            with scope(self.scope_name):
                o_latent = _pa.paged_latent_attention_xla(
                    q, pool, tables, read, rank, scale, first=first)
            ctx = jnp.einsum(
                "bkgr,rkv->bkgv", o_latent.reshape(b, kvh, g, rank), w_v,
                preferred_element_type=F32).astype(x.dtype)
            return self._finish(p, x, ctx.reshape(b, 1, -1)), pool

        out, pool = self._run(f, x, pool, name="gdla_attention")
        return out, (as_array(pool),)


class _GQAttention(_Mixer):
    """What the grouped-query mixers share: the dense cache of `generate`
    and of a prefill, the decode step over pages or rings, and the choice
    of the prefill's attention. A kind gives `_project(p, x, positions) ->
    (q, k, v, *rest)`, `_finish(p, ctx, *rest)`, and sets `window`
    (positions a query sees, itself included; None: every one),
    `kv_heads`, `key_dim`, `value_dim`, `OP` (its ops' name). The cache is
    (k, v) rows of every kv head, a key `_pool_width(key_dim)` wide: every
    position's for a full layer, a ring of the last window's pages for a
    window layer (`kernels/paged_attention.py`)."""
    SINK = None   # the leaf that holds a sink, where the kind has one

    @property
    def scope_name(self):
        return "full" if self.window is None else "window"

    def cache_layout(self):
        """((heads, width), (heads, width)): this layer's K and V pools."""
        return ((self.kv_heads, _pool_width(self.key_dim)),
                (self.kv_heads, self.value_dim))

    def forward_cached(self, x, cache, cur_len, live=None):
        """x [b, s, hidden] at positions cur_len .. cur_len + s - 1; cache
        (k rows [b, t, kv, key width], v rows [b, t, kv, d_v]) of EVERY
        position (the dense cache of `generate` and of a prefill, whatever
        the layer keeps in pages). Returns (out, new cache)."""
        window, d_k, d_v = self.window, self.key_dim, self.value_dim
        k_rows, v_rows = (as_array(c) for c in cache)
        traced = hasattr(cur_len, "_data")
        start = as_array(cur_len) if traced else cur_len
        # a prefill from position 0 that fills the cache attends over what
        # it has just computed, through the kernel where the length asks
        whole = not traced and isinstance(start, int) and start == 0 \
            and int(x.shape[1]) == k_rows.shape[1]
        lens = _true_lens(live)

        def f(p, x, k_rows, v_rows):
            b, s, _ = x.shape
            positions = jnp.broadcast_to(start + jnp.arange(s), (b, s))
            q, k, v, *rest = self._project(p, x, positions)
            sink = {} if self.SINK is None else {"sink": p[self.SINK]}
            with scope("kv_write"):
                zero = jnp.zeros((), jnp.int32)
                at = (zero, jnp.asarray(start, jnp.int32), zero, zero)
                k_rows = jax.lax.dynamic_update_slice(
                    k_rows, _widen(k, k_rows.shape[-1]).astype(k_rows.dtype),
                    at)
                v_rows = jax.lax.dynamic_update_slice(
                    v_rows, v.astype(v_rows.dtype), at)
            with scope(self.scope_name):
                if whole and _fa.use_gqa_flash(s, d_k, d_v):
                    ctx = _fa.flash_attention_gqa_bshd(
                        q, k, v, window=window, lens=lens, **sink
                    ).reshape(b, s, -1)
                elif whole:
                    ctx = gqa_attention(q, k, v, window, **sink)
                else:
                    seen = k_rows if k_rows.shape[-1] == d_k \
                        else k_rows[..., :d_k]
                    ctx = gqa_attention(q, seen.astype(q.dtype),
                                        v_rows.astype(q.dtype), window,
                                        offset=start, **sink)
            return self._finish(p, ctx, *rest), k_rows, v_rows

        out, k_rows, v_rows = self._run(f, x, k_rows, v_rows,
                                        name=self.OP + "_attention")
        return out, (as_array(k_rows), as_array(v_rows))

    def forward(self, x):
        dtype = as_array(x).dtype
        empty = [jnp.zeros((x.shape[0], x.shape[1], heads, width), dtype)
                 for heads, width in self.cache_layout()]
        return self.forward_cached(x, tuple(empty), 0)[0]

    def forward_paged(self, x, cache, block_tables, context_lens,
                      active=None, mesh=None):
        """One new token a row over (k pages, v pages): a full layer's
        pages are the rows' block tables' (`paged_attention_step`), a
        window layer's its rings (`window_attention_step`), which take no
        table."""
        window = self.window
        lens = as_array(context_lens)
        width = _pool_width(self.key_dim)
        # a key stored wider than it is scores by its own width
        kw = {} if width == self.key_dim \
            else {"scale": 1.0 / math.sqrt(self.key_dim)}

        def proj(p, x):
            q, k, v, *rest = self._project(p, x, lens[:, None])
            sink = () if self.SINK is None else (p[self.SINK],)
            return (_widen(q, width), _widen(k, width), v) + sink \
                + tuple(rest)

        q, k, v, *rest = self._run(proj, x, name=self.OP + "_projections")
        if self.SINK is not None:
            kw["sink"], rest = rest[0], rest[1:]
        if window is None:
            ctx, cache = paged_attention_step(
                q, k, v, cache, block_tables, context_lens, active=active,
                mesh=mesh, kv_heads=self.kv_heads,
                attend_scope=self.scope_name, **kw)
        else:
            ctx, cache = window_attention_step(
                q, k, v, cache, context_lens, window, active=active,
                attend_scope=self.scope_name, **kw)
        out = self._run(lambda p, ctx, *rest: self._finish(p, ctx, *rest),
                        ctx, *rest, name=self.OP + "_output")
        return out, tuple(as_array(c) for c in cache)


class GatedGQAttention(_GQAttention):
    """Grouped-query attention with per-head q / k norms and a sigmoid
    gate on its output, taken from the same normed input as q. A window
    makes it a `gqa_window` layer, which ropes q and k; None a `gqa_full`
    one, which encodes no position."""
    LEAVES = ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj", "q_norm",
              "k_norm")
    OP = "gated_gqa"

    def __init__(self, config, window=None):
        super().__init__()
        c = self.config = config
        self.window = window
        h, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.kv_heads, self.key_dim, self.value_dim = kv, d, d
        self.q_proj = _Weight(c.hidden_size, h * d)
        self.k_proj = _Weight(c.hidden_size, kv * d)
        self.v_proj = _Weight(c.hidden_size, kv * d)
        self.gate_proj = _Weight(c.hidden_size, h * d)
        self.o_proj = _Weight(h * d, c.hidden_size)
        self.q_norm = _Weight(d)
        self.k_norm = _Weight(d)

    def _project(self, p, x, positions):
        return gqa_projections(x, p, self.config, positions,
                               rope=self.window is not None)

    def _finish(self, p, ctx, gate):
        return _mm((ctx.astype(F32) * gate).astype(ctx.dtype), p["o_proj"])


class SinkGQAttention(_GQAttention):
    """Ungated grouped-query attention as MiMo-V2.5 publishes it: keys
    wider than values, rope on the first dims of a head, the values
    scaled, no norm on q or k, and where the kind has one a learned sink a
    head in the softmax's denominator (`attention_sink_bias`, [heads]).
    The kv-head count, the rope's base, the window and the sink are the
    KIND's: a `sink_window` layer's differ from a `sink_full` one's
    (`MiMoV2Config.attention`)."""
    OP = "sink_gqa"

    def __init__(self, config, window):
        super().__init__()
        a = self.spec = config.attention(window)
        if a["sink"] and a["window"] is None:
            raise NotImplementedError(
                "a sink in a full layer's softmax is not built (the decode "
                "step over block tables takes none): "
                "add_full_attention_sink_bias must be false")
        self.window = a["window"]
        self.kv_heads = a["kv_heads"]
        self.key_dim, self.value_dim = a["key_dim"], a["value_dim"]
        hid = config.hidden_size
        self.q_proj = _Weight(hid, a["heads"] * a["key_dim"])
        self.k_proj = _Weight(hid, a["kv_heads"] * a["key_dim"])
        self.v_proj = _Weight(hid, a["kv_heads"] * a["value_dim"])
        self.o_proj = _Weight(a["heads"] * a["value_dim"], hid)
        self.LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj")
        if a["sink"]:
            self.SINK = "attention_sink_bias"
            self.LEAVES += (self.SINK,)
            self.attention_sink_bias = self.create_parameter(
                shape=[a["heads"]], default_initializer=I.Constant(0.0))

    def _project(self, p, x, positions):
        return sink_projections(x, p, self.spec, positions)

    def _finish(self, p, ctx):
        return _mm(ctx, p["o_proj"])


class MambaMixer(_Mixer):
    """Mamba-1's selective state-space mixer as AI21-Jamba2-3B publishes it
    (the module docstring's `mamba` equations): the four projections in,
    x, dt and out without a bias, a depthwise causal conv of `d_conv` taps
    with one, RMS norms on dt's input, B and C, a diagonal A = -exp(A_log)
    and a skip D. It caches no K/V: a sequence's state is the conv's last
    `d_conv - 1` inputs (the served type) and the scan's [d_state, d_inner]
    (float32), `StatePool`s of the engine's. `scope("ssm")` holds the conv
    and the scan with their state's reads and writes; the projections stay
    in the layer's `attn`."""
    LEAVES = ("in_proj", "conv_weight", "conv_bias", "x_proj",
              "dt_layernorm", "b_layernorm", "c_layernorm", "dt_proj",
              "dt_bias", "A_log", "D", "out_proj")
    window = None     # no positions are kept at all

    def __init__(self, config):
        super().__init__()
        m = self.spec = config.mamba()
        self.eps = config.rms_norm_eps
        hid, e, n = config.hidden_size, m["d_inner"], m["d_state"]
        self.in_proj = _Weight(hid, 2 * e)
        # [d_conv, d_inner]: tap k weighs the input k - d_conv + 1 back
        self.conv_weight = self.create_parameter(
            shape=[m["d_conv"], e], default_initializer=I.Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter(
            shape=[e], default_initializer=I.Constant(0.0))
        self.x_proj = _Weight(e, m["dt_rank"] + 2 * n)
        self.dt_layernorm = _Weight(m["dt_rank"])
        self.b_layernorm = _Weight(n)
        self.c_layernorm = _Weight(n)
        self.dt_proj = _Weight(m["dt_rank"], e)
        # Mamba's initialisation: softplus(dt_bias) spread geometrically
        # over [0.001, 0.1] across the channels, A = -(1 .. d_state), D = 1
        dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), e))
        self.dt_bias = self.create_parameter(
            shape=[e], default_initializer=I.Assign(
                dt + np.log(-np.expm1(-dt))))
        self.A_log = self.create_parameter(
            shape=[e, n], default_initializer=I.Assign(
                np.log(np.tile(np.arange(1.0, n + 1), (e, 1)))))
        self.D = self.create_parameter(
            shape=[e], default_initializer=I.Constant(1.0))
        self.out_proj = _Weight(e, hid)

    def cache_layout(self):
        m = self.spec
        return (StatePool((m["d_conv"] - 1, m["d_inner"])),
                StatePool((m["d_state"], m["d_inner"]), "float32"))

    def _inputs(self, p, x):
        """x [b, s, hidden] (normed) -> (u, z) [b, s, d_inner] each."""
        u, z = jnp.split(_mm(x, p["in_proj"]), 2, axis=-1)
        return u, z

    def _scan_inputs(self, p, u):
        """u' [b, s, d_inner] -> (dt float32 [b, s, d_inner], B, C float32
        [b, s, d_state], A float32 [d_state, d_inner])."""
        m, eps = self.spec, self.eps
        r, n = m["dt_rank"], m["d_state"]
        d, b, c = jnp.split(_mm(u, p["x_proj"]), [r, r + n], axis=-1)
        d = rms_norm(d, p["dt_layernorm"], eps)
        b = rms_norm(b.astype(F32), p["b_layernorm"], eps)
        c = rms_norm(c.astype(F32), p["c_layernorm"], eps)
        dt = jax.nn.softplus(
            jnp.matmul(d, p["dt_proj"].astype(d.dtype),
                       preferred_element_type=F32)
            + p["dt_bias"].astype(F32))
        a = -jnp.exp(p["A_log"].astype(F32)).T
        return dt, b, c, a

    def _out(self, p, y, z):
        gate = jax.nn.silu(z.astype(F32))
        return _mm((y.astype(F32) * gate).astype(z.dtype), p["out_proj"])

    def forward_cached(self, x, cache, cur_len, live=None):
        """x [b, s, hidden] from the state `cache` (conv tail [b, d_conv -
        1, d_inner], scan state [b, d_state, d_inner]); `cur_len` is not
        read (the state is the position). With `live` ([b, s] bool, a
        prefill's real tokens first), a row's tokens past its real ones
        leave the state as it was: the state handed back is the one at the
        row's last real token, and the conv tail its last d_conv - 1 real
        inputs. Returns (out, new cache)."""
        conv, ssm = (as_array(a) for a in cache)
        lens = _true_lens(live)

        def f(p, x, conv, ssm):
            b, s, _ = x.shape
            n = jnp.full((b,), s, jnp.int32) if lens is None else lens
            u, z = self._inputs(p, x)
            with scope("ssm"):
                k = conv.shape[1]
                ext = jnp.concatenate([conv.astype(u.dtype), u], axis=1)
                acc = p["conv_bias"].astype(F32) + sum(
                    ext[:, i:i + s].astype(F32)
                    * p["conv_weight"][i].astype(F32) for i in range(k + 1))
                u = jax.nn.silu(acc).astype(u.dtype)
                # the last d_conv - 1 real inputs: ext's positions n .. n +
                # d_conv - 2 (the old tail's where a row has fewer)
                at = n[:, None] + jnp.arange(k)
                conv = jnp.take_along_axis(ext, at[..., None], axis=1
                                           ).astype(conv.dtype)
            dt, bm, cm, a = self._scan_inputs(p, u)
            with scope("ssm"):
                y, ssm = _ss.selective_scan(u, dt, a, bm, cm, p["D"], ssm, n)
            return self._out(p, y, z), conv, ssm

        out, conv, ssm = self._run(f, x, conv, ssm, name="mamba_mixer")
        return out, (as_array(conv), as_array(ssm))

    def forward(self, x):
        b, dtype = x.shape[0], as_array(x).dtype
        empty = tuple(pool.zeros(b, dtype) for pool in self.cache_layout())
        return self.forward_cached(x, empty, 0)[0]

    def forward_paged(self, x, cache, block_tables, context_lens,
                      active=None, mesh=None):
        """One new token a row (x [b, 1, hidden]) over the state pools
        `(conv [b, d_conv - 1, d_inner], scan [b, d_state, d_inner])`,
        indexed by the row's slot: a row that is not `active` keeps its
        state as it was. The step's update is XLA's fusion."""
        conv, ssm = (as_array(a) for a in cache)
        act = None if active is None else as_array(active)

        def f(p, x, conv, ssm):
            u, z = self._inputs(p, x[:, 0])
            with scope("ssm"):
                ext = jnp.concatenate([conv.astype(u.dtype), u[:, None]],
                                      axis=1)
                acc = p["conv_bias"].astype(F32) + jnp.sum(
                    ext.astype(F32) * p["conv_weight"].astype(F32), axis=1)
                u = jax.nn.silu(acc).astype(u.dtype)
                new_conv = ext[:, 1:].astype(conv.dtype)
            dt, bm, cm, a = self._scan_inputs(p, u)
            with scope("ssm"):
                uf = u.astype(F32)
                h = jnp.exp(dt[:, None, :] * a) * ssm \
                    + (dt * uf)[:, None, :] * bm[..., None]
                y = jnp.sum(h * cm[..., None], axis=1) \
                    + p["D"].astype(F32) * uf
                if act is not None:
                    new_conv = jnp.where(act[:, None, None], new_conv, conv)
                    h = jnp.where(act[:, None, None], h, ssm)
            return self._out(p, y.astype(u.dtype), z)[:, None], new_conv, h

        out, conv, ssm = self._run(f, x, conv, ssm, name="mamba_mixer")
        return out, (as_array(conv), as_array(ssm))


class PolyNorm(nn.Layer):
    """A PolyNorm activation's own leaves: `weight` [3] (the cube's, the
    square's and the input's), `bias` [1]."""

    def __init__(self):
        super().__init__()
        self.weight = self.create_parameter(
            shape=[3], default_initializer=I.Constant(1.0 / 3.0))
        self.bias = self.create_parameter(
            shape=[1], default_initializer=I.Constant(0.0))


class GatedFFN(nn.Layer):
    """W_down(act(x W_gate) * x W_up): SiLU where `act` is None, else
    PolyNorm with `act` (`scale`, `clamp`, `eps`) and leaves of its own,
    `act_fn`."""

    def __init__(self, hidden, width, act=None):
        super().__init__()
        self.gate_proj = _Weight(hidden, width)
        self.up_proj = _Weight(hidden, width)
        self.down_proj = _Weight(width, hidden)
        self.act = act
        if act is not None:
            self.act_fn = PolyNorm()

    def forward(self, x):
        shape = [int(s) for s in x.shape]
        flat = x.reshape([-1, shape[-1]])
        if self.act is None:
            y = _apply_op(gated_ffn, flat, self.gate_proj.weight,
                          self.up_proj.weight, self.down_proj.weight,
                          _name="gated_ffn")
        else:
            a = self.act
            y = _apply_op(
                lambda x, wg, wu, wd, w, b: gated_ffn(
                    x, wg, wu, wd, act=(w, b, a["scale"], a["clamp"],
                                        a["eps"])),
                flat, self.gate_proj.weight, self.up_proj.weight,
                self.down_proj.weight, self.act_fn.weight, self.act_fn.bias,
                _name="gated_ffn")
        return y.reshape(shape)


class RoutedSharedFFN(nn.Layer):
    """This chip's share of the routed experts plus, where the config has
    one, the shared expert (every chip computes that alike)."""

    def __init__(self, config):
        super().__init__()
        c, m = config, config.moe_spec()
        act = m.get("act")
        self.experts = ExpertShareLayer(
            c.hidden_size, m["width"], m["num_experts"], m["top_k"],
            ep_rank=c.ep_rank, ep_degree=c.ep_degree,
            routed_scaling_factor=m["scale"], norm_topk_prob=m["norm_topk"],
            pick_bias=m["pick_bias"], act=act)
        self.shared_experts = GatedFFN(
            c.hidden_size, m["shared"] * m["width"], act=act) \
            if m["shared"] else None

    def forward(self, x, live=None):
        routed = self.experts(x, live=live)
        if self.shared_experts is None:
            return routed
        with scope("shared"):
            return routed + self.shared_experts(x)


class HyperConnection(nn.Layer):
    """One sublayer's mHC (the module docstring's `hyper` equations):
    `phi` [n C, 2n + n^2], `alpha` [3] (pre, post, res), `bias` [2n +
    n^2]. `forward(streams, sublayer)` reads the sublayer's input from the
    streams [..., n, C], calls `sublayer(x) -> (y, extra)` and writes y
    back while it re-mixes them; the mixes and the Sinkhorn run under the
    scope `mhc`."""

    def __init__(self, config):
        super().__init__()
        n, c = config.mhc_expansion_rate, config.hidden_size
        self.iters, self.eps = config.mhc_sinkhorn_iters, config.rms_norm_eps
        self.clamp = config.hidden_clamp
        self.phi = self.create_parameter(
            shape=[n * c, 2 * n + n * n],
            default_initializer=I.Normal(0.0, 0.02))
        self.alpha = self.create_parameter(
            shape=[3], default_initializer=I.Constant(0.01))
        self.bias = self.create_parameter(
            shape=[2 * n + n * n], default_initializer=I.Constant(0.0))

    def forward(self, streams, sublayer):
        with scope("mhc"):
            x, res, post = _apply_op(
                lambda s, phi, a, b: hyper_in(s, phi, a, b, self.iters,
                                              self.eps),
                streams, self.phi, self.alpha, self.bias, _name="hyper_in")
        y, extra = sublayer(x)
        with scope("mhc"):
            out = _apply_op(
                lambda s, r, p, y: hyper_out(s, r, p, y, self.clamp),
                streams, res, post, y, _name="hyper_out")
        return out, extra


# a mixer kind -> how it is built from a config
MIXERS = {
    "latent": LatentAttention,
    "gqa_window": lambda c: GatedGQAttention(c, c.sliding_window),
    "gqa_full": lambda c: GatedGQAttention(c, None),
    "sink_window": lambda c: SinkGQAttention(c, True),
    "sink_full": lambda c: SinkGQAttention(c, False),
    "mamba": MambaMixer,
    "gdla_window": lambda c: DiffLatentAttention(c, c.sliding_window),
    "gdla_full": lambda c: DiffLatentAttention(c, None),
}


class LatentMoEDecoderLayer(nn.Layer):
    def __init__(self, config, kinds):
        super().__init__()
        mixer, ffn, norms, *residual = kinds
        residual = residual[0] if residual else "plain"
        spec = config.moe_spec()
        shared = spec["shared"]
        if mixer not in MIXERS or norms not in ("sandwich", "pre") \
                or ffn not in ("dense", "routed+shared", "routed") \
                or (ffn != "dense" and (ffn == "routed") == bool(shared)) \
                or residual not in ("plain", "hyper"):
            raise ValueError(f"unknown layer kinds {kinds}")
        self.eps = config.rms_norm_eps
        self.routed = ffn != "dense"
        self.sandwich = norms == "sandwich"
        self.hyper = residual == "hyper"
        self.input_layernorm = _Weight(config.hidden_size)
        self.self_attn = MIXERS[mixer](config)
        self.pre_mlp_layernorm = _Weight(config.hidden_size)
        self.mlp = RoutedSharedFFN(config) if self.routed else GatedFFN(
            config.hidden_size, config.intermediate_size,
            act=spec.get("act"))
        if self.sandwich:
            self.post_attention_layernorm = _Weight(config.hidden_size)
            self.post_mlp_layernorm = _Weight(config.hidden_size)
        if self.hyper:
            self.attn_hc = HyperConnection(config)
            self.mlp_hc = HyperConnection(config)

    def _norm(self, x, which):
        return _apply_op(rms_norm, x, getattr(self, which).weight,
                         _name="rms_norm", eps=self.eps)

    def _block(self, x, attend, live=None):
        """The block around `attend(normed x) -> (out, cache)`."""
        if self.hyper:
            return self._hyper_block(x, attend, live)
        with scope("attn"):
            a, cache = attend(self._norm(x, "input_layernorm"))
            if self.sandwich:
                a = self._norm(a, "post_attention_layernorm")
            x = x + a
        with scope("mlp"):
            m = self._norm(x, "pre_mlp_layernorm")
            m = self.mlp(m, live=live) if self.routed else self.mlp(m)
            if self.sandwich:
                m = self._norm(m, "post_mlp_layernorm")
            return x + m, cache

    def _hyper_block(self, streams, attend, live):
        """The block over n residual streams [..., n, hidden]: each
        sublayer, its norms included, between its mHC's read and write."""
        def attn(x):
            a, cache = attend(self._norm(x, "input_layernorm"))
            if self.sandwich:
                a = self._norm(a, "post_attention_layernorm")
            return a, cache

        def mlp(x):
            m = self._norm(x, "pre_mlp_layernorm")
            m = self.mlp(m, live=live) if self.routed else self.mlp(m)
            if self.sandwich:
                m = self._norm(m, "post_mlp_layernorm")
            return m, None

        with scope("attn"):
            streams, cache = self.attn_hc(streams, attn)
        with scope("mlp"):
            streams, _ = self.mlp_hc(streams, mlp)
        return streams, cache

    def forward(self, x):
        return self._block(x, lambda a: (self.self_attn(a), None))[0]

    def forward_cached(self, x, cache, cur_len, live=None):
        return self._block(
            x, lambda a: self.self_attn.forward_cached(a, cache, cur_len,
                                                       live),
            live=live)

    def forward_paged(self, x, cache, block_tables, context_lens,
                      active=None, **kw):
        return self._block(
            x, lambda a: self.self_attn.forward_paged(
                a, cache, block_tables, context_lens, active=active, **kw),
            live=None if active is None else as_array(active))


class LatentMoEModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = _Weight(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LatentMoEDecoderLayer(config, kinds)
             for kinds in config.layer_kinds()])
        self.norm = _Weight(config.hidden_size)
        # n residual streams where the layers are hyper-connected
        self.streams = config.mhc_expansion_rate if any(
            k[3:] == ("hyper",) for k in config.layer_kinds()) else None

    def _embed(self, input_ids):
        scale = getattr(self.config, "embed_scale", None)
        n = self.streams

        def take(ids, w):
            rows = jnp.take(w, ids, axis=0)
            rows = rows if scale is None \
                else (rows.astype(F32) * scale).astype(rows.dtype)
            # where the layers are hyper-connected, copied into every stream
            return rows if n is None else jnp.broadcast_to(
                rows[..., None, :], rows.shape[:-1] + (n, rows.shape[-1]))

        with scope("embed"):
            return _apply_op(take, input_ids, self.embed_tokens.weight,
                             _name="embedding")

    def _final_norm(self, h):
        eps = self.config.rms_norm_eps
        with scope("head"):
            if self.streams is None:
                return _apply_op(rms_norm, h, self.norm.weight,
                                 _name="rms_norm", eps=eps)
            return _apply_op(   # the norm of the streams' sum
                lambda h, g: rms_norm(jnp.sum(h.astype(F32), axis=-2)
                                      .astype(h.dtype), g, eps),
                h, self.norm.weight, _name="rms_norm")

    def forward(self, input_ids, attn_mask=None):
        if attn_mask is not None:
            raise NotImplementedError(
                "LatentMoEModel attends causally; it takes no attn_mask")
        h = self._embed(input_ids)
        for layer in self.layers:
            h = layer(h)
        return self._final_norm(h)

    def forward_cached(self, input_ids, caches, cur_len, live=None):
        """`live` ([batch, positions] bool, optional) says which positions
        hold a token: the expert layers make no pair of one that does not
        (a prefill's padding). Every position where None."""
        h = self._embed(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, caches):
            h, nc = layer.forward_cached(h, cache, cur_len, live=live)
            # The residual stream crosses from layer to layer through an
            # optimization barrier. Without it XLA:TPU folds the residual
            # adds into their consumers and keeps every sublayer's output
            # of a prefill alive to the last layer: 67 MB a layer at 8,192
            # tokens of hidden 2,048, 4.1 GB of temporaries over 32 layers
            # against 1.0 GB behind the barrier (AOT for a v5e, PR 31).
            h = _apply_op(jax.lax.optimization_barrier, h,
                          _name="residual_barrier")
            new_caches.append(nc)
        return self._final_norm(h), new_caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, **kw):
        h = self._embed(input_ids)
        new_caches = []
        for layer, cache in zip(self.layers, paged_caches):
            h, nc = layer.forward_paged(h, cache, block_tables,
                                        context_lens, active=active, **kw)
            new_caches.append(nc)
        return self._final_norm(h), new_caches


class _Head(_Weight):
    """The untied head, [hidden, vocab]: float32 logits whatever the
    model's dtype (a bf16 logit near 2 moves in steps of 1/64)."""

    def forward(self, h):
        return _apply_op(
            lambda h, w: jnp.matmul(h, w.astype(h.dtype),
                                    preferred_element_type=F32),
            h, self.weight, _name="lm_head")


def _live_positions(input_ids, true_lens):
    """[batch, positions] bool: the positions of a padded prefill that
    hold a prompt's token."""
    return jnp.arange(int(input_ids.shape[1]))[None, :] < true_lens[:, None]


class LatentMoEForCausalLM(CausalLMBase):
    """The serving contract of `GPTForCausalLM` (`forward`,
    `forward_cached`, `forward_paged`, `generate`) over `LatentMoEModel`.
    Serving only: nothing here has been trained through (the expert layer
    and the blocked attention have no tested backward)."""

    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        self.config = config
        self.model = LatentMoEModel(config)
        self.lm_head = None if config.tie_word_embeddings else _Head(
            config.hidden_size, config.vocab_size)
        self.loss_fn = nn.CrossEntropyLoss()

    def kv_cache_layouts(self):
        """Each layer's mixer says what it caches."""
        return tuple(layer.self_attn.cache_layout()
                     for layer in self.model.layers)

    def kv_cache_windows(self):
        return tuple(layer.self_attn.window for layer in self.model.layers)

    def _backbone_embed_weight(self):
        return self.model.embed_tokens.weight

    @scope("head")
    def _head(self, h):
        """float32 logits whatever the model's dtype, tied or not (a bf16
        logit near 10 moves in steps of 1/16)."""
        if self.lm_head is not None:
            return self.lm_head(h)
        return _apply_op(
            lambda h, w: jnp.einsum("...h,vh->...v", h, w.astype(h.dtype),
                                    preferred_element_type=F32),
            h, self.model.embed_tokens.weight, _name="lm_head")

    def forward(self, input_ids, attn_mask=None):
        return self._head(self.model(input_ids, attn_mask))

    def forward_cached(self, input_ids, caches, cur_len, live=None):
        h, new_caches = self.model.forward_cached(input_ids, caches, cur_len,
                                                  live=live)
        return self._head(h), new_caches

    def forward_prefill(self, input_ids, caches, true_lens):
        """`CausalLMBase.forward_prefill` with the prompts' padding told to
        the expert layers: a row of `true_lens` 0 is all padding."""
        logits, caches = self.forward_cached(
            input_ids, caches, 0, live=_live_positions(input_ids, true_lens))
        return as_array(logits)[jnp.arange(int(input_ids.shape[0])),
                                jnp.maximum(true_lens - 1, 0), :], caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, mesh=None, limit_lens=None,
                      max_layers=None):
        if int(input_ids.shape[1]) != 1 or limit_lens is not None \
                or max_layers is not None:
            raise NotImplementedError(
                "latent pages are decoded one token a row: no window step, "
                "no shallow-exit draft (speculative decoding and chunked "
                "prefill are not built for latent attention)")
        h, new_caches = self.model.forward_paged(
            input_ids, paged_caches, block_tables, context_lens,
            active=active)
        return self._head(h), new_caches


class AfmoeForCausalLM(LatentMoEForCausalLM):
    """Trinity-Mini's shape over the same stack: window and full gated
    grouped-query layers, dense then routed + shared FFNs, sandwich norms,
    the embedding scaled by sqrt(hidden), an untied float32 head. Serving
    only, like its parent."""

    def forward_prefill(self, input_ids, caches, true_lens):
        """The head at each prompt's last position alone: the float32
        logits of every position of a round (16,384 x 200,192) would be
        13 GB."""
        h, caches = self.model.forward_cached(
            input_ids, caches, 0, live=_live_positions(input_ids, true_lens))
        last = as_array(h)[jnp.arange(int(input_ids.shape[0])),
                           jnp.maximum(true_lens - 1, 0)][:, None]
        return as_array(self._head(Tensor(last)))[:, 0], caches

    def forward_paged(self, input_ids, paged_caches, block_tables,
                      context_lens, active=None, mesh=None, limit_lens=None,
                      max_layers=None):
        if int(input_ids.shape[1]) != 1 or limit_lens is not None \
                or max_layers is not None:
            raise NotImplementedError(
                "a mixed layout of window rings and full pages is decoded "
                "one token a row: no window step, no shallow-exit draft "
                "(speculative decoding and chunked prefill are not built "
                "for it)")
        h, new_caches = self.model.forward_paged(
            input_ids, paged_caches, block_tables, context_lens,
            active=active, mesh=mesh)
        return self._head(h), new_caches


class MiMoV2ForCausalLM(AfmoeForCausalLM):
    """MiMo-V2.5's shape over the same stack: `sink_window` and
    `sink_full` layers whose kv-head counts differ, a dense then routed
    FFNs with no shared expert, norms before the sublayers only, an
    untied float32 head taken at each prompt's last position. Serving
    only, like its parents."""


class JambaForCausalLM(AfmoeForCausalLM):
    """AI21-Jamba2-3B's shape over the same stack: Mamba layers beside
    grouped-query attention layers with every option off, the dense FFN
    in every layer, norms before the sublayers only, a tied float32 head
    taken at each prompt's last real position. A prefill's padding leaves
    every Mamba layer's state where the prompt's last token left it (the
    stack tells the mixer which positions are live). Serving only: the
    scan has no backward."""


class MotifForCausalLM(AfmoeForCausalLM):
    """Motif-3-Beta's shape over the same stack: GDLA layers over a window
    (rings) and over every position (pages), a dense then routed + shared
    FFNs with PolyNorm, norms before the sublayers, four residual streams
    mixed by mHC around every sublayer, an untied float32 head taken at
    each prompt's last position over the streams' sum. The
    multi-token-prediction layer is not built. Serving only, like its
    parents: nothing here has a tested backward."""
