"""Nemotron-H (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type: nemotron_h``; the family: arXiv:2504.03624): a decoder whose
every layer is ONE mixer — ``x <- x + Mixer(RMSNorm(x))`` — of three
kinds, laid out by ``hybrid_override_pattern``, one character a layer:

- ``M`` — **Mamba-2** (Dao & Gu 2024, arXiv:2405.21060).  ``[z | xBC | dt]
  = h W_in`` with ``d_inner = mamba_num_heads * mamba_head_dim`` (not
  ``expand * d_model``), ``xBC`` being ``d_inner + 2 * n_groups *
  ssm_state_size`` wide; ``xBC <- silu(conv(xBC) + b_conv)``, a depthwise
  causal convolution; split into ``x`` [heads, head_dim] and ``B``, ``C``
  [n_groups, ssm_state_size]; ``dt <- softplus(dt + dt_bias)`` and ``A =
  -exp(A_log)``, one scalar a head, float32; the state-space scan
  (ops/state_space.py) ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = H_t C_t + D x_t``; then ``y <- y * silu(z)``, an RMSNorm **per
  group** of ``d_inner / n_groups`` channels with a weight (the gate
  first: ``norm_before_gate`` false), and ``W_out``.  With packed
  documents the state and the convolution's history are zero at a
  document's first token.
- ``*`` — **attention**: grouped-query causal softmax attention inside a
  document, no bias, no q/k norm and **no position embedding** (the
  family has none; the state-space layers carry order).
- ``E`` — **experts** (moe/layer.py): ``s = sigmoid(h W_r)`` in float32;
  the choice is the ``top_k`` largest of ``s + e_score_correction_bias``
  (a leaf the loss does not train); the weights are ``s`` of the chosen,
  divided by their sum, times ``routed_scaling_factor``; an expert is
  un-gated, ``W_down relu(W_up h)^2``; beside them one shared expert of
  the same form, added as it is.  ``experts_held`` (with
  ``expert_offset``) makes this chip's share of an expert-parallel layer.

Plain RMSNorm with a weight; untied head; final norm.  The layers of a
kind are stacked on their own, ``blocks = {"ssm": [P, n_M, ...],
"experts": [P, n_E, ...], "attn": [P, n_*, ...]}`` over ``P`` repeats of
the pattern (``num_layers / len(pattern)``; the published pattern is all
52 layers, once), and ``models/model.py scan_layer_kinds`` runs the loop.
Not built: a load-driven update of the router's bias (it stays where it
was initialised); serving (a cache that holds recurrent state beside keys
and values — the entry points raise); ZeRO-3 and parameter streaming.
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_half,
                                        held_share_model, layer_block,
                                        no_experts, param_count, qdot,
                                        refuse_param_stream, resolve_size,
                                        scan_layer_kinds, segment_ids_of)
from deepspeed_tpu.models.llama import _rms_norm
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.ops.linear_attention import causal_conv
from deepspeed_tpu.ops.state_space import ssd_scan
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_CONV, SCOPE_GATE_NORM, SCOPE_HEAD_LOSS,
    SCOPE_IN_PROJ, SCOPE_OUT_PROJ, SCOPE_SCAN, SCOPE_SSM)

SSM, EXPERTS, ATTN = "ssm", "experts", "attn"
#: ``hybrid_override_pattern``'s characters
KINDS = {"M": SSM, "E": EXPERTS, "*": ATTN}
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    max_seq_len: int = 262144
    num_layers: int = 52
    #: one character a layer (:data:`KINDS`); ``num_layers`` is a whole
    #: number of repeats of it
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    d_model: int = 2688
    # attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # experts
    d_ff: int = 1856
    num_experts: int = 128
    top_k: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    #: the plan of the rows held here is this many times their even share
    #: (moe/layer.py ``MoEConfig.held_rows_factor``)
    held_rows_factor: int = 2
    shared_expert_d_ff: int = 3712
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    moe_dispatch: str = "grouped"
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    @property
    def pattern(self) -> tuple:
        """The kinds of one repeat's layers, in order."""
        unknown = set(self.hybrid_override_pattern) - set(KINDS)
        if unknown:
            raise ValueError(
                f"nemotron-h: hybrid_override_pattern has {sorted(unknown)}; "
                f"a layer is one of {sorted(KINDS)} (a dense MLP layer, "
                f"'-', is not built)")
        return tuple(KINDS[c] for c in self.hybrid_override_pattern)

    @property
    def num_periods(self) -> int:
        n = len(self.hybrid_override_pattern)
        if n == 0 or self.num_layers % n:
            raise ValueError(
                f"nemotron-h: {self.num_layers} layers are not whole "
                f"repeats of the {n}-layer pattern "
                f"{self.hybrid_override_pattern!r}")
        return self.num_layers // n

    def layers_of(self, kind: str) -> int:
        """Layers of ``kind`` in the whole model."""
        return self.num_periods * self.pattern.count(kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig.of(self, router="sigmoid", activation="relu2",
                            dispatch_mode=self.moe_dispatch)


NEMOTRON_H_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=5,
                 hybrid_override_pattern="MEM*E", d_model=32, num_heads=4,
                 num_kv_heads=2, head_dim=16, mamba_num_heads=4,
                 mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                 chunk_size=16, d_ff=16, num_experts=8, top_k=2,
                 shared_expert_d_ff=32),
    # huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    # config.json: the defaults above.  31.58B parameters whole; one chip
    # trains the first nine layers with 8 of each layer's 128 experts held
    # (benchmarks/configs)
    "3-nano-30b-a3b": dict(),
}


def init_params(config: NemotronHConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02, the mixers' output projections 0.02 /
    sqrt(layers) (``rescale_prenorm_residual``); norm weights 1; ``A_log =
    log U(1, 16)`` (``mamba_ssm``'s draw; Hugging Face's constructor has
    log(1..heads)), ``dt = exp U(log time_step_min, log time_step_max)``
    floored at ``time_step_floor`` with ``dt_bias`` its inverse softplus,
    ``D = 1``, the convolution's taps normal 0.02 and its bias 0,
    ``e_score_correction_bias`` 0."""
    D, V, L = config.d_model, config.vocab_size, config.num_layers
    n_p = config.num_periods
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    Hm, K = config.mamba_num_heads, config.conv_kernel
    d_in, conv_ch = config.d_inner, config.conv_channels
    std = 0.02
    res_std = std / L ** 0.5
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 16))

    def moe(key, lead):
        keys = jax.random.split(key, lead[0] * lead[1])
        stacked = jax.vmap(partial(init_moe_params, config.moe))(keys)
        return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), stacked)

    lead = {kind: (n_p, config.pattern.count(kind))
            for kind in (SSM, EXPERTS, ATTN)}
    ssm, exp, att = lead[SSM], lead[EXPERTS], lead[ATTN]
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        next(k), ssm + (Hm,), minval=math.log(config.time_step_min),
        maxval=math.log(config.time_step_max))), config.time_step_floor)
    blocks = {
        SSM: {
            "norm": jnp.ones(ssm + (D,)),
            "w_in": norm(next(k), ssm + (D, d_in + conv_ch + Hm)) * std,
            "conv_w": norm(next(k), ssm + (K, conv_ch)) * std,
            "conv_b": jnp.zeros(ssm + (conv_ch,)),
            "A_log": jnp.log(jax.random.uniform(
                next(k), ssm + (Hm,), minval=1.0, maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones(ssm + (Hm,)),
            "gate_norm": jnp.ones(ssm + (d_in,)),
            "w_out": norm(next(k), ssm + (d_in, D)) * res_std,
        },
        EXPERTS: {
            "norm": jnp.ones(exp + (D,)),
            "moe": moe(next(k), exp),
        },
        ATTN: {
            "norm": jnp.ones(att + (D,)),
            "wq": norm(next(k), att + (D, H * hd)) * std,
            "wk": norm(next(k), att + (D, KV * hd)) * std,
            "wv": norm(next(k), att + (D, KV * hd)) * std,
            "wo": norm(next(k), att + (H * hd, D)) * res_std,
        },
    }
    return {
        "wte": norm(next(k), (V, D)) * std,
        # a kind the pattern lacks has no stack (and no block to read it)
        "blocks": {kind: tree for kind, tree in blocks.items()
                   if lead[kind][1]},
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }


def logical_specs(config: NemotronHConfig) -> dict:
    lead = lambda spec: P(None, None, *spec)
    moe = jax.tree.map(lead, moe_logical_specs(config.moe),
                       is_leaf=lambda s: isinstance(s, P))
    blocks = {
        SSM: {"norm": P(), "w_in": P(), "conv_w": P(), "conv_b": P(),
              "A_log": P(), "dt_bias": P(), "D": P(), "gate_norm": P(),
              "w_out": P()},
        EXPERTS: {"norm": P(), "moe": moe},
        ATTN: {"norm": P(),
               "wq": P(None, None, None, "model"),
               "wk": P(None, None, None, "model"),
               "wv": P(None, None, None, "model"),
               "wo": P(None, None, "model", None)},
    }
    return {
        "wte": P("model", None),
        "blocks": {kind: tree for kind, tree in blocks.items()
                   if kind in config.pattern},
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }


def ssm_branch(x, layer, config, segment_ids, heads=None):
    """``Mamba2(N(x))``, the branch alone: whoever calls owns the residual
    (the block below adds ``x``; models/granite_hybrid.py scales the branch
    first).  ``config`` is any with this file's Mamba-2 sizes; ``heads``
    are the heads BUILT here (None: all ``mamba_num_heads``) — a
    tensor-parallel share builds ``z``, ``x``, ``dt`` and what follows them
    for its own heads and ``B``, ``C`` whole, and its gated norm runs over
    the channels it holds.  The caller's scope is ``ssm``."""
    B, S, _ = x.shape
    Hm = config.mamba_num_heads if heads is None else heads
    Pd = config.mamba_head_dim
    G, N = config.n_groups, config.ssm_state_size
    d_in = Hm * Pd
    conv_ch = d_in + 2 * G * N
    f32 = lambda a: a.astype(jnp.float32)
    with jax.named_scope(SCOPE_IN_PROJ):
        h = _rms_norm(x, layer["norm"], config.norm_eps)
        zxbcdt = qdot(h, layer["w_in"])
        z = zxbcdt[..., :d_in]
        dt = jax.nn.softplus(f32(zxbcdt[..., d_in + conv_ch:])
                             + f32(layer["dt_bias"]))
        A = -jnp.exp(f32(layer["A_log"]))
    with jax.named_scope(SCOPE_CONV):
        # x, B and C each from the projection itself and as the array the
        # scan takes, positions along lanes: how XLA lays this layer's
        # arrays out by itself, and how the scan's kernels read them
        xs, Bs, Cs = (
            causal_conv(zxbcdt, layer["conv_w"][:, first:first + width],
                        segment_ids, bias=layer["conv_b"][first:first + width],
                        activation="silu", positions="lanes",
                        first_channel=d_in + first)
            for first, width in ((0, d_in), (d_in, G * N),
                                 (d_in + G * N, G * N)))
    with jax.named_scope(SCOPE_SCAN):
        y = ssd_scan(xs.reshape(B, S, Hm, Pd), dt, A,
                     Bs.reshape(B, S, G, N), Cs.reshape(B, S, G, N),
                     layer["D"], segment_ids, chunk=config.chunk_size)
    y = jax.ad_checkpoint.checkpoint_name(y, "attn_out")
    with jax.named_scope(SCOPE_GATE_NORM):
        y = _gated_norm(y.reshape(B, S, d_in), z, layer["gate_norm"], G,
                        config.norm_eps)
    with jax.named_scope(SCOPE_OUT_PROJ):
        return qdot(y, layer["w_out"])


def _gated_norm(y, z, w, groups, eps):
    """``y * silu(z)``, then one RMSNorm per group of ``d_inner / groups``
    channels with the weight ``w`` [d_inner] (the gate first:
    ``norm_before_gate`` false), in float32 as the layer's own norm
    computes it; ``y``, ``z`` [..., d_inner] -> ``y``'s dtype."""
    f32 = lambda a: a.astype(jnp.float32)
    shape = y.shape[:-1] + (groups, y.shape[-1] // groups)
    gated = (f32(y) * jax.nn.silu(f32(z))).reshape(shape)
    return _rms_norm(gated, f32(w).reshape(shape[-2:]), eps).reshape(
        y.shape).astype(y.dtype)


@jax.named_scope(SCOPE_BLOCK)
def _ssm_block(x, layer, config: NemotronHConfig, train, rng=None,
               segment_ids=None):
    with jax.named_scope(SCOPE_SSM):
        out = ssm_branch(x, layer, config, segment_ids)
        with jax.named_scope(SCOPE_OUT_PROJ):
            return x + out, no_experts()


def attention_branch(x, layer, config, segment_ids, heads=None,
                     q_scale=None):
    """``Attention(N(x))``, the branch alone, its scopes (``attn``) its
    own.  ``config`` is any with this file's attention sizes; ``heads`` =
    (query, key/value) heads BUILT here (None: all of both); ``q_scale``
    multiplies ``q`` in float32 before its one rounding: a family whose
    scores are not scaled by ``1 / sqrt(head_dim)`` folds the ratio in
    there (``causal_attention`` takes no scale)."""
    B, S, _ = x.shape
    H, KV = heads or (config.num_heads, config.num_kv_heads)
    hd = config.head_dim
    with jax.named_scope(SCOPE_ATTN):
        h = _rms_norm(x, layer["norm"], config.norm_eps)
        q = qdot(h, layer["wq"])
        if q_scale is not None:
            q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
        q = q.reshape(B, S, H, hd)
        k = qdot(h, layer["wk"]).reshape(B, S, KV, hd)
        v = qdot(h, layer["wv"]).reshape(B, S, KV, hd)
        # no rotary embedding: the family has no position embedding
        attn = causal_attention(q, k, v, impl=config.attention_impl,
                                segment_ids=segment_ids)
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    with jax.named_scope(SCOPE_ATTN):
        return qdot(attn.reshape(B, S, H * hd), layer["wo"])


@jax.named_scope(SCOPE_BLOCK)
def _attn_block(x, layer, config: NemotronHConfig, train, rng=None,
                segment_ids=None):
    out = attention_branch(x, layer, config, segment_ids)
    with jax.named_scope(SCOPE_ATTN):
        return x + out, no_experts()


@jax.named_scope(SCOPE_BLOCK)
def _experts_block(x, layer, config: NemotronHConfig, train, rng=None,
                   segment_ids=None):
    return expert_half(
        x, layer["moe"], config.moe,
        lambda x: _rms_norm(x, layer["norm"], config.norm_eps), train, rng)


_BLOCKS = {SSM: _ssm_block, EXPERTS: _experts_block, ATTN: _attn_block}


def head_with_aux(params, batch, config: NemotronHConfig,
                  train: bool = True, rng=None):
    """-> (the head's inputs, router loss summed over layers, routed rows
    over ``held_rows_bound`` summed over layers: int32, 0 unless the
    experts held are a subset)."""
    refuse_param_stream(
        "nemotron-h",
        "three stacks (ssm, experts, attn) walked pattern by pattern")
    dtype = jnp.dtype(config.dtype)
    x = embed_tokens(params["wte"], batch["input_ids"], dtype)
    x, (aux, over) = scan_layer_kinds(
        x, params["blocks"], config.pattern,
        {kind: layer_block(block, config, train=train, rng=rng,
                           segment_ids=segment_ids_of(batch))
         for kind, block in _BLOCKS.items()})
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
    return Head(x, params["lm_head"]), aux, over


def count_params(config: NemotronHConfig) -> int:
    return param_count(partial(init_params, config))


def nemotron_h_model(size: str = "3-nano-30b-a3b", **overrides) -> Model:
    config = NemotronHConfig(**{
        **resolve_size(NEMOTRON_H_SIZES, size, "nemotron_h"), **overrides})
    return held_share_model(
        "nemotron-h", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        expert_layers=config.layers_of(EXPERTS), expert_matrices=2,
        lookup_params=config.vocab_size * config.d_model,
        serving_needs=(
            "serving a model with state-space layers needs a cache that "
            "holds each sequence's recurrent state (and convolution "
            "history) beside the attention layers' keys and values"))
