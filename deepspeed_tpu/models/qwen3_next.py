"""Qwen3-Next (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct): a decoder
whose layers are of two kinds — three Gated-DeltaNet (linear attention,
Yang et al. 2024, arXiv:2412.06464) layers to one gated full-attention
layer — every layer followed by a routed-expert FFN with a gated shared
expert.

``N(x; w) = x / rms(x) * (1 + w)`` (zero-centred weight).  Layer ``i`` is
a full-attention layer when ``(i + 1) % full_attention_interval == 0``,
else a linear one.  Every layer: ``x <- x + Mixer(N(x))``, then
``x <- x + MoE(N(x))``.  No biases; untied head; final ``N``.

- **Gated DeltaNet mixer.**  ``[q, k, v, z] = h W_qkvz``, ``[b, a] = h
  W_ba``; ``[q, k, v] <- silu(conv(concat(q, k, v)))``, a depthwise causal
  convolution (width 4, no bias); per value head ``beta = sigmoid(b)``,
  ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q <- l2norm(q) /
  sqrt(dk)``, ``k <- l2norm(k)``; the gated delta rule
  (ops/linear_attention.py) with a float32 state; ``y = RMSNorm(o; w_o,
  over the head, plain weight) * silu(z)``; output ``y W_out``.  With
  packed documents the state and the convolution's history are zero at a
  document's first token.
- **Gated full attention.**  ``[q, gate] = h W_q`` (per head a query and
  a gate), ``k``, ``v``; ``q <- N(q; w_q)``, ``k <- N(k; w_k)`` per head;
  rotate-half rotary on the first ``partial_rotary_factor`` of the head;
  causal softmax attention inside a document (GQA); output ``(attn *
  sigmoid(gate)) W_o``.
- **Experts.**  ``moe/layer.py``: softmax over all ``num_experts``, the
  ``top_k`` largest renormalised, SwiGLU experts, plus ``sigmoid(h w_sg) *
  SwiGLU_shared(h)``.  ``experts_held`` (with ``expert_offset``) makes
  this chip's share of an expert-parallel layer: the router keeps its
  width, the weights are the held experts', and a token's other choices
  add nothing.

Both kinds of layer are stacked on their own, ``blocks = {"linear": [P,
n_linear, ...], "full": [P, 1, ...]}`` over the ``P`` periods, and
``models/model.py scan_layer_kinds`` runs the loop.  Not built:
multi-token prediction; serving (a cache that holds recurrent state
beside keys and values — the entry points raise).
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_half,
                                        held_share_model, layer_block,
                                        param_count, qdot,
                                        refuse_param_stream, resolve_size,
                                        scan_layer_kinds, segment_ids_of)
from deepspeed_tpu.models.llama import _rms_norm, rope
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.ops.linear_attention import (causal_conv,
                                                gated_delta_rule)
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_CONV, SCOPE_DELTA_RULE, SCOPE_GATE_NORM,
    SCOPE_HEAD_LOSS, SCOPE_IN_PROJ, SCOPE_LINEAR_ATTN, SCOPE_OUT_PROJ)

LINEAR, FULL = "linear", "full"


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    max_seq_len: int = 262144
    num_layers: int = 48
    full_attention_interval: int = 4
    d_model: int = 2048
    # gated full attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated delta rule
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    delta_rule_chunk: int = 64
    # experts
    d_ff: int = 512
    num_experts: int = 512
    top_k: int = 10
    norm_topk_prob: bool = True
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    shared_expert_d_ff: int = 512
    aux_loss_coef: float = 0.001
    load_balance: str = "all_choices"
    moe_dispatch: str = "grouped"
    rms_norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    @property
    def pattern(self) -> tuple:
        """The kinds of one period's layers, in order."""
        n = self.full_attention_interval
        return (LINEAR,) * (n - 1) + (FULL,)

    @property
    def num_periods(self) -> int:
        if self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"qwen3-next: {self.num_layers} layers are not whole "
                f"periods of {self.full_attention_interval}")
        return self.num_layers // self.full_attention_interval

    @property
    def rotary_ndims(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig.of(self, activation="silu_glu",
                            dispatch_mode=self.moe_dispatch,
                            shared_expert_gate=True)


QWEN3_NEXT_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=4, d_model=32,
                 num_heads=4, num_kv_heads=2, head_dim=16,
                 linear_num_key_heads=2, linear_num_value_heads=4,
                 linear_key_head_dim=8, linear_value_head_dim=8,
                 d_ff=16, num_experts=8, top_k=2, shared_expert_d_ff=16,
                 delta_rule_chunk=16),
    # huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct config.json: the
    # defaults above.  79.67B parameters whole; one chip trains one period
    # with 32 of its 512 experts held (benchmarks/configs)
    "80b-a3b": dict(),
}


def _widths(config: Qwen3NextConfig):
    Hk, Hv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    return Hk, Hv, dk, dv


def init_params(config: Qwen3NextConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02 (output projections 0.02 / sqrt(2 * layers)),
    zero-centred norm weights 0 and the per-head output norm 1, ``A_log =
    log U(0, 16)`` and ``dt_bias = 1`` (Hugging Face's initialisation of
    the layer), the convolution's taps normal 0.02."""
    D, V, L = config.d_model, config.vocab_size, config.num_layers
    n_p, n_lin = config.num_periods, config.full_attention_interval - 1
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    Hk, Hv, dk, dv = _widths(config)
    K = config.linear_conv_kernel_dim
    std = 0.02
    res_std = std / (2 * L) ** 0.5
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 24))

    def moe(key, lead):
        keys = jax.random.split(key, lead[0] * lead[1])
        stacked = jax.vmap(partial(init_moe_params, config.moe))(keys)
        return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), stacked)

    lin, full = (n_p, n_lin), (n_p, 1)
    conv_ch = 2 * Hk * dk + Hv * dv
    return {
        "wte": norm(next(k), (V, D)) * std,
        "blocks": {
            LINEAR: {
                "attn_norm": jnp.zeros(lin + (D,)),
                "w_qkvz": norm(next(k), lin + (D, conv_ch + Hv * dv)) * std,
                "w_ba": norm(next(k), lin + (D, 2 * Hv)) * std,
                "conv_w": norm(next(k), lin + (K, conv_ch)) * std,
                "A_log": jnp.log(jax.random.uniform(
                    next(k), lin + (Hv,), minval=1e-3, maxval=16.0)),
                "dt_bias": jnp.ones(lin + (Hv,)),
                "o_norm": jnp.ones(lin + (dv,)),
                "w_out": norm(next(k), lin + (Hv * dv, D)) * res_std,
                "mlp_norm": jnp.zeros(lin + (D,)),
                "moe": moe(next(k), lin),
            },
            FULL: {
                "attn_norm": jnp.zeros(full + (D,)),
                "wq": norm(next(k), full + (D, H * 2 * hd)) * std,
                "wk": norm(next(k), full + (D, KV * hd)) * std,
                "wv": norm(next(k), full + (D, KV * hd)) * std,
                "q_norm": jnp.zeros(full + (hd,)),
                "k_norm": jnp.zeros(full + (hd,)),
                "wo": norm(next(k), full + (H * hd, D)) * res_std,
                "mlp_norm": jnp.zeros(full + (D,)),
                "moe": moe(next(k), full),
            },
        },
        "final_norm": jnp.zeros((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }


def logical_specs(config: Qwen3NextConfig) -> dict:
    lead = lambda spec: P(None, None, *spec)
    moe = jax.tree.map(lead, moe_logical_specs(config.moe),
                       is_leaf=lambda s: isinstance(s, P))
    return {
        "wte": P("model", None),
        "blocks": {
            LINEAR: {
                "attn_norm": P(), "w_qkvz": P(), "w_ba": P(), "conv_w": P(),
                "A_log": P(), "dt_bias": P(), "o_norm": P(), "w_out": P(),
                "mlp_norm": P(), "moe": moe,
            },
            FULL: {
                "attn_norm": P(),
                "wq": P(None, None, None, "model"),
                "wk": P(None, None, None, "model"),
                "wv": P(None, None, None, "model"),
                "q_norm": P(), "k_norm": P(),
                "wo": P(None, None, "model", None),
                "mlp_norm": P(), "moe": moe,
            },
        },
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }


def _norm(x, w, eps):
    """Zero-centred RMSNorm: the stored weight is the scale less one."""
    return _rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _partial_rope(x, config: Qwen3NextConfig):
    """Rotate-half rotary on the first ``rotary_ndims`` of each head."""
    rot = config.rotary_ndims
    xr = rope(x[..., :rot], config.rope_theta)
    return jnp.concatenate([xr, x[..., rot:]], axis=-1)


def _moe_finish(x, layer, config: Qwen3NextConfig, train, rng):
    return expert_half(
        x, layer["moe"], config.moe,
        lambda x: _norm(x, layer["mlp_norm"], config.rms_norm_eps),
        train, rng)


def _linear_mixer(x, layer, config: Qwen3NextConfig, segment_ids):
    B, S, D = x.shape
    Hk, Hv, dk, dv = _widths(config)
    conv_ch = 2 * Hk * dk + Hv * dv
    with jax.named_scope(SCOPE_IN_PROJ):
        h = _norm(x, layer["attn_norm"], config.rms_norm_eps)
        qkvz = qdot(h, layer["w_qkvz"])
        ba = qdot(h, layer["w_ba"]).astype(jnp.float32)
        z = qkvz[..., conv_ch:]
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., Hv:] + layer["dt_bias"].astype(jnp.float32))
    with jax.named_scope(SCOPE_CONV):
        # q, k and v each from the projection itself and as the array the
        # delta rule takes: no slice of [B, S, conv_ch] before or after
        q, k, v = (
            causal_conv(qkvz, layer["conv_w"][:, first:first + width],
                        segment_ids, activation="silu", first_channel=first)
            for first, width in ((0, Hk * dk), (Hk * dk, Hk * dk),
                                 (2 * Hk * dk, Hv * dv)))
    with jax.named_scope(SCOPE_DELTA_RULE):
        q, k = q.reshape(B, S, Hk, dk), k.reshape(B, S, Hk, dk)
        v = v.reshape(B, S, Hv, dv)
        o = gated_delta_rule(q, k, v, g, beta, segment_ids,
                             chunk=config.delta_rule_chunk,
                             l2norm_scales=(dk ** -0.5, 1.0))
    o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope(SCOPE_GATE_NORM):
        y = _rms_norm(o, layer["o_norm"], config.rms_norm_eps) \
            * jax.nn.silu(z.reshape(B, S, Hv, dv))
    with jax.named_scope(SCOPE_OUT_PROJ):
        return x + qdot(y.reshape(B, S, Hv * dv), layer["w_out"])


@jax.named_scope(SCOPE_BLOCK)
def _linear_block(x, layer, config: Qwen3NextConfig, train, rng=None,
                  segment_ids=None):
    with jax.named_scope(SCOPE_LINEAR_ATTN):
        x = _linear_mixer(x, layer, config, segment_ids)
    return _moe_finish(x, layer, config, train, rng)


@jax.named_scope(SCOPE_BLOCK)
def _full_block(x, layer, config: Qwen3NextConfig, train, rng=None,
                segment_ids=None):
    B, S, D = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    eps = config.rms_norm_eps
    with jax.named_scope(SCOPE_ATTN):
        h = _norm(x, layer["attn_norm"], eps)
        qg = qdot(h, layer["wq"]).reshape(B, S, H, 2, hd)
        q, gate = qg[..., 0, :], qg[..., 1, :]
        kk = qdot(h, layer["wk"]).reshape(B, S, KV, hd)
        v = qdot(h, layer["wv"]).reshape(B, S, KV, hd)
        q = _partial_rope(_norm(q, layer["q_norm"], eps), config)
        kk = _partial_rope(_norm(kk, layer["k_norm"], eps), config)
        attn = causal_attention(q, kk, v, impl=config.attention_impl,
                                segment_ids=segment_ids)
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    with jax.named_scope(SCOPE_ATTN):
        gated = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            attn.dtype)
        x = x + qdot(gated.reshape(B, S, H * hd), layer["wo"])
    return _moe_finish(x, layer, config, train, rng)


def head_with_aux(params, batch, config: Qwen3NextConfig,
                  train: bool = True, rng=None):
    """-> (the head's inputs, router loss summed over layers, routed rows
    over ``held_rows_bound`` summed over layers: int32, 0 unless the
    experts held are a subset)."""
    refuse_param_stream(
        "qwen3-next", "two stacks (linear, full) walked period by period")
    dtype = jnp.dtype(config.dtype)
    x = embed_tokens(params["wte"], batch["input_ids"], dtype)
    x, (aux, over) = scan_layer_kinds(
        x, params["blocks"], config.pattern,
        {kind: layer_block(block, config, train=train, rng=rng,
                           segment_ids=segment_ids_of(batch))
         for kind, block in ((LINEAR, _linear_block), (FULL, _full_block))})
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _norm(x, params["final_norm"], config.rms_norm_eps)
    return Head(x, params["lm_head"]), aux, over


def _wq_halves(grads, config: Qwen3NextConfig):
    wq = grads["blocks"][FULL]["wq"]
    wq = wq.reshape(wq.shape[:-1] + (config.num_heads, 2, config.head_dim))
    return wq[..., 0, :], wq[..., 1, :]


def count_params(config: Qwen3NextConfig) -> int:
    return param_count(partial(init_params, config))


def qwen3_next_model(size: str = "80b-a3b", **overrides) -> Model:
    config = Qwen3NextConfig(**{
        **resolve_size(QWEN3_NEXT_SIZES, size, "qwen3_next"), **overrides})
    return held_share_model(
        "qwen3-next", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        expert_layers=config.num_layers, expert_matrices=3,
        serving_needs=(
            "serving a model with linear-attention layers needs a cache "
            "that holds each sequence's recurrent state (and convolution "
            "history) beside the full layers' keys and values"),
        # parts of a leaf worth a row of their own in a gradient table
        # (scripts/olmoe_grad_check.py)
        meta={"gradient_views": {
            "full.wq[query half]": lambda g: _wq_halves(g, config)[0],
            "full.wq[gate half]": lambda g: _wq_halves(g, config)[1]}})
