"""Granite 4.0-H (huggingface.co/ibm-granite/granite-4.0-h-small,
``model_type: granitemoehybrid``, ibm-granite 2025-10, "32B-A9B"; the
state-space layer: Dao & Gu 2024, arXiv:2405.21060): a decoder whose every
layer is TWO sublayers — a mixer, Mamba-2 or attention by ``layer_types``
(attention at layers 5, 15, 25, 35 of 40: a period of ten), then a mixture
of small SwiGLU experts beside one shared expert — under four muP scalars.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``; no bias but the
convolution's; the head is the embedding table (tied).

    x_0 = embedding_multiplier * E[ids]
    x <- x + residual_multiplier * Mixer_l(N(x))
    x <- x + residual_multiplier * (MoE(h) + Shared(h)),   h = N(x)
    logits = N(x_L) E^T / logits_scaling

**Mamba-2 mixer** (``mamba``): models/nemotron_h.py ``ssm_branch``'s
equations at ``n_groups`` 1 — ``[z | x B C | dt] = h W_in``; ``xBC <-
silu(conv(xBC) + b)``; ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
``H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t``, ``y_t = H_t C_t + D x_t``;
``y <- N(y * silu(z); w_g)`` over the group; ``W_out``.  State and
convolution history are zero at a document's first token; the step is not
clamped.

**Attention mixer** (``attention``): grouped-query causal softmax attention
inside a document, no bias, no q/k norm, **no positions**
(``position_embedding_type: nope``), the scores scaled by
``attention_multiplier`` and not by ``1 / sqrt(head_dim)``: ``q`` is
multiplied by ``attention_multiplier * sqrt(head_dim)`` in float32 before
its one rounding, beside the flash call's own ``1 / sqrt(head_dim)``.

**Experts** (moe/layer.py): ``logits = h W_r`` in float32 over all
``num_experts``; the ``top_k`` largest; their weights a softmax over those
``top_k`` (``router="softmax"`` with ``norm_topk_prob``: ``p_i /
sum_chosen p_j`` is that softmax); an expert is ``W_down(silu(W_gate h) *
W_up h)`` at ``d_ff``; the shared expert the same form at
``shared_expert_d_ff``, added un-gated.

**A chip's share of a layer.**  ``experts_held`` / ``expert_offset`` as
every held-share family.  ``mamba_heads_held``, ``attn_heads_held`` and
``kv_heads_held`` (None = all) decide the widths that are BUILT, a
tensor-parallel share by heads: ``W_in``'s ``z``, ``x`` and ``dt``
columns, the convolution's ``x`` channels, ``A_log``, ``dt_bias``, ``D``,
the gated norm's weight and ``W_out``'s rows for the Mamba-2 heads held;
``W_q``, ``W_k``, ``W_v`` columns and ``W_o`` rows for the query heads held
and the key/value heads that serve them.  ``B``, ``C`` and their
convolution channels (one group serves every head), the router, the shared
expert, the norms and the table are whole.  ``head_share`` says which share
it is (:func:`take_share` cuts that share out of an uncut tree; no equation
reads it).  One place couples the heads: the gated norm's mean square runs
over the group's channels, and a share's runs over the channels it holds —
a tensor-parallel deployment all-reduces that one scalar a token, and
nothing here stands in for the exchange.

The layers of a kind are stacked on their own, mixer and expert sublayer
together: ``blocks = {"ssm": [P, n_M, ...], "attn": [P, n_A, ...]}`` over
``P`` repeats of the period; ``models/model.py scan_layer_kinds`` runs the
loop.  Under ``remat`` a layer's mixer and its expert sublayer are
rematerialised apart.

Not built: serving (a cache that holds recurrent state beside keys and
values — the entry points raise); ZeRO-3 and parameter streaming; the
all-reduce of the gated norm's statistic and of the branches' partial sums
over a ``model`` mesh axis.
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import _rms_norm
from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_branch,
                                        held_share_model, maybe_stream,
                                        param_count, refuse_param_stream,
                                        remat_policy, resolve_size,
                                        scan_layer_kinds, segment_ids_of)
from deepspeed_tpu.models.nemotron_h import attention_branch, ssm_branch
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs)
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_EMBED, SCOPE_HEAD_LOSS, SCOPE_MLP,
    SCOPE_OUT_PROJ, SCOPE_SSM)

SSM, ATTN = "ssm", "attn"
#: ``layer_types``' words
KINDS = {"mamba": SSM, "attention": ATTN}
#: the published order: attention at layers 5, 15, 25, 35
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 131072
    num_layers: int = 40
    #: the kind of each layer's mixer; a depth cut keeps the first
    #: ``num_layers`` of them
    layer_types: tuple = LAYER_TYPES
    d_model: int = 4096
    # attention
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 1
    ssm_state_size: int = 128
    conv_kernel: int = 4
    #: the scan's blocking, not a width (ops/state_space.py; the source's
    #: ``mamba_chunk_size`` is 256, which the Mosaic kernels do not take)
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    #: the heads this chip builds (None = all) and which share they are
    mamba_heads_held: "int | None" = None
    attn_heads_held: "int | None" = None
    kv_heads_held: "int | None" = None
    head_share: int = 0
    # experts
    d_ff: int = 768
    num_experts: int = 72
    top_k: int = 10
    norm_topk_prob: bool = True
    expert_offset: int = 0
    experts_held: "int | None" = None
    held_rows_factor: int = 2
    shared_expert_d_ff: int = 1536
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    # muP
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(KINDS)
        if unknown or len(self.layer_types) < self.num_layers:
            raise ValueError(
                f"granite-hybrid: layer_types names {len(self.layer_types)} "
                f"layers for {self.num_layers}, kinds {sorted(unknown)} "
                f"unknown (known: {sorted(KINDS)})")
        H, KV = self.attn_heads, self.kv_heads
        if H % KV or H * self.num_kv_heads != KV * self.num_heads:
            raise ValueError(
                f"granite-hybrid: {H} query heads over {KV} key/value heads "
                f"held, of {self.num_heads} over {self.num_kv_heads}: a "
                f"share holds the query heads of its key/value heads")
        if self.mamba_heads % self.n_groups or (
                self.n_groups > 1 and self.mamba_heads_held is not None):
            raise ValueError(
                f"granite-hybrid: {self.mamba_heads} Mamba-2 heads held of "
                f"{self.mamba_num_heads} over {self.n_groups} groups: a "
                f"share of the heads is built for one group, whose B and C "
                f"every share computes")

    @property
    def kinds(self) -> tuple:
        return tuple(KINDS[t] for t in self.layer_types[:self.num_layers])

    @property
    def layer_kinds(self) -> str:
        """A letter a layer: ``M`` Mamba-2, ``A`` attention."""
        return "".join("M" if kind == SSM else "A" for kind in self.kinds)

    @property
    def pattern(self) -> tuple:
        """The kinds of one period's layers: the shortest run the built
        layers repeat."""
        kinds = self.kinds
        return next(kinds[:p] for p in range(1, len(kinds) + 1)
                    if len(kinds) % p == 0 and kinds == kinds[:p]
                    * (len(kinds) // p))

    @property
    def mamba_heads(self) -> int:
        """Mamba-2 heads built here."""
        return self.mamba_num_heads if self.mamba_heads_held is None \
            else self.mamba_heads_held

    @property
    def attn_heads(self) -> int:
        return self.num_heads if self.attn_heads_held is None \
            else self.attn_heads_held

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads if self.kv_heads_held is None \
            else self.kv_heads_held

    @property
    def d_inner(self) -> int:
        """Channels of the Mamba-2 heads built here."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def moe(self) -> MoEConfig:
        # a held share runs through the grouped dispatch only
        return MoEConfig.of(self, router="softmax", activation="silu_glu",
                            dispatch_mode="grouped")


GRANITE_HYBRID_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=4,
                 layer_types=("mamba", "attention", "mamba", "attention"),
                 d_model=32, num_heads=4, num_kv_heads=2, head_dim=16,
                 mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
                 chunk_size=16, d_ff=16, num_experts=8, top_k=2,
                 shared_expert_d_ff=32),
    # huggingface.co/ibm-granite/granite-4.0-h-small config.json: the
    # defaults above.  32.2B parameters whole; one chip trains the first
    # period of ten layers at an eighth of the heads, the experts and the
    # vocabulary (benchmarks/configs)
    "4.0-h-small": dict(),
}


# ------------------------------------------------------------- parameters
def init_params(config: GraniteHybridConfig, rng) -> dict:
    """Seeded.  Assumed (the published config has no ``initializer_range``):
    every matrix normal of std 0.02; norm weights 1; ``A_log = log U(1,
    16)``, ``dt = exp U(log time_step_min, log time_step_max)`` floored at
    ``time_step_floor`` with ``dt_bias`` its inverse softplus, ``D = 1``,
    the convolution's taps normal 0.02 and its bias 0 (models/nemotron_h.py's
    draws)."""
    D, V = config.d_model, config.vocab_size
    H, KV, hd = config.attn_heads, config.kv_heads, config.head_dim
    Hm, K = config.mamba_heads, config.conv_kernel
    d_in, conv_ch = config.d_inner, config.conv_channels
    n_p = config.num_layers // len(config.pattern)
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 16))

    def experts(key, lead):
        keys = jax.random.split(key, lead[0] * lead[1])
        stacked = jax.vmap(partial(init_moe_params, config.moe))(keys)
        return {"mlp_norm": jnp.ones(lead + (D,)),
                "moe": jax.tree.map(
                    lambda a: a.reshape(lead + a.shape[1:]), stacked)}

    ssm, att = ((n_p, config.pattern.count(kind)) for kind in (SSM, ATTN))
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
            "w_out": norm(next(k), ssm + (d_in, D)) * std,
            **experts(next(k), ssm),
        },
        ATTN: {
            "norm": jnp.ones(att + (D,)),
            "wq": norm(next(k), att + (D, H * hd)) * std,
            "wk": norm(next(k), att + (D, KV * hd)) * std,
            "wv": norm(next(k), att + (D, KV * hd)) * std,
            "wo": norm(next(k), att + (H * hd, D)) * std,
            **experts(next(k), att),
        },
    }
    return {
        "wte": norm(next(k), (V, D)) * std,
        # a kind the pattern lacks has no stack (and no block to read it)
        "blocks": {kind: tree for kind, tree in blocks.items()
                   if kind in config.pattern},
        "final_norm": jnp.ones((D,)),
    }


def logical_specs(config: GraniteHybridConfig) -> dict:
    """The experts over ``expert``, the table over ``model`` by vocabulary;
    the mixers replicated: their share by heads is BUILT
    (``mamba_heads_held`` ...), and the all-reduce a ``model`` axis would
    need after ``W_out``, ``W_o`` and inside the gated norm is not."""
    lead = lambda spec: P(None, None, *spec)
    experts = {"mlp_norm": P(), "moe": jax.tree.map(
        lead, moe_logical_specs(config.moe),
        is_leaf=lambda s: isinstance(s, P))}
    blocks = {
        SSM: {**dict.fromkeys(("norm", "w_in", "conv_w", "conv_b", "A_log",
                               "dt_bias", "D", "gate_norm", "w_out"), P()),
              **experts},
        ATTN: {**dict.fromkeys(("norm", "wq", "wk", "wv", "wo"), P()),
               **experts},
    }
    return {
        "wte": P("model", None),
        "blocks": {kind: tree for kind, tree in blocks.items()
                   if kind in config.pattern},
        "final_norm": P(),
    }


def take_share(params, whole: GraniteHybridConfig,
               share: GraniteHybridConfig) -> dict:
    """The parameter tree ``share`` builds, cut out of the uncut model's
    (``whole``: every head, every expert): share ``head_share`` of the
    Mamba-2, query and key/value heads, experts ``expert_offset`` on; what
    every chip holds alike (B and C's columns, the router, the shared
    expert, the norms) whole.  The table is left whole too: a vocabulary
    slice is another vocabulary, not a cut of this one."""
    r, Pd, hd = share.head_share, whole.mamba_head_dim, whole.head_dim
    d_all, d_in = whole.d_inner, share.d_inner
    bc = 2 * whole.n_groups * whole.ssm_state_size
    Hm, H, KV = share.mamba_heads, share.attn_heads, share.kv_heads
    at = lambda first, n: jnp.arange(first, first + n)
    x_cols = at(r * d_in, d_in)
    conv_cols = jnp.concatenate([x_cols, at(d_all, bc)])
    in_cols = jnp.concatenate([
        x_cols, d_all + conv_cols, at(2 * d_all + bc + r * Hm, Hm)])
    heads = at(r * Hm, Hm)
    q_cols, kv_cols = at(r * H * hd, H * hd), at(r * KV * hd, KV * hd)
    e = at(share.expert_offset, share.moe.held)
    mixer_cuts = {
        "w_in": lambda w: w[..., in_cols],
        "conv_w": lambda w: w[..., conv_cols],
        "conv_b": lambda w: w[..., conv_cols],
        "A_log": lambda w: w[..., heads], "dt_bias": lambda w: w[..., heads],
        "D": lambda w: w[..., heads],
        "gate_norm": lambda w: w[..., x_cols],
        "w_out": lambda w: w[..., x_cols, :],
        "wq": lambda w: w[..., q_cols], "wk": lambda w: w[..., kv_cols],
        "wv": lambda w: w[..., kv_cols], "wo": lambda w: w[..., q_cols, :],
    }

    def cut(path, w):
        name = path[-1].key
        if any(getattr(p, "key", None) == "moe" for p in path):
            # [periods, layers, experts, ...]; router and shared expert whole
            return w[:, :, e] if name in ("w_gate", "w_in", "w_out") else w
        return mixer_cuts.get(name, lambda w: w)(w)

    return jax.tree_util.tree_map_with_path(cut, params)


# ------------------------------------------------------------------ blocks
def _scaled(x, out, config: GraniteHybridConfig):
    """``x + residual_multiplier * out``, the product in float32 and
    rounded once."""
    return x + (out.astype(jnp.float32)
                * config.residual_multiplier).astype(x.dtype)


@jax.named_scope(SCOPE_BLOCK)
def _mixed(x, layer, config: GraniteHybridConfig, kind, segment_ids):
    """``x + residual_multiplier * Mixer(N(x))`` of a layer of ``kind``."""
    layer = maybe_stream(layer)
    if kind == SSM:
        with jax.named_scope(SCOPE_SSM):
            out = ssm_branch(x, layer, config, segment_ids,
                             heads=config.mamba_heads)
            with jax.named_scope(SCOPE_OUT_PROJ):
                return _scaled(x, out, config)
    out = attention_branch(
        x, layer, config, segment_ids,
        heads=(config.attn_heads, config.kv_heads),
        q_scale=config.attention_multiplier * math.sqrt(config.head_dim))
    with jax.named_scope(SCOPE_ATTN):
        return _scaled(x, out, config)


@jax.named_scope(SCOPE_BLOCK)
def _fed(x, layer, config: GraniteHybridConfig, train, rng):
    """``x + residual_multiplier * (MoE(h) + Shared(h))`` -> (x, (router
    loss, routed rows over ``held_rows_bound``))."""
    layer = maybe_stream(layer)
    out, sums = expert_branch(
        x, layer["moe"], config.moe,
        lambda x: _rms_norm(x, layer["mlp_norm"], config.norm_eps),
        train, rng)
    with jax.named_scope(SCOPE_MLP):
        return _scaled(x, out, config), sums


def _layer_fn(config: GraniteHybridConfig, kind, train, rng, segment_ids):
    """``fn(x, layer) -> (x, sums)`` of one layer.  Under ``remat`` its
    mixer and its expert sublayer are rematerialised apart: a layer keeps
    ``x`` and the mixer's output, and the backward holds one sublayer's
    activations at a time."""
    mix = partial(_mixed, config=config, kind=kind, segment_ids=segment_ids)
    feed = partial(_fed, config=config, train=train, rng=rng)
    if config.remat:
        keep = partial(jax.checkpoint,
                       policy=remat_policy(config.remat_policy))
        mix, feed = keep(mix), keep(feed)
    return lambda x, layer: feed(mix(x, layer), layer)


def embedded(params, batch, config: GraniteHybridConfig):
    """``embedding_multiplier * E[ids]`` in the model's dtype."""
    dtype = jnp.dtype(config.dtype)
    x = embed_tokens(params["wte"], batch["input_ids"], dtype)
    with jax.named_scope(SCOPE_EMBED):
        return (x.astype(jnp.float32)
                * config.embedding_multiplier).astype(dtype)


def head_with_aux(params, batch, config: GraniteHybridConfig,
                  train: bool = True, rng=None):
    """-> (the head's inputs, router loss summed over layers, routed rows
    over ``held_rows_bound`` summed over layers: int32, 0 unless the
    experts held are a subset).  The logits' muP divisor is applied to the
    hidden state, and the head is the embedding table on its own axis."""
    refuse_param_stream(
        "granite-hybrid",
        "two stacks (ssm, attn) walked period by period")
    dtype = jnp.dtype(config.dtype)
    seg = segment_ids_of(batch)
    x, (aux, over) = scan_layer_kinds(
        embedded(params, batch, config), params["blocks"], config.pattern,
        {kind: _layer_fn(config, kind, train, rng, seg)
         for kind in (SSM, ATTN)})
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
        x = (x.astype(jnp.float32) / config.logits_scaling).astype(dtype)
    return Head(x, params["wte"], tied=True), aux, over


def count_params(config: GraniteHybridConfig) -> int:
    return param_count(partial(init_params, config))


def granite_hybrid_model(size: str = "4.0-h-small", **overrides) -> Model:
    config = GraniteHybridConfig(**{
        **resolve_size(GRANITE_HYBRID_SIZES, size, "granite_hybrid"),
        **overrides})
    return held_share_model(
        "granite-hybrid", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        expert_layers=config.num_layers, expert_matrices=3,
        # the table is tied: read once as a lookup and multiplied once as
        # the head, so every parameter but the absent experts' multiplies
        serving_needs=(
            "serving a model with state-space layers needs a cache that "
            "holds each sequence's recurrent state (and convolution "
            "history) beside the attention layers' keys and values"))
