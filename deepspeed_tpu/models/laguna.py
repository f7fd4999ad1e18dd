"""Laguna (huggingface.co/poolside/Laguna-S-2.1, ``model_type: laguna``): a
decoder whose attention layers are of **two kinds with different head
counts in one stack** — *sliding* layers (a causal window of
``sliding_window`` keys, ``num_heads_sliding`` query heads, plain rotary on
the whole head) and *full* layers (every earlier key, ``num_heads_full``
query heads, YaRN-scaled rotary on part of the head) — with a **per-head
sigmoid gate** on the attention's output, one leading dense layer, then
softmax-routed SwiGLU experts beside a shared one.  RMSNorm everywhere, no
bias anywhere, grouped-query attention (``num_kv_heads`` in both kinds),
untied head.

For layer ``l`` (``l % full_attention_interval == 0``: full, else sliding)
with ``H`` its kind's query heads, ``hd = head_dim``:

- ``h = N(x)``; ``q = h W_q`` [S, H, hd]; ``k = h W_k``, ``v = h W_v`` [S,
  KV, hd]; ``g = sigmoid(h W_g)`` [S, H] (float32): one gate a head.
- Rotary, rotate-half (dim i with i + rot/2), by the position along the
  sequence.  *Sliding*: ``sliding_rope_theta`` on all ``hd`` dimensions.
  *Full*: on the first ``rot = hd * partial_rotary_factor``, base
  ``rope_theta``, YaRN (arXiv:2309.00071): the inverse frequencies
  ``theta^(-2i/rot)`` and those over ``rope_factor`` blended by a linear
  ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
  times in ``original_max_position_embeddings`` positions, and cos and sin
  times ``attention_factor`` (:func:`yarn_inv_freq`).
- ``a = softmax(q k^T / sqrt(hd) + mask) v``: j <= i, one document, and in
  a sliding layer ``i - j < sliding_window`` — the flash kernels' window
  (ops/pallas/ds_flash_attention.py: tiles outside it are skipped).  Query
  head n reads KV head ``n // (H / KV)``.
- ``x <- x + (g * a, head by head) W_o``; ``h' = N(x)``.
- Layer 0: ``x <- x + W_down(silu(W_gate h') * W_up h')`` at ``d_ff_dense``.
  Others (moe/layer.py): ``p = softmax(h' W_r)`` in float32, the ``top_k``
  largest, weights ``p / sum(chosen p) * routed_scaling_factor`` on the
  experts' outputs, SwiGLU experts at ``d_ff`` and one shared SwiGLU expert
  added as it is.  ``experts_held`` (with ``expert_offset``) makes this
  chip's share of an expert-parallel layer.

The layer loop is the leading block once, then ``scan_layer_kinds`` over
the periods (``full_attention_interval - 1`` sliding layers, then a full
one), then one scan over the sliding layers left over (the published 48:
the lead, 11 periods, 3).  Not built: serving (a cache that keeps
``sliding_window`` positions for the sliding layers and all for the full
ones — the entry points raise); ZeRO-3 and parameter streaming.
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_half, held_share_model,
                                        layer_block, param_count, qdot,
                                        refuse_param_stream, resolve_size,
                                        scan_layer_kinds, segment_ids_of)
from deepspeed_tpu.models.llama import _rms_norm
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_ATTN_FULL, SCOPE_ATTN_SLIDING, SCOPE_BLOCK,
    SCOPE_HEAD_GATE, SCOPE_HEAD_LOSS, SCOPE_LEAD_MLP, SCOPE_MLP,
    SCOPE_OUT_PROJ, SCOPE_ROPE, SCOPE_SCORES)

FULL, SLIDING = "full", "sliding"


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    max_seq_len: int = 1048576
    #: layer 0 (full attention, dense feed-forward), then expert layers:
    #: layer l is a full one where ``l % full_attention_interval == 0``
    num_layers: int = 48
    full_attention_interval: int = 4
    d_model: int = 3072
    #: query heads of a full and of a sliding layer (``num_attention_heads``
    #: and the other value of ``num_attention_heads_per_layer``)
    num_heads_full: int = 48
    num_heads_sliding: int = 72
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    #: ``rope_parameters.sliding_attention``: plain rotary, the whole head
    sliding_rope_theta: float = 10000.0
    #: ``rope_parameters.full_attention``: YaRN on part of the head
    rope_theta: float = 500000.0
    partial_rotary_factor: float = 0.5
    rope_factor: float = 128.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.4852030263919618
    #: the leading dense layer's width (``intermediate_size``)
    d_ff_dense: int = 12288
    #: an expert's width (``moe_intermediate_size``)
    d_ff: int = 1024
    num_experts: int = 256
    top_k: int = 10
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    held_rows_factor: int = 2
    shared_expert_d_ff: int = 1024
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.num_layers < 2 or self.full_attention_interval < 2:
            raise ValueError(
                f"laguna: the stack is one leading dense layer and then "
                f"expert layers, sliding ones between full ones (num_layers "
                f">= 2, full_attention_interval >= 2), not "
                f"{self.num_layers} and {self.full_attention_interval}")
        for heads in (self.num_heads_full, self.num_heads_sliding):
            if heads % self.num_kv_heads:
                raise ValueError(
                    f"laguna: {heads} query heads are not whole groups of "
                    f"{self.num_kv_heads} KV heads")

    @property
    def rotary_ndims(self) -> int:
        """Of a full layer's head; a sliding layer rotates all of it."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def expert_layers(self) -> int:
        return self.num_layers - 1

    @property
    def num_periods(self) -> int:
        return self.expert_layers // self.full_attention_interval

    @property
    def tail_layers(self) -> int:
        """Sliding layers after the last whole period."""
        return self.expert_layers % self.full_attention_interval

    @property
    def pattern(self) -> tuple:
        """One period's kinds, in order."""
        return (SLIDING,) * (self.full_attention_interval - 1) + (FULL,)

    def heads(self, kind) -> int:
        return self.num_heads_full if kind == FULL else self.num_heads_sliding

    @property
    def moe(self) -> MoEConfig:
        # a held share runs through the grouped dispatch only
        return MoEConfig.of(self, router="softmax", activation="silu_glu",
                            dispatch_mode="grouped")


LAGUNA_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=5, d_model=32,
                 num_heads_full=4, num_heads_sliding=6, num_kv_heads=2,
                 head_dim=16, sliding_window=8,
                 original_max_position_embeddings=16, d_ff_dense=64,
                 d_ff=16, num_experts=8, top_k=2, shared_expert_d_ff=16),
    # huggingface.co/poolside/Laguna-S-2.1 config.json: the defaults above.
    # 118B parameters whole; one chip trains the first five layers with 8
    # of each layer's 256 experts held (benchmarks/configs)
    "s-2.1": dict(),
}


def yarn_inv_freq(config: LagunaConfig) -> np.ndarray:
    """A full layer's ``rotary_ndims / 2`` inverse frequencies (float64):
    ``theta^(-2i/rot)`` where dimension i turns more than ``beta_fast``
    times in the original context, that over ``rope_factor`` where it
    turns fewer than ``beta_slow`` times, and a linear ramp in i between
    the two (the correction dimensions, floor and ceiling, inside [0, rot
    - 1])."""
    rot, base = config.rotary_ndims, config.rope_theta
    extrapolated = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def correction_dim(turns):
        return rot * math.log(config.original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(config.beta_fast)), 0)
    high = min(math.ceil(correction_dim(config.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return extrapolated / config.rope_factor * ramp \
        + extrapolated * (1.0 - ramp)


def rotary_table(config: LagunaConfig, kind):
    """(inverse frequencies [rot / 2], what cos and sin are multiplied by)
    of a layer kind."""
    if kind == FULL:
        return yarn_inv_freq(config), config.attention_factor
    hd = config.head_dim
    return config.sliding_rope_theta ** (
        -np.arange(0, hd, 2, dtype=np.float64) / hd), 1.0


def _rotary(x, inv_freq, scale):
    """Rotate-half rotary on the first ``2 * len(inv_freq)`` dimensions of
    each head of x [B, S, H, hd], cos and sin times ``scale``."""
    rot = 2 * len(inv_freq)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :rot // 2], xf[..., rot // 2:rot]
    out = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if rot < x.shape[-1]:
        out.append(xf[..., rot:])
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


def _attn_params(config: LagunaConfig, kind, key, lead=()):
    D, KV, hd = config.d_model, config.num_kv_heads, config.head_dim
    H = config.heads(kind)
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(key, 5))
    std = 0.02
    return {
        "attn_norm": jnp.ones(lead + (D,)),
        "wq": norm(next(k), lead + (D, H * hd)) * std,
        "wk": norm(next(k), lead + (D, KV * hd)) * std,
        "wv": norm(next(k), lead + (D, KV * hd)) * std,
        "wg": norm(next(k), lead + (D, H)) * std,
        "wo": norm(next(k), lead + (H * hd, D)) * std,
    }


def _expert_block_params(config: LagunaConfig, kind, key, lead):
    """Expert layers of one kind stacked ``lead + (...)``."""
    k_attn, k_moe = jax.random.split(key)
    n = int(np.prod(lead))
    moe = jax.vmap(partial(init_moe_params, config.moe))(
        jax.random.split(k_moe, n))
    moe = jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), moe)
    return {**_attn_params(config, kind, k_attn, lead),
            "mlp_norm": jnp.ones(lead + (config.d_model,)), "moe": moe}


def init_params(config: LagunaConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02, norm weights 1."""
    D, V, F = config.d_model, config.vocab_size, config.d_ff_dense
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 10))
    n_p, n_slide = config.num_periods, config.full_attention_interval - 1
    params = {
        "wte": norm(next(k), (V, D)) * std,
        "lead": {**_attn_params(config, FULL, next(k)),
                 "mlp_norm": jnp.ones((D,)),
                 "w_gate": norm(next(k), (D, F)) * std,
                 "w_up": norm(next(k), (D, F)) * std,
                 "w_down": norm(next(k), (F, D)) * std},
        "blocks": {
            SLIDING: _expert_block_params(config, SLIDING, next(k),
                                          (n_p, n_slide)),
            FULL: _expert_block_params(config, FULL, next(k), (n_p, 1)),
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }
    if config.tail_layers:
        params["tail"] = _expert_block_params(
            config, SLIDING, next(k), (config.tail_layers,))
    return params


def logical_specs(config: LagunaConfig) -> dict:
    def attn(lead):
        col, row = P(*lead, None, "model"), P(*lead, "model", None)
        return {"attn_norm": P(), "wq": col, "wk": col, "wv": col,
                "wg": col, "wo": row}

    def expert_block(lead):
        moe = jax.tree.map(lambda spec: P(*lead, *spec),
                           moe_logical_specs(config.moe),
                           is_leaf=lambda s: isinstance(s, P))
        return {**attn(lead), "mlp_norm": P(), "moe": moe}

    specs = {
        "wte": P("model", None),
        "lead": {**attn(()), "mlp_norm": P(), "w_gate": P(None, "model"),
                 "w_up": P(None, "model"), "w_down": P("model", None)},
        "blocks": {SLIDING: expert_block((None, None)),
                   FULL: expert_block((None, None))},
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }
    if config.tail_layers:
        specs["tail"] = expert_block((None,))
    return specs


def _gate_heads(attn, gate):
    """attn [B, S, H, hd] times gate [B, S, H]: one value a head."""
    return attn * gate[..., None].astype(attn.dtype)


def _attention(x, layer, config: LagunaConfig, kind, segment_ids):
    """``x + (g * A(N(x))) W_o`` of one kind; the caller's scope is
    ``ds.block``, and this layer's is ``ds.attn_full`` or
    ``ds.attn_sliding`` around ``attn``."""
    B, S, _ = x.shape
    H, KV, hd = config.heads(kind), config.num_kv_heads, config.head_dim
    inv_freq, scale = rotary_table(config, kind)
    window = config.sliding_window if kind == SLIDING else None
    with jax.named_scope(SCOPE_ATTN_FULL if kind == FULL
                         else SCOPE_ATTN_SLIDING), \
            jax.named_scope(SCOPE_ATTN):
        h = _rms_norm(x, layer["attn_norm"], config.norm_eps)
        q = qdot(h, layer["wq"]).reshape(B, S, H, hd)
        k = qdot(h, layer["wk"]).reshape(B, S, KV, hd)
        v = qdot(h, layer["wv"]).reshape(B, S, KV, hd)
        with jax.named_scope(SCOPE_ROPE):
            q = _rotary(q, inv_freq, scale)
            k = _rotary(k, inv_freq, scale)
        with jax.named_scope(SCOPE_SCORES):
            attn = causal_attention(q, k, v, impl=config.attention_impl,
                                    segment_ids=segment_ids, window=window)
        attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
        with jax.named_scope(SCOPE_HEAD_GATE):
            gate = jax.nn.sigmoid(qdot(h, layer["wg"]).astype(jnp.float32))
            attn = _gate_heads(attn, gate)
        with jax.named_scope(SCOPE_OUT_PROJ):
            return x + qdot(attn.reshape(B, S, H * hd), layer["wo"])


@jax.named_scope(SCOPE_BLOCK)
def _lead_block(x, layer, config: LagunaConfig, segment_ids=None):
    x = _attention(x, layer, config, FULL, segment_ids)
    with jax.named_scope(SCOPE_LEAD_MLP), jax.named_scope(SCOPE_MLP):
        h = _rms_norm(x, layer["mlp_norm"], config.norm_eps)
        h = jax.nn.silu(qdot(h, layer["w_gate"])) * qdot(h, layer["w_up"])
        return x + qdot(h, layer["w_down"])


@jax.named_scope(SCOPE_BLOCK)
def _expert_block(x, layer, config: LagunaConfig, kind, train, rng=None,
                  segment_ids=None):
    """-> (x, (router loss, routed rows over ``held_rows_bound``))."""
    x = _attention(x, layer, config, kind, segment_ids)
    return expert_half(
        x, layer["moe"], config.moe,
        lambda x: _rms_norm(x, layer["mlp_norm"], config.norm_eps),
        train, rng)


def hidden_with_aux(params, batch, config: LagunaConfig, train: bool = True,
                    rng=None):
    """-> (the last layer's output [B, S, D], before the final norm; router
    loss summed over the expert layers; routed rows over
    ``held_rows_bound`` summed over them, int32)."""
    refuse_param_stream(
        "laguna", "a leading dense block and two stacks (sliding, full) "
        "walked period by period")
    seg = segment_ids_of(batch)
    x = embed_tokens(params["wte"], batch["input_ids"],
                     jnp.dtype(config.dtype))
    x = layer_block(_lead_block, config, segment_ids=seg)(x, params["lead"])
    block_fns = {kind: layer_block(_expert_block, config, kind=kind,
                                   train=train, rng=rng, segment_ids=seg)
                 for kind in (SLIDING, FULL)}
    aux = over = 0
    if config.num_periods:
        x, (aux, over) = scan_layer_kinds(x, params["blocks"],
                                          config.pattern, block_fns)
    if config.tail_layers:
        x, (tail_aux, tail_over) = lax.scan(block_fns[SLIDING], x,
                                            params["tail"])
        aux, over = aux + jnp.sum(tail_aux), over + jnp.sum(tail_over, 0)
    return x, aux, over


def head_with_aux(params, batch, config: LagunaConfig, train: bool = True,
                  rng=None):
    """-> (the head's inputs, router loss, rows over the bound)."""
    x, aux, over = hidden_with_aux(params, batch, config, train, rng)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        return (Head(_rms_norm(x, params["final_norm"], config.norm_eps),
                     params["lm_head"]), aux, over)


def layers_in_order(params, config: LagunaConfig):
    """[(kind, that layer's parameters)] of the expert layers, 1.. in the
    stack's order — for diagnostics that write the layers out."""
    take = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)
    layers = []
    for p in range(config.num_periods):
        taken = dict.fromkeys((SLIDING, FULL), 0)
        for kind in config.pattern:
            layers.append((kind, take(params["blocks"][kind], p,
                                      taken[kind])))
            taken[kind] += 1
    for i in range(config.tail_layers):
        layers.append((SLIDING, take(params["tail"], i)))
    return layers


def routed_rows(params, batch, config: LagunaConfig):
    """[expert layers, num_experts] int32: the (token, choice) pairs each
    layer's router sends to each of ALL experts for this micro-batch — what
    ``held_rows_bound`` has to hold a share's sum of
    (scripts/held_rows_table.py).  A diagnostic: the layers written out,
    no scan."""
    from deepspeed_tpu.moe.layer import _route, _routing_logits
    seg = segment_ids_of(batch)
    moe = config.moe
    x = params["wte"].astype(jnp.dtype(config.dtype))[batch["input_ids"]]
    x = _lead_block(x, params["lead"], config, segment_ids=seg)
    rows = []
    for kind, layer in layers_in_order(params, config):
        attended = _attention(x, layer, config, kind, seg)
        h = _rms_norm(attended, layer["mlp_norm"], config.norm_eps)
        logits = _routing_logits(layer["moe"],
                                 h.reshape(-1, config.d_model), moe)
        chosen = _route(layer["moe"], logits, moe, True, None).expert_idx
        rows.append(jnp.bincount(chosen.reshape(-1),
                                 length=config.num_experts))
        x, _ = _expert_block(x, layer, config, kind, train=True,
                             segment_ids=seg)
    return jnp.stack(rows)


def count_params(config: LagunaConfig) -> int:
    return param_count(partial(init_params, config))


def laguna_model(size: str = "s-2.1", **overrides) -> Model:
    config = LagunaConfig(**{
        **resolve_size(LAGUNA_SIZES, size, "laguna"), **overrides})
    return held_share_model(
        "laguna", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        expert_layers=config.expert_layers, expert_matrices=3,
        lookup_params=config.vocab_size * config.d_model,
        serving_needs=(
            "serving needs a cache that keeps sliding_window positions for "
            "the sliding layers and every position for the full ones, at "
            "two head counts"),
        # every expert's routed rows, layer by layer
        meta={"routed_rows": lambda p, b: routed_rows(p, b, config)})
