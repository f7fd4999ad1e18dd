"""Mellum 2 (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type: mellum``): a decoder whose attention layers are of **two
kinds at one head count** — *sliding* layers (a causal window of
``sliding_window`` keys, plain rotary) and *full* layers (every earlier
key, YaRN-scaled rotary), both on the whole head, the full one **last** in
its period (``l % full_attention_interval == full_attention_interval -
1``) — and whose every layer is a sparse one: softmax-routed SwiGLU
experts, the chosen gates renormalised, no shared expert, no leading dense
layer.  RMSNorm everywhere, no bias anywhere, grouped-query attention,
untied head.  ``intermediate_size`` of the published file is used by no
layer and is no field here; the row's "MTP head" has no key in the file
and none is built.

For layer ``l`` with ``H = num_heads``, ``hd = head_dim``:

- ``h = N(x)``; ``q = h W_q`` [S, H, hd]; ``k = h W_k``, ``v = h W_v`` [S,
  KV, hd].
- Rotary, rotate-half, by the position along the sequence, on all ``hd``
  dimensions.  *Sliding*: base ``sliding_rope_theta``.  *Full*: base
  ``rope_theta`` under YaRN (``models/laguna.py yarn_inv_freq``: the
  inverse frequencies and those over ``rope_factor`` blended by a ramp
  between the dimensions that turn ``beta_fast`` and ``beta_slow`` times in
  ``original_max_position_embeddings`` positions; cos and sin times
  ``attention_factor``).
- ``a = softmax(q k^T / sqrt(hd) + mask) v``: j <= i, one document, and in
  a sliding layer ``i - j < sliding_window`` (the flash kernels' window).
  Query head n reads KV head ``n // (H / KV)``.
- ``x <- x + a W_o``; ``h' = N(x)``; ``p = softmax(h' W_r)`` in float32,
  the ``top_k`` largest, weights ``p / sum(chosen p)`` on the outputs of
  SwiGLU experts at ``d_ff`` (moe/layer.py).  ``experts_held`` (with
  ``expert_offset``) makes one chip's share of an expert-parallel layer
  without its exchange; on a mesh whose ``expert`` axis is wider than one
  the layer holds all its experts spread over it and exchanges its rows
  (``moe/layer.py _exchanged_grouped_moe``).

The layer loop is ``scan_layer_kinds`` over the periods and one scan over
the sliding layers left over (the published 28: seven periods, none).  Not
built: serving (the entry points raise); ZeRO-3 and parameter streaming.
"""
import itertools
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.laguna import (FULL, SLIDING, _rotary,
                                         rotary_table)
from deepspeed_tpu.models.llama import _rms_norm
# head_nll_sum: the head's loss in chunks of tokens, first built here for
# this family's 98,304 ids (1,024 tokens a chunk) and every family's since
from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_half, head_nll_sum,  # noqa: F401
                                        held_share_model, layer_block,
                                        param_count, qdot,
                                        refuse_param_stream, resolve_size,
                                        scan_layer_kinds, segment_ids_of)
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_ATTN_FULL, SCOPE_ATTN_SLIDING, SCOPE_BLOCK,
    SCOPE_HEAD_LOSS, SCOPE_OUT_PROJ, SCOPE_ROPE, SCOPE_SCORES)


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    max_seq_len: int = 131072
    #: layer l is a full one where ``l % full_attention_interval`` is the
    #: interval's last, a sliding one elsewhere
    num_layers: int = 28
    full_attention_interval: int = 4
    d_model: int = 2304
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    #: ``rope_parameters.sliding_attention``: plain rotary
    sliding_rope_theta: float = 500000.0
    #: ``rope_parameters.full_attention``: YaRN
    rope_theta: float = 500000.0
    rope_factor: float = 16.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782
    #: an expert's width (``moe_intermediate_size``)
    d_ff: int = 896
    num_experts: int = 64
    top_k: int = 8
    norm_topk_prob: bool = True
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    held_rows_factor: int = 2
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.num_layers < 1 or self.full_attention_interval < 2:
            raise ValueError(
                f"mellum: sliding layers before a full one (num_layers >= "
                f"1, full_attention_interval >= 2), not {self.num_layers} "
                f"and {self.full_attention_interval}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"mellum: {self.num_heads} query heads are not whole groups "
                f"of {self.num_kv_heads} KV heads")

    #: both kinds rotate the whole head (what ``laguna.rotary_table`` reads)
    partial_rotary_factor = 1.0

    @property
    def rotary_ndims(self) -> int:
        return self.head_dim

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def tail_layers(self) -> int:
        """Sliding layers after the last whole period."""
        return self.num_layers % self.full_attention_interval

    @property
    def pattern(self) -> tuple:
        """One period's kinds, in order: the full layer last."""
        return (SLIDING,) * (self.full_attention_interval - 1) + (FULL,)

    @property
    def moe(self) -> MoEConfig:
        # a held share, and the exchange, run through the grouped dispatch
        return MoEConfig.of(self, router="softmax", activation="silu_glu",
                            dispatch_mode="grouped")


MELLUM_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=4, d_model=32,
                 num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=8,
                 original_max_position_embeddings=16, d_ff=16,
                 num_experts=8, top_k=2),
    # huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json: the
    # defaults above.  12.15B parameters whole; one four-chip host trains
    # one period with its 64 experts spread four ways (benchmarks/configs)
    "12b-a2.5b": dict(),
}


def _block_params(config: MellumConfig, key, lead):
    """Layers of one kind stacked ``lead + (...)`` (the two kinds have the
    same shapes)."""
    D, H, KV, hd = (config.d_model, config.num_heads, config.num_kv_heads,
                    config.head_dim)
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(key, 5))
    std = 0.02
    n = int(np.prod(lead))
    moe = jax.vmap(partial(init_moe_params, config.moe))(
        jax.random.split(next(k), n))
    return {
        "attn_norm": jnp.ones(lead + (D,)),
        "wq": norm(next(k), lead + (D, H * hd)) * std,
        "wk": norm(next(k), lead + (D, KV * hd)) * std,
        "wv": norm(next(k), lead + (D, KV * hd)) * std,
        "wo": norm(next(k), lead + (H * hd, D)) * std,
        "mlp_norm": jnp.ones(lead + (D,)),
        "moe": jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), moe),
    }


def init_params(config: MellumConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02, norm weights 1."""
    D, V = config.d_model, config.vocab_size
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 5))
    n_p, n_slide = config.num_periods, config.full_attention_interval - 1
    params = {
        "wte": norm(next(k), (V, D)) * std,
        "blocks": {SLIDING: _block_params(config, next(k), (n_p, n_slide)),
                   FULL: _block_params(config, next(k), (n_p, 1))},
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }
    if config.tail_layers:
        params["tail"] = _block_params(config, next(k),
                                       (config.tail_layers,))
    return params


def logical_specs(config: MellumConfig) -> dict:
    def block(lead):
        col, row = P(*lead, None, "model"), P(*lead, "model", None)
        moe = jax.tree.map(lambda spec: P(*lead, *spec),
                           moe_logical_specs(config.moe),
                           is_leaf=lambda s: isinstance(s, P))
        return {"attn_norm": P(), "wq": col, "wk": col, "wv": col,
                "wo": row, "mlp_norm": P(), "moe": moe}

    specs = {
        "wte": P("model", None),
        "blocks": {SLIDING: block((None, None)), FULL: block((None, None))},
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }
    if config.tail_layers:
        specs["tail"] = block((None,))
    return specs


def _attention(x, layer, config: MellumConfig, kind, segment_ids):
    """``x + A(N(x)) W_o`` of one kind; the caller's scope is ``ds.block``,
    and this layer's is ``ds.attn_full`` or ``ds.attn_sliding`` around
    ``attn``."""
    B, S, _ = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
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
        with jax.named_scope(SCOPE_OUT_PROJ):
            return x + qdot(attn.reshape(B, S, H * hd), layer["wo"])


@jax.named_scope(SCOPE_BLOCK)
def _block(x, layer, config: MellumConfig, kind, train, rng=None,
           segment_ids=None):
    """-> (x, (router loss, routed rows over a bound))."""
    x = _attention(x, layer, config, kind, segment_ids)
    return expert_half(
        x, layer["moe"], config.moe,
        lambda x: _rms_norm(x, layer["mlp_norm"], config.norm_eps),
        train, rng)


def hidden_with_aux(params, batch, config: MellumConfig, train: bool = True,
                    rng=None):
    """-> (the last layer's output [B, S, D], before the final norm; router
    loss summed over the layers; routed rows over a bound summed over
    them, int32)."""
    refuse_param_stream(
        "mellum", "two stacks (sliding, full) walked period by period")
    seg = segment_ids_of(batch)
    x = embed_tokens(params["wte"], batch["input_ids"],
                     jnp.dtype(config.dtype))
    block_fns = {kind: layer_block(_block, config, kind=kind, train=train,
                                   rng=rng, segment_ids=seg)
                 for kind in (SLIDING, FULL)}
    def run_of(kind):
        # a period's layers of one kind as a loop of their own, not written
        # out: one layer's buffers live at a time (written out, the
        # scheduler spreads a period's four exchanges over each other and
        # the step no longer fits the chip)
        def fn(x, layers):
            x, (aux, over) = lax.scan(block_fns[kind], x, layers)
            return x, (jnp.sum(aux), jnp.sum(over, 0))
        return fn

    aux = over = 0
    if config.num_periods:
        # the pattern's runs, in order: (sliding, full) as published
        runs = tuple(kind for kind, _ in itertools.groupby(config.pattern))
        x, (aux, over) = scan_layer_kinds(
            x, jax.tree.map(lambda a: a[:, None], params["blocks"]), runs,
            {kind: run_of(kind) for kind in runs})
    if config.tail_layers:
        x, (tail_aux, tail_over) = lax.scan(block_fns[SLIDING], x,
                                            params["tail"])
        aux, over = aux + jnp.sum(tail_aux), over + jnp.sum(tail_over, 0)
    return x, aux, over


def head_with_aux(params, batch, config: MellumConfig, train: bool = True,
                  rng=None):
    """-> (the head's inputs, router loss, rows over a bound)."""
    x, aux, over = hidden_with_aux(params, batch, config, train, rng)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        return (Head(_rms_norm(x, params["final_norm"], config.norm_eps),
                     params["lm_head"]), aux, over)


def layers_in_order(params, config: MellumConfig):
    """[(kind, that layer's parameters)] in the stack's order — for
    diagnostics that write the layers out."""
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


def routed_rows(params, batch, config: MellumConfig):
    """[layers, num_experts] int32: the (token, choice) pairs each layer's
    router sends to each of ALL experts for this micro-batch — what a
    bound on a share's, or a pair of chips', rows has to hold
    (scripts/held_rows_table.py).  A diagnostic: the layers written out,
    no scan."""
    from deepspeed_tpu.moe.layer import _route, _routing_logits
    seg = segment_ids_of(batch)
    moe = config.moe
    x = params["wte"].astype(jnp.dtype(config.dtype))[batch["input_ids"]]
    rows = []
    for kind, layer in layers_in_order(params, config):
        attended = _attention(x, layer, config, kind, seg)
        h = _rms_norm(attended, layer["mlp_norm"], config.norm_eps)
        logits = _routing_logits(layer["moe"],
                                 h.reshape(-1, config.d_model), moe)
        chosen = _route(layer["moe"], logits, moe, True, None).expert_idx
        rows.append(jnp.bincount(chosen.reshape(-1),
                                 length=config.num_experts))
        x, _ = _block(x, layer, config, kind, train=True, segment_ids=seg)
    return jnp.stack(rows)


def count_params(config: MellumConfig) -> int:
    return param_count(partial(init_params, config))


def mellum_model(size: str = "12b-a2.5b", **overrides) -> Model:
    config = MellumConfig(**{
        **resolve_size(MELLUM_SIZES, size, "mellum"), **overrides})
    return held_share_model(
        "mellum", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        expert_layers=config.num_layers, expert_matrices=3,
        lookup_params=config.vocab_size * config.d_model,
        serving_needs=(
            "serving needs a cache that keeps sliding_window positions for "
            "the sliding layers and every position for the full ones, and "
            "an exchange at decode's sizes"),
        # every expert's routed rows, layer by layer
        meta={"routed_rows": lambda p, b: routed_rows(p, b, config)})
