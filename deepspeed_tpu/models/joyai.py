"""JoyAI-LLM-Flash (huggingface.co/jdopensource/JoyAI-LLM-Flash,
``model_type: joyai_llm_flash``), whose config is key for key the
DeepSeek-V3 layout: **latent attention** in every layer (DeepSeek-V2,
arXiv:2405.04434 section 2.1), one leading dense layer, then layers of
sigmoid-routed SwiGLU experts beside a shared one, and a
**multi-token-prediction** module in the loss (DeepSeek-V3,
arXiv:2412.19437 sections 2.1-2.2).  RMSNorm everywhere, no bias anywhere,
untied head.

- *Latent attention* (``H`` heads).  Queries: ``c_q = N(x W_dq)``
  (``q_lora_rank`` wide), ``[q_nope | q_rope] = c_q W_uq`` per head
  (``qk_nope_head_dim | qk_rope_head_dim``).  Keys and values: ``[c_kv |
  k_r] = x W_dkv`` (``kv_lora_rank | qk_rope_head_dim``); ``c_kv <-
  N(c_kv)``; ``[k_nope | v] = c_kv W_ukv`` per head (``qk_nope_head_dim |
  v_head_dim``); ``k_r`` is ONE rotary key shared by all heads.  Rotary
  (``rope_theta``, pairs ``(2i, 2i+1)``) on ``q_rope`` and ``k_r`` only, by
  the position along the sequence.  ``q = [q_nope | q_rope]``, ``k =
  [k_nope | k_r]``: the score head is ``qk_nope + qk_rope`` wide and the
  value head ``v_head_dim`` — the flash kernels take the two widths
  (ops/pallas/ds_flash_attention.py).  Causal softmax of ``q k^T /
  sqrt(qk_nope + qk_rope)`` inside a document; ``o = concat_heads(P v)
  W_o``.  This is the expanded, per-head form that training runs; the
  absorbed form that decoding would run (scores against ``c_kv`` itself) is
  not built.  ``k`` and ``v`` are never assembled: ``k = [c_kv | k_r] W_k``
  is one product whose operand ``W_k`` holds each head's key columns of
  ``W_ukv`` and, under them, an identity that carries ``k_r`` into every
  head's last lanes (exact: a value times one plus zeros), and ``v`` is
  the product with the value columns; their cotangents go back through
  the same products (``_key_value_weights``).  ``W_ukv`` stays one leaf.
- *Layer 0*: ``x + MLA(N(x))``, then ``x + W_down(silu(W_gate h) * W_up
  h)`` at ``d_ff_dense``.
- *Layers 1..*: the same attention, then experts (moe/layer.py): ``s =
  sigmoid(h W_r)`` in float32; the choice is the ``top_k`` largest of ``s +
  e_score_correction_bias`` (a leaf the loss does not train); the weights
  are ``s`` of the chosen over their sum, times ``routed_scaling_factor``;
  SwiGLU experts at ``d_ff`` and one shared SwiGLU expert, added as it is.
  ``experts_held`` (with ``expert_offset``) makes this chip's share of an
  expert-parallel layer.
- *Multi-token prediction*, depth 1 (``num_mtp_layers``): with ``h_t`` the
  last main layer's output at position t, BEFORE the final norm, and ``E``
  the shared embedding: ``h'_t = [N_h(h_t) ; N_e(E[x_{t+1}])] W_eh``, one
  more block of the layers-1.. kind, a final norm of its own and the
  SHARED head, scored against ``x_{t+2}`` where t, t+1 and t+2 are of one
  document.  ``L = L_main + mtp_loss_weight * L_mtp + router losses``.

The layer loop is **lead-then-run**: the dense block once, then one
``lax.scan`` over the expert layers' stack (``params["blocks"]``), so that
depth does not multiply the traced text.  The module's embedding and head
are ``params["wte"]`` and ``params["lm_head"]`` themselves: one leaf each,
two uses, and their gradients are the sum of both.  Each head pass is
rematerialised on its own, so one ``[tokens, vocab]`` float32 logits array
lives at a time.  Not built: a load-driven update of the router's bias;
serving (the absorbed form and a cache of latents — the entry points
raise); ZeRO-3 and parameter streaming.
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_half, held_share_model,
                                        layer_block, next_token_targets,
                                        param_count, qdot,
                                        refuse_param_stream, resolve_size,
                                        segment_ids_of)
from deepspeed_tpu.models.llama import _rms_norm, rope
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs, named_sums)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_EMBED, SCOPE_HEAD_LOSS, SCOPE_IN_PROJ,
    SCOPE_KV_LATENT, SCOPE_MLP, SCOPE_MTP, SCOPE_OUT_PROJ, SCOPE_Q_LATENT,
    SCOPE_ROPE, SCOPE_SCORES)


@dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    max_seq_len: int = 131072
    #: main layers: ONE leading layer whose feed-forward is dense
    #: (``first_k_dense_replace`` 1), then expert layers; the prediction
    #: module's block is one more
    num_layers: int = 40
    d_model: int = 2048
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    #: the leading dense layer's width (``intermediate_size``)
    d_ff_dense: int = 7168
    #: an expert's width (``moe_intermediate_size``)
    d_ff: int = 768
    num_experts: int = 256
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    held_rows_factor: int = 2
    shared_expert_d_ff: int = 768
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    #: prediction modules behind the main model (``num_nextn_predict_layers``:
    #: 0 = the main stack alone, 1 = as published) and the weight of the
    #: module's loss
    num_mtp_layers: int = 1
    mtp_loss_weight: float = 0.3
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.num_layers < 2:
            raise ValueError(
                f"joyai: the stack is one leading dense layer and then "
                f"expert layers (num_layers >= 2), not {self.num_layers}")
        if self.num_mtp_layers not in (0, 1):
            raise ValueError(
                f"joyai: num_mtp_layers is 0 or 1 (a second prediction "
                f"module is not built), not {self.num_mtp_layers}")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        """Main layers with experts (the module's block not among them)."""
        return self.num_layers - 1

    @property
    def moe(self) -> MoEConfig:
        # a held share runs through the grouped dispatch only
        return MoEConfig.of(self, router="sigmoid", activation="silu_glu",
                            dispatch_mode="grouped")


JOYAI_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=3, d_model=32,
                 num_heads=2, q_lora_rank=24, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 d_ff_dense=64, d_ff=16, num_experts=8, top_k=2,
                 shared_expert_d_ff=16),
    # huggingface.co/jdopensource/JoyAI-LLM-Flash config.json: the defaults
    # above.  48.9B parameters whole, 1.25B more with the prediction
    # module; one chip trains the first five layers and the module with 16
    # of each layer's 256 experts held (benchmarks/configs)
    "llm-flash": dict(),
}


def _attn_params(config: JoyAIConfig, key, lead=()):
    D, H = config.d_model, config.num_heads
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    nope, rot, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                     config.v_head_dim)
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(key, 5))
    std = 0.02
    return {
        "attn_norm": jnp.ones(lead + (D,)),
        "w_dq": norm(next(k), lead + (D, rq)) * std,
        "q_norm": jnp.ones(lead + (rq,)),
        "w_uq": norm(next(k), lead + (rq, H * (nope + rot))) * std,
        "w_dkv": norm(next(k), lead + (D, rkv + rot)) * std,
        "kv_norm": jnp.ones(lead + (rkv,)),
        "w_ukv": norm(next(k), lead + (rkv, H * (nope + vd))) * std,
        "w_o": norm(next(k), lead + (H * vd, D)) * std,
    }


def _expert_block_params(config: JoyAIConfig, key, n=None):
    """``n`` expert layers stacked (None: one, unstacked)."""
    lead = () if n is None else (n,)
    k_attn, k_moe = jax.random.split(key)
    if n is None:
        moe = init_moe_params(config.moe, k_moe)
    else:
        moe = jax.vmap(partial(init_moe_params, config.moe))(
            jax.random.split(k_moe, n))
    return {**_attn_params(config, k_attn, lead),
            "mlp_norm": jnp.ones(lead + (config.d_model,)), "moe": moe}


def init_params(config: JoyAIConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02, norm weights 1, ``e_score_correction_bias`` 0."""
    D, V, F = config.d_model, config.vocab_size, config.d_ff_dense
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 10))
    params = {
        "wte": norm(next(k), (V, D)) * std,
        "dense": {**_attn_params(config, next(k)),
                  "mlp_norm": jnp.ones((D,)),
                  "w_gate": norm(next(k), (D, F)) * std,
                  "w_up": norm(next(k), (D, F)) * std,
                  "w_down": norm(next(k), (F, D)) * std},
        "blocks": _expert_block_params(config, next(k),
                                       config.expert_layers),
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }
    if config.num_mtp_layers:
        # no embedding and no head of its own: wte and lm_head above
        params["mtp"] = {
            "norm_h": jnp.ones((D,)), "norm_e": jnp.ones((D,)),
            "w_eh": norm(next(k), (2 * D, D)) * std,
            "block": _expert_block_params(config, next(k)),
            "final_norm": jnp.ones((D,)),
        }
    return params


def attn_specs(lead=()):
    col, row = P(*lead, None, "model"), P(*lead, "model", None)
    return {"attn_norm": P(), "w_dq": P(), "q_norm": P(), "w_uq": col,
            "w_dkv": P(), "kv_norm": P(), "w_ukv": col, "w_o": row}


def dense_mlp_specs(lead=()):
    return {"mlp_norm": P(), "w_gate": P(*lead, None, "model"),
            "w_up": P(*lead, None, "model"),
            "w_down": P(*lead, "model", None)}


def expert_block_specs(config, lead=()):
    moe = jax.tree.map(lambda spec: P(*lead, *spec),
                       moe_logical_specs(config.moe),
                       is_leaf=lambda s: isinstance(s, P))
    return {**attn_specs(lead), "mlp_norm": P(), "moe": moe}


def logical_specs(config: JoyAIConfig) -> dict:
    specs = {
        "wte": P("model", None),
        "dense": {**attn_specs(), **dense_mlp_specs()},
        "blocks": expert_block_specs(config, (None,)),
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }
    if config.num_mtp_layers:
        specs["mtp"] = {"norm_h": P(), "norm_e": P(), "w_eh": P(),
                        "block": expert_block_specs(config),
                        "final_norm": P()}
    return specs


def _rotary(q, k_r, config):
    """q [B, S, H, nope + rot] turned in place on its last ``rot`` lanes,
    its position-free lanes passing through; the one shared key k_r [B, S,
    1, rot] whole."""
    return (rope(q, config.rope_theta, interleaved=True,
                 first=config.qk_nope_head_dim),
            rope(k_r, config.rope_theta, interleaved=True))


def _key_value_weights(w_ukv, config, dtype):
    """The ``w_ukv`` leaf ``[rkv, H (nope + vd)]`` as the two operands
    that write the kernels' ``k`` and ``v``: ``W_k`` ``[rkv + rot, H, nope
    + rot]`` — each head's key columns with ``rot`` zero columns behind
    them, and under those ``rot`` rows of zeros and one identity, the same
    for every head, which carry the shared rotary key into each head's
    last lanes — and ``W_v`` ``[rkv, H, vd]``, each head's value columns.
    Built while tracing, so the leaf, its gradient and the checkpoint stay
    as they are; the constant blocks' gradient is dropped by the
    transpose."""
    H, rkv = config.num_heads, config.kv_lora_rank
    nope, rot, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                     config.v_head_dim)
    w = w_ukv.astype(dtype).reshape(rkv, H, nope + vd)
    carry = jnp.broadcast_to(jnp.eye(rot, dtype=dtype)[:, None],
                             (rot, H, rot))
    w_k = jnp.concatenate([
        jnp.pad(w[..., :nope], ((0, 0), (0, 0), (0, rot))),
        jnp.pad(carry, ((0, 0), (0, 0), (nope, 0)))], axis=0)
    return w_k, w[..., nope:]


def latent_attention(x, layer, config, segment_ids, rotary=_rotary):
    """``MLA(N(x))``, the branch alone: whoever calls owns the residual
    (the block below adds ``x``; models/xing.py writes it into its
    streams).  ``config`` is any with this file's attention sizes;
    ``rotary(q, k_r, config)`` turns both (a family with scaled
    frequencies brings its own; None: nothing turns, the shared key part
    is kept as it is — models/kimi_linear.py).  A layer without ``w_dq``
    has no query latent: ``q = h W_q`` (``w_q``), one matrix."""
    B, S, _ = x.shape
    H, rkv = config.num_heads, config.kv_lora_rank
    nope, rot, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                     config.v_head_dim)
    eps = config.norm_eps
    with jax.named_scope(SCOPE_ATTN):
        h = _rms_norm(x, layer["attn_norm"], eps)
        if "w_dq" in layer:
            with jax.named_scope(SCOPE_Q_LATENT):
                c_q = _rms_norm(qdot(h, layer["w_dq"]), layer["q_norm"], eps)
                q = qdot(c_q, layer["w_uq"])
        else:
            with jax.named_scope(SCOPE_IN_PROJ):
                q = qdot(h, layer["w_q"])
        q = q.reshape(B, S, H, nope + rot)
        with jax.named_scope(SCOPE_KV_LATENT):
            ckv = qdot(h, layer["w_dkv"])
            c_kv = _rms_norm(ckv[..., :rkv], layer["kv_norm"], eps)
        k_r = jnp.expand_dims(ckv[..., rkv:], 2)
        if rotary is not None:
            with jax.named_scope(SCOPE_ROPE):
                q, k_r = rotary(q, k_r, config)
        with jax.named_scope(SCOPE_KV_LATENT):
            w_k, w_v = _key_value_weights(layer["w_ukv"], config,
                                          c_kv.dtype)
            # one product writes k [B, S, H, nope + rot] as the kernels
            # read it: a bf16 value times one plus exact zeros, summed in
            # float32 and rounded once, is that value, so every head's
            # last ``rot`` lanes are the one rotary key to the bit
            k = jnp.einsum(
                "bsc,chd->bshd",
                jnp.concatenate([c_kv, k_r.reshape(B, S, rot)], axis=-1),
                w_k)
            v = jnp.einsum("bsc,chd->bshd", c_kv, w_v)
        with jax.named_scope(SCOPE_SCORES):
            attn = causal_attention(q, k, v, impl=config.attention_impl,
                                    segment_ids=segment_ids)
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    with jax.named_scope(SCOPE_ATTN), jax.named_scope(SCOPE_OUT_PROJ):
        return qdot(attn.reshape(B, S, H * vd), layer["w_o"])


def _latent_attention(x, layer, config: JoyAIConfig, segment_ids):
    """``x + MLA(N(x))``; the caller's scope is ``ds.block``."""
    out = latent_attention(x, layer, config, segment_ids)
    with jax.named_scope(SCOPE_ATTN), jax.named_scope(SCOPE_OUT_PROJ):
        return x + out


def dense_mlp(x, layer, config):
    """``W_down(silu(W_gate h) * W_up h)``, ``h = N(x)``: the leading
    layer's feed-forward branch alone."""
    with jax.named_scope(SCOPE_MLP):
        h = _rms_norm(x, layer["mlp_norm"], config.norm_eps)
        h = jax.nn.silu(qdot(h, layer["w_gate"])) * qdot(h, layer["w_up"])
        return qdot(h, layer["w_down"])


@jax.named_scope(SCOPE_BLOCK)
def _dense_block(x, layer, config: JoyAIConfig, segment_ids=None):
    x = _latent_attention(x, layer, config, segment_ids)
    out = dense_mlp(x, layer, config)
    with jax.named_scope(SCOPE_MLP):
        return x + out


@jax.named_scope(SCOPE_BLOCK)
def _expert_block(x, layer, config: JoyAIConfig, train, rng=None,
                  segment_ids=None):
    """-> (x, (router loss, routed rows over ``held_rows_bound``))."""
    x = _latent_attention(x, layer, config, segment_ids)
    return expert_half(
        x, layer["moe"], config.moe,
        lambda x: _rms_norm(x, layer["mlp_norm"], config.norm_eps),
        train, rng)


def hidden_with_aux(params, batch, config: JoyAIConfig, train: bool = True,
                    rng=None):
    """The main stack: -> (the last layer's output [B, S, D], before the
    final norm; router loss summed over the expert layers; routed rows over
    ``held_rows_bound`` summed over them, int32)."""
    refuse_param_stream(
        "joyai", "a leading dense block, a stack of expert blocks and a "
        "prediction module")
    seg = segment_ids_of(batch)
    x = embed_tokens(params["wte"], batch["input_ids"],
                     jnp.dtype(config.dtype))
    x = layer_block(_dense_block, config, segment_ids=seg)(
        x, params["dense"])
    x, (aux, over) = lax.scan(
        layer_block(_expert_block, config, train=train, rng=rng,
                    segment_ids=seg), x, params["blocks"])
    return x, jnp.sum(aux), jnp.sum(over, 0)


def _head(x, norm_w, lm_head, config: JoyAIConfig):
    with jax.named_scope(SCOPE_HEAD_LOSS):
        return Head(_rms_norm(x, norm_w, config.norm_eps), lm_head)


def head_with_aux(params, batch, config, train: bool = True, rng=None,
                  stack=None):
    """-> (the main head's inputs, router loss, rows over the bound): the
    main model alone, as a forward pass reads it.  ``stack``: as
    :func:`loss_with_counts`."""
    hidden, _ = stack or (hidden_with_aux, mtp_hidden_with_aux)
    x, aux, over = hidden(params, batch, config, train, rng)
    return (_head(x, params["final_norm"], params["lm_head"], config), aux,
            over)


def mtp_input(params, x, batch, config):
    """What the module's block reads: ``[N_h(x_t) ; N_e(E[id_{t+1}])]
    W_eh``."""
    dtype = jnp.dtype(config.dtype)
    mtp = params["mtp"]
    eps = config.norm_eps
    with jax.named_scope(SCOPE_EMBED):
        nxt = params["wte"].astype(dtype)[
            jnp.roll(batch["input_ids"], -1, axis=1)]
    joined = jnp.concatenate([_rms_norm(x, mtp["norm_h"], eps),
                              _rms_norm(nxt, mtp["norm_e"], eps)], axis=-1)
    return qdot(joined, mtp["w_eh"])


def mtp_hidden_with_aux(params, x, batch, config: JoyAIConfig,
                        train: bool = True, rng=None):
    """The prediction module up to its block's output: ``x`` is the main
    stack's last hidden state (before the final norm); position t joins it
    with the embedding of token t+1 (the last position's, which has none,
    is never scored and nothing attends to it)."""
    return layer_block(_expert_block, config, train=train, rng=rng,
                       segment_ids=segment_ids_of(batch))(
        mtp_input(params, x, batch, config), params["mtp"]["block"])


def routed_rows(params, batch, config: JoyAIConfig):
    """[expert blocks, num_experts] int32: the (token, choice) pairs each
    block's router sends to each of ALL experts for this micro-batch, the
    main layers' blocks in order and then the module's — what
    ``held_rows_bound`` has to hold a share's sum of
    (scripts/held_rows_table.py).  A diagnostic: the layers written out,
    no scan."""
    seg = segment_ids_of(batch)
    moe = config.moe

    def count(x, layer):
        attended = _latent_attention(x, layer, config, seg)
        h = _rms_norm(attended, layer["mlp_norm"], config.norm_eps)
        from deepspeed_tpu.moe.layer import _route, _routing_logits
        logits = _routing_logits(layer["moe"],
                                 h.reshape(-1, config.d_model), moe)
        chosen = _route(layer["moe"], logits, moe, True, None).expert_idx
        return jnp.bincount(chosen.reshape(-1), length=config.num_experts)

    x = params["wte"].astype(jnp.dtype(config.dtype))[batch["input_ids"]]
    x = _dense_block(x, params["dense"], config, segment_ids=seg)
    rows = []
    for i in range(config.expert_layers):
        layer = jax.tree.map(lambda a: a[i], params["blocks"])
        rows.append(count(x, layer))
        x, _ = _expert_block(x, layer, config, train=True, segment_ids=seg)
    if config.num_mtp_layers:
        joined = mtp_input(params, x, batch, config)
        rows.append(count(joined, params["mtp"]["block"]))
        # run for a registry tap to hear this block's plan as the others'
        # (moe/layer.py ``_emit_held_plan``); with no tap nothing is left
        _expert_block(joined, params["mtp"]["block"], config, train=True,
                      segment_ids=seg)
    return jnp.stack(rows)


def mtp_targets(batch):
    """(targets [B, S]: token t+2 at position t; scored [B, S]: where t,
    t+1 and t+2 lie in one document and inside the sequence)."""
    return next_token_targets(batch, ahead=2)


def _scored_nll(logits, targets):
    logits = logits.astype(jnp.float32)
    return jax.scipy.special.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def mtp_token_losses(params, batch, config, stack=None):
    """Every position's negative log likelihood of token t+2 from the
    module's own forward pass [B, S] float32, and which are scored.
    ``stack``: as :func:`loss_with_counts`."""
    hidden, mtp_hidden = stack or (hidden_with_aux, mtp_hidden_with_aux)
    x, _, _ = hidden(params, batch, config, train=False)
    h, _ = mtp_hidden(params, x, batch, config, train=False)
    targets, scored = mtp_targets(batch)
    head = _head(h, params["mtp"]["final_norm"], params["lm_head"], config)
    return _scored_nll(head.logits(), targets), scored


def loss_with_counts(params, batch, config, rng=None, stack=None):
    """-> (``L_main + mtp_loss_weight * L_mtp + router losses``, {rows over
    the bound}).  ``stack``: the (``hidden_with_aux``,
    ``mtp_hidden_with_aux``) of a family whose layers differ and whose
    heads, module and losses are these (models/xing.py); None: this
    file's."""
    hidden, mtp_hidden = stack or (hidden_with_aux, mtp_hidden_with_aux)
    x, aux, over = hidden(params, batch, config, True, rng)
    # neither head pass holds its [tokens, vocab] logits
    # (models/model.py head_token_loss)
    loss = _head(x, params["final_norm"], params["lm_head"],
                 config).token_loss(batch) + aux
    if config.num_mtp_layers:
        with jax.named_scope(SCOPE_MTP):
            h, (mtp_aux, mtp_over) = mtp_hidden(
                params, x, batch, config, True, rng)
            mtp_loss = _head(
                h, params["mtp"]["final_norm"], params["lm_head"],
                config).token_loss(batch, targets=mtp_targets(batch),
                                   name="mtp")
            loss = loss + mtp_aux + config.mtp_loss_weight * mtp_loss
            over = over + mtp_over
    return loss, named_sums(over)


def count_params(config: JoyAIConfig) -> int:
    return param_count(partial(init_params, config))


def joyai_model(size: str = "llm-flash", **overrides) -> Model:
    config = JoyAIConfig(**{
        **resolve_size(JOYAI_SIZES, size, "joyai"), **overrides})
    head = config.d_model * config.vocab_size
    return held_share_model(
        "joyai", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        loss_with_counts=loss_with_counts,
        expert_layers=config.expert_layers + config.num_mtp_layers,
        expert_matrices=3, lookup_params=head,
        # with the module the head multiplies a token twice
        reused_params=config.num_mtp_layers * head,
        serving_needs=(
            "serving latent attention needs its absorbed form (scores "
            "against the cached latents themselves) and a paged cache of "
            "latents and rotary keys, and the prediction module as a "
            "self-drafting head"),
        meta={
            # the module's per-token losses, for a check against the plain
            # reference's (scripts/reference_control.py)
            "mtp_token_losses": (lambda p, b: mtp_token_losses(
                p, b, config)) if config.num_mtp_layers else None,
            # every expert's routed rows, block by block
            "routed_rows": lambda p, b: routed_rows(p, b, config)})
