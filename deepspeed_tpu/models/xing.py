"""Xing4.0-29B-A4B (huggingface.co/XingChen-AGI/Xing4.0-29B-A4B,
``model_type: xing4_0``): the DeepSeek-V3 layout of models/joyai.py —
latent attention in every layer, leading dense layers, then layers of
sigmoid-routed SwiGLU experts beside a shared one, a prediction module in
the loss — under **a residual of n streams mixed by manifold-constrained
hyper-connections** (``hc_mult`` 4; ops/hyper_connection.py has the
equations and their source), with YaRN-scaled rotary frequencies.

- *The residual.*  Per token ``X`` [n, D], held as one row of n D (the
  stream of a batch is [B, S, n D]: ops/hyper_connection.py says why).
  Entry: ``X[i] = E[x_t]`` for every i.  **Each sublayer** (a layer's
  attention; its MLP or experts) has its own hyper-connection leaves
  (``hc_attn``, ``hc_mlp``: ``phi``, ``alpha``, ``b_pre``, ``b_post``,
  ``b_res``): ``h = sum_i H_pre[i] X[i]``,
  ``y = F(N(h))`` with the sublayer's own RMSNorm as in JoyAI's blocks,
  ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` with ``H_res`` made
  doubly stochastic by ``hc_sinkhorn_iters`` Sinkhorn sweeps.  Exit:
  ``sum_i X[i]``, then the final norm and the head.
- *The sublayers* are JoyAI's own branch functions, imported:
  ``joyai.latent_attention``, ``joyai.dense_mlp``, ``model.expert_branch``
  — the residual add is the caller's there, and here the caller writes the
  branch into the streams.  The attention's ``k`` and ``v`` leave two
  products of ``[c_kv | k_r]`` as the kernels read them, under this file's
  rotary as under JoyAI's (models/joyai.py ``_key_value_weights``).  The
  heads, the prediction module's input and both losses are
  ``joyai.loss_with_counts`` over this file's stack.
- *Rotary*: YaRN (``rope_factor`` over ``original_max_position_embeddings``
  between ``beta_fast`` and ``beta_slow`` turns; models/laguna.py
  ``yarn_inv_freq``) at every length, pairs ``(2i, 2i+1)``; the softmax
  scale is ``1 / sqrt(qk_nope + qk_rope)`` times ``m(mscale_all_dim)^2``,
  ``m(s) = 0.1 s ln(rope_factor) + 1`` (the DeepSeek-V2 convention the keys
  are from), and cos and sin are times ``m(mscale) / m(mscale_all_dim)``.
  The flash kernels scale by the score width themselves, so the factor is
  laid on ``q`` where it turns: one rounding, no pass of its own.
- *The prediction module* reads the exit sum of the main stack (before the
  main final norm, as JoyAI's reads ``h_t``), replicates ``h'_t`` into n
  streams for its own block and sums them at its own exit.

The layer loop is lead-then-run: the ``num_dense_layers`` leading blocks
written out (``params["dense"]``, stacked), then one ``lax.scan`` over the
expert layers' stack (``params["blocks"]``) whose carry is the stream [B,
S, n D]: under per-layer remat a block saves that and nothing else.  Not
built: serving (the absorbed form, a cache of latents, a stream state at
the current position — the entry points raise); a load-driven update of the
router's bias; ZeRO-3 and parameter streaming.
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import joyai
from deepspeed_tpu.models.joyai import (attn_specs, dense_mlp,
                                        dense_mlp_specs, expert_block_specs,
                                        latent_attention, mtp_input)
from deepspeed_tpu.models.laguna import yarn_inv_freq
from deepspeed_tpu.models.llama import (_rms_norm, _turn_pairs,
                                        interleaved_tables)
from deepspeed_tpu.models.model import (Model, embed_tokens, expert_branch,
                                        held_share_model, layer_block,
                                        param_count, refuse_param_stream,
                                        resolve_size, segment_ids_of)
from deepspeed_tpu.moe.layer import MoEConfig
from deepspeed_tpu.ops.hyper_connection import (HyperConnection, exit_sum,
                                                hc_coefficients, hc_read,
                                                hc_write, init_hc_params,
                                                replicate)
from deepspeed_tpu.telemetry.tracing import (SCOPE_ATTN, SCOPE_BLOCK,
                                             SCOPE_MLP)


@dataclass(frozen=True)
class XingConfig:
    vocab_size: int = 131072
    max_seq_len: int = 262144
    #: main layers: ``num_dense_layers`` leading ones whose feed-forward is
    #: dense (``first_k_dense_replace``), then expert layers; the
    #: prediction module's block is one more
    num_layers: int = 40
    num_dense_layers: int = 2
    d_model: int = 3584
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    #: ``rope_scaling`` (type yarn): factor, original_max_position_embeddings,
    #: beta_fast, beta_slow, mscale, mscale_all_dim
    rope_factor: float = 64.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    #: the residual's streams and their mixing (ops/hyper_connection.py)
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    #: the leading dense layers' width (``intermediate_size``)
    d_ff_dense: int = 9216
    #: an expert's width (``moe_intermediate_size``)
    d_ff: int = 1024
    num_experts: int = 64
    top_k: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    held_rows_factor: int = 2
    shared_expert_d_ff: int = 1024
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    num_mtp_layers: int = 1
    mtp_loss_weight: float = 0.3
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        if not 1 <= self.num_dense_layers < self.num_layers:
            raise ValueError(
                f"xing: the stack is leading dense layers and then expert "
                f"layers (1 <= num_dense_layers < num_layers), not "
                f"{self.num_dense_layers} of {self.num_layers}")
        if self.num_mtp_layers not in (0, 1):
            raise ValueError(
                f"xing: num_mtp_layers is 0 or 1 (a second prediction "
                f"module is not built), not {self.num_mtp_layers}")
        if self.hc_mult < 1:
            raise ValueError(f"xing: hc_mult {self.hc_mult} streams")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rotary_ndims(self) -> int:
        """What ``yarn_inv_freq`` calls the rotary width."""
        return self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        """Main layers with experts (the module's block not among them)."""
        return self.num_layers - self.num_dense_layers

    @property
    def moe(self) -> MoEConfig:
        return MoEConfig.of(self, router="sigmoid", activation="silu_glu",
                            dispatch_mode="grouped")

    @property
    def hc(self) -> HyperConnection:
        return HyperConnection(
            streams=self.hc_mult, sweeps=self.hc_sinkhorn_iters,
            sinkhorn_eps=self.hc_eps, clamp_min=self.hc_clamp_min,
            clamp_max=self.hc_clamp_max, norm_eps=self.norm_eps)


XING_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=3,
                 num_dense_layers=1, d_model=32, num_heads=2, q_lora_rank=24,
                 kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, original_max_position_embeddings=32,
                 d_ff_dense=64, d_ff=16, num_experts=8, top_k=2,
                 shared_expert_d_ff=16),
    # huggingface.co/XingChen-AGI/Xing4.0-29B-A4B config.json: the defaults
    # above.  29.5B parameters with the prediction module; one chip trains
    # one leading layer, four expert layers and the module with 8 of each
    # layer's 64 experts held (benchmarks/configs)
    "4.0-29b-a4b": dict(),
}


# ------------------------------------------------------------- parameters
def _hc_pair(config: XingConfig, key, lead=()):
    k_attn, k_mlp = jax.random.split(key)
    make = partial(init_hc_params, config.hc, config.d_model, lead=lead)
    return {"hc_attn": make(k_attn), "hc_mlp": make(k_mlp)}


def _expert_block_params(config: XingConfig, key, n=None):
    k_block, k_hc = jax.random.split(key)
    return {**joyai._expert_block_params(config, k_block, n),
            **_hc_pair(config, k_hc, () if n is None else (n,))}


def init_params(config: XingConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02 (``phi`` too), norm weights 1,
    ``e_score_correction_bias`` 0, and the hyper-connections' scalars and
    biases as ``init_hc_params`` has them, so that the first step's
    function is close to the pre-norm residual."""
    D, V, F = config.d_model, config.vocab_size, config.d_ff_dense
    L = config.num_dense_layers
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 12))
    params = {
        "wte": norm(next(k), (V, D)) * std,
        "dense": {**joyai._attn_params(config, next(k), (L,)),
                  "mlp_norm": jnp.ones((L, D)),
                  "w_gate": norm(next(k), (L, D, F)) * std,
                  "w_up": norm(next(k), (L, D, F)) * std,
                  "w_down": norm(next(k), (L, F, D)) * std,
                  **_hc_pair(config, next(k), (L,))},
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


def logical_specs(config: XingConfig) -> dict:
    # the streams' leaves are small and every chip mixes its own tokens
    hc = {name: {"phi": P(), "alpha": P(), "b_pre": P(), "b_post": P(),
                 "b_res": P()} for name in ("hc_attn", "hc_mlp")}
    specs = {
        "wte": P("model", None),
        "dense": {**attn_specs((None,)), **dense_mlp_specs((None,)), **hc},
        "blocks": {**expert_block_specs(config, (None,)), **hc},
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }
    if config.num_mtp_layers:
        specs["mtp"] = {"norm_h": P(), "norm_e": P(), "w_eh": P(),
                        "block": {**expert_block_specs(config), **hc},
                        "final_norm": P()}
    return specs


# ------------------------------------------------------------------ rotary
def _yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_factor(config: XingConfig) -> float:
    """What the softmax scale ``1 / sqrt(qk_nope + qk_rope)`` is times."""
    return _yarn_m(config.rope_factor, config.mscale_all_dim) ** 2


def _yarn_rotary(q, k_r, config: XingConfig):
    """As ``joyai._rotary`` at YaRN's frequencies, ``q`` leaving times
    :func:`softmax_factor` — lanes that turn and lanes that pass alike."""
    nope, rot = config.qk_nope_head_dim, config.qk_rope_head_dim
    positions = jnp.arange(q.shape[1])
    on_tables = _yarn_m(config.rope_factor, config.mscale) \
        / _yarn_m(config.rope_factor, config.mscale_all_dim)
    inv_freq = yarn_inv_freq(config)
    c_q, s_q = interleaved_tables(positions, None, rot, nope, inv_freq)
    c_k, s_k = interleaved_tables(positions, None, rot, 0, inv_freq)
    # q: the softmax factor on every lane, the tables' own on those that turn
    on_q = softmax_factor(config) * jnp.where(
        jnp.arange(nope + rot) >= nope, on_tables, 1.0)
    return (_turn_pairs(q, c_q * on_q, s_q * on_q, nope, False),
            _turn_pairs(k_r, c_k * on_tables, s_k * on_tables, 0, False))


# ------------------------------------------------------------------ blocks
def _replicate(x, n: int):
    with jax.named_scope(SCOPE_BLOCK):
        return replicate(x, n)


def _exit_sum(x, n: int):
    with jax.named_scope(SCOPE_BLOCK):
        return exit_sum(x, n)


def _hyper(x, hc_params, branch, config: XingConfig, scope, at, calls):
    """One hyper-connected sublayer on the stream ``x`` [B, S, n D]:
    ``branch(h) -> (y, sums)`` is the sublayer without a residual.  The
    coefficients, the read and the write lie inside the sublayer's own
    scope, beside what the branch writes there."""
    with jax.named_scope(scope):
        pre, post, res = hc_coefficients(x, hc_params, config.hc,
                                         at=at, calls=calls)
        h = hc_read(x, pre)
    y, sums = branch(h)
    with jax.named_scope(scope):
        return hc_write(x, y, post, res), sums


def _attention_sublayer(x, layer, config, segment_ids, at, calls):
    return _hyper(
        x, layer["hc_attn"],
        lambda h: (latent_attention(h, layer, config, segment_ids,
                                    rotary=_yarn_rotary), None),
        config, SCOPE_ATTN, at + "/attn", calls)[0]


@jax.named_scope(SCOPE_BLOCK)
def _dense_block(x, layer, config: XingConfig, segment_ids=None,
                 at="dense", calls=1):
    x = _attention_sublayer(x, layer, config, segment_ids, at, calls)
    return _hyper(x, layer["hc_mlp"],
                  lambda h: (dense_mlp(h, layer, config), None),
                  config, SCOPE_MLP, at + "/mlp", calls)[0]


@jax.named_scope(SCOPE_BLOCK)
def _expert_block(x, layer, config: XingConfig, train, rng=None,
                  segment_ids=None, at="blocks", calls=1):
    """-> (x, (router loss, routed rows over ``held_rows_bound``))."""
    x = _attention_sublayer(x, layer, config, segment_ids, at, calls)
    return _hyper(
        x, layer["hc_mlp"],
        lambda h: expert_branch(
            h, layer["moe"], config.moe,
            lambda h: _rms_norm(h, layer["mlp_norm"], config.norm_eps),
            train, rng),
        config, SCOPE_MLP, at + "/mlp", calls)


def hidden_with_aux(params, batch, config: XingConfig, train: bool = True,
                    rng=None):
    """The main stack: -> (the streams' sum after the last layer [B, S, D],
    before the final norm; router loss summed over the expert layers;
    routed rows over ``held_rows_bound`` summed over them, int32)."""
    refuse_param_stream(
        "xing", "leading dense blocks, a stack of expert blocks and a "
        "prediction module")
    seg = segment_ids_of(batch)
    x = _replicate(embed_tokens(params["wte"], batch["input_ids"],
                                jnp.dtype(config.dtype)), config.hc_mult)
    dense = layer_block(_dense_block, config, segment_ids=seg,
                        calls=config.num_dense_layers)
    for i in range(config.num_dense_layers):
        x = dense(x, jax.tree.map(lambda a: a[i], params["dense"]))
    x, (aux, over) = lax.scan(
        layer_block(_expert_block, config, train=train, rng=rng,
                    segment_ids=seg, calls=config.expert_layers),
        x, params["blocks"])
    return _exit_sum(x, config.hc_mult), jnp.sum(aux), jnp.sum(over, 0)


def mtp_hidden_with_aux(params, x, batch, config: XingConfig,
                        train: bool = True, rng=None):
    """The prediction module up to its block's exit sum: ``x`` is the main
    stack's (before the final norm); ``joyai.mtp_input`` joins it with the
    embedding of token t+1, and the module's own block runs on n copies of
    the result."""
    h = _replicate(mtp_input(params, x, batch, config), config.hc_mult)
    h, sums = layer_block(_expert_block, config, train=train, rng=rng,
                          segment_ids=segment_ids_of(batch), at="mtp")(
        h, params["mtp"]["block"])
    return _exit_sum(h, config.hc_mult), sums


_STACK = (hidden_with_aux, mtp_hidden_with_aux)
head_with_aux = partial(joyai.head_with_aux, stack=_STACK)
loss_with_counts = partial(joyai.loss_with_counts, stack=_STACK)
mtp_token_losses = partial(joyai.mtp_token_losses, stack=_STACK)


def count_params(config: XingConfig) -> int:
    return param_count(partial(init_params, config))


def xing_model(size: str = "4.0-29b-a4b", **overrides) -> Model:
    config = XingConfig(**{
        **resolve_size(XING_SIZES, size, "xing"), **overrides})
    head = config.d_model * config.vocab_size
    return held_share_model(
        "xing", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        loss_with_counts=loss_with_counts,
        expert_layers=config.expert_layers + config.num_mtp_layers,
        expert_matrices=3, lookup_params=head,
        # with the module the head multiplies a token twice
        reused_params=config.num_mtp_layers * head,
        serving_needs=(
            "serving a hyper-connected stack needs the stream's state at "
            "the current position, latent attention's absorbed form (scores "
            "against the cached latents themselves) and a paged cache of "
            "latents and rotary keys"),
        meta={
            # the module's per-token losses, for a check against the plain
            # reference's (scripts/reference_control.py)
            "mtp_token_losses": (lambda p, b: mtp_token_losses(
                p, b, config)) if config.num_mtp_layers else None})
