"""Kimi-Linear-48B-A3B (huggingface.co/moonshotai/Kimi-Linear-48B-A3B-
Instruct, ``model_type: kimi_linear``; Kimi Team 2025, "Kimi Linear: An
Expressive, Efficient Attention Architecture", arXiv:2510.26692): a decoder
whose mixers are of two kinds — three **Kimi Delta Attention** layers (a
gated delta rule whose decay is a vector, one entry a key channel) to one
layer of **latent attention without positions** — over one leading dense
layer and then layers of sigmoid-routed SwiGLU experts beside a shared one.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  Every layer: ``x <- x +
Mixer(N(x))``, ``x <- x + FFN(N(x))``.  No bias; untied head; final ``N``.
Which layer has which mixer is the published lists' (``kda_layers``,
``full_attn_layers``, numbered from 1); a depth cut keeps the first
``num_layers`` of them.

- **KDA mixer** (``H`` heads of ``dk = dv = kda_head_dim``), ``h = N(x)``:
  ``[q | k | v] = silu(conv(h W_qkv))`` (depthwise causal convolutions of
  ``short_conv_kernel_size`` taps, no bias; ops/linear_attention.py
  ``causal_conv``), ``q <- l2norm(q) / sqrt(dk)``, ``k <- l2norm(k)``;
  ``g = -exp(A_log[head]) * softplus((h W_f_down) W_f_up + dt_bias)``, a
  float32 **vector of dk log-decays a head** through a low-rank pair;
  ``beta = sigmoid(h W_beta)`` a head; ``o = gated_delta_rule(q, k, v, g,
  beta)`` with a float32 state; ``y = RMSNorm(o; w_o, over the head) *
  sigmoid((h W_g_down) W_g_up)``, a second low-rank pair; output ``y
  W_out``.  With packed documents the state and the convolutions' history
  are zero at a document's first token.
- **MLA mixer** (``mla_use_nope``): models/joyai.py ``latent_attention``
  (imported) with no query latent — ``q = h W_q``, one matrix — and nothing
  rotated: the ``qk_rope_head_dim``-wide key part shared by all heads is
  kept as it leaves ``W_dkv``.
- **Layer 1**: a KDA mixer, then ``W_down(silu(W_gate h) * W_up h)`` at
  ``d_ff_dense`` (``joyai.dense_mlp``).  **Layers 2..**: a mixer of the
  layer's kind, then experts (models/model.py ``expert_half``, moe/layer.py):
  ``s = sigmoid(h W_r)``, the ``top_k`` largest of ``s +
  e_score_correction_bias``, weights ``s`` of those over their sum times
  ``routed_scaling_factor``, beside one shared expert added as it is.
  ``experts_held`` (with ``expert_offset``) makes this chip's share of an
  expert-parallel layer.

The layer loop is lead-then-runs: the leading block once, then the expert
layers as runs of equal periods — a period is the KDA layers up to and
with the next MLA layer — each run one ``scan_layer_kinds`` (the first
eight layers: the lead, one period K K M, one K K K M; the published 27:
the lead, K K M, five of K K K M, K K M).  Not built: serving (a recurrent
state and three convolution tails a sequence beside a cache of latents,
and latent attention's absorbed form — the entry points raise); a
load-driven update of the router's bias; ZeRO-3 and parameter streaming.
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.joyai import (dense_mlp, dense_mlp_specs,
                                        latent_attention)
from deepspeed_tpu.models.llama import _rms_norm
from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        expert_half,
                                        held_share_model, layer_block,
                                        param_count, qdot,
                                        refuse_param_stream, resolve_size,
                                        scan_layer_kinds, segment_ids_of)
from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                     moe_logical_specs)
from deepspeed_tpu.ops.linear_attention import causal_conv, gated_delta_rule
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_CONV, SCOPE_DELTA_RULE, SCOPE_GATE_NORM,
    SCOPE_HEAD_LOSS, SCOPE_IN_PROJ, SCOPE_LEAD_MLP, SCOPE_LINEAR_ATTN,
    SCOPE_LOW_RANK_GATE, SCOPE_MLP, SCOPE_OUT_PROJ)

KDA, MLA = "kda", "mla"
_LETTER = {KDA: "K", MLA: "M"}


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    max_seq_len: int = 1048576
    #: ONE leading layer whose feed-forward is dense
    #: (``first_k_dense_replace`` 1), then expert layers
    num_layers: int = 27
    d_model: int = 2304
    #: ``linear_attn_config``: which layers (from 1) have which mixer
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26)
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    # Kimi Delta Attention
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    #: the width between the two matrices of the decay's and of the output
    #: gate's low-rank pair (the paper: the head dimension)
    kda_gate_rank: int = 128
    delta_rule_chunk: int = 64
    # latent attention without positions
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: the leading dense layer's width (``intermediate_size``)
    d_ff_dense: int = 9216
    #: an expert's width (``moe_intermediate_size``)
    d_ff: int = 1024
    num_experts: int = 256
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    #: the experts this chip holds (None = all): moe/layer.py MoEConfig
    expert_offset: int = 0
    experts_held: "int | None" = None
    held_rows_factor: int = 2
    shared_expert_d_ff: int = 1024
    aux_loss_coef: float = 1e-4
    load_balance: str = "all_choices"
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        object.__setattr__(self, "kda_layers", tuple(self.kda_layers))
        object.__setattr__(self, "full_attn_layers",
                           tuple(self.full_attn_layers))
        if self.num_layers < 2:
            raise ValueError(
                f"kimi-linear: the stack is one leading dense layer and "
                f"then expert layers (num_layers >= 2), not "
                f"{self.num_layers}")
        listed = sorted(self.kda_layers + self.full_attn_layers)
        if listed[:self.num_layers] != list(range(1, self.num_layers + 1)):
            raise ValueError(
                f"kimi-linear: kda_layers and full_attn_layers do not name "
                f"each of the layers 1..{self.num_layers} once: {listed}")
        if self.layer_kinds[0] != _LETTER[KDA]:
            raise ValueError(
                "kimi-linear: the leading dense layer's mixer is a KDA one "
                "(layer 1 is of kda_layers), not latent attention")

    @property
    def layer_kinds(self) -> str:
        """The mixers of layers 1..``num_layers``, a letter each: ``K`` a
        KDA layer, ``M`` a latent-attention one."""
        return "".join(_LETTER[KDA if i in self.kda_layers else MLA]
                       for i in range(1, self.num_layers + 1))

    @property
    def runs(self) -> tuple:
        """The expert layers (2..) as ((one period's kinds, periods), ...):
        a period ends with its MLA layer (the last may have none), and
        equal periods in a row make one run."""
        kind_of = {letter: kind for kind, letter in _LETTER.items()}
        periods, current = [], []
        for letter in self.layer_kinds[1:]:
            current.append(kind_of[letter])
            if letter == _LETTER[MLA]:
                periods.append(tuple(current))
                current = []
        if current:
            periods.append(tuple(current))
        runs = []
        for period in periods:
            if runs and runs[-1][0] == period:
                runs[-1][1] += 1
            else:
                runs.append([period, 1])
        return tuple((pattern, n) for pattern, n in runs)

    @property
    def expert_layers(self) -> int:
        return self.num_layers - 1

    @property
    def moe(self) -> MoEConfig:
        # a held share runs through the grouped dispatch only
        return MoEConfig.of(self, router="sigmoid", activation="silu_glu",
                            dispatch_mode="grouped")


KIMI_LINEAR_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=8, d_model=32,
                 kda_num_heads=2, kda_head_dim=8, kda_gate_rank=8,
                 delta_rule_chunk=16, num_heads=2, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 d_ff_dense=64, d_ff=16, num_experts=8, top_k=2,
                 shared_expert_d_ff=16),
    # huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct config.json:
    # the defaults above.  49.1B parameters whole; one chip trains the
    # first eight layers with 8 of each layer's 256 experts held
    # (benchmarks/configs)
    "48b-a3b": dict(),
}


# ------------------------------------------------------------- parameters
def _kda_params(config: KimiLinearConfig, key, lead=()):
    """Assumed where the published config is silent: ``A_log = log U(1,
    16)`` a head, ``dt_bias = softplus^-1 of U(1e-3, 1e-1)`` a channel (a
    decay of e^-0.001 .. e^-1.6 a token at the first step), the taps and
    every matrix normal 0.02, the norms' weights 1."""
    D, H, hd = config.d_model, config.kda_num_heads, config.kda_head_dim
    K, r = config.short_conv_kernel_size, config.kda_gate_rank
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(key, 10))
    dt = jax.random.uniform(next(k), lead + (H * hd,), minval=1e-3,
                            maxval=1e-1)
    return {
        "attn_norm": jnp.ones(lead + (D,)),
        "w_qkv": norm(next(k), lead + (D, 3 * H * hd)) * std,
        "conv_w": norm(next(k), lead + (K, 3 * H * hd)) * std,
        "w_f_down": norm(next(k), lead + (D, r)) * std,
        "w_f_up": norm(next(k), lead + (r, H * hd)) * std,
        "A_log": jnp.log(jax.random.uniform(
            next(k), lead + (H,), minval=1.0, maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "w_beta": norm(next(k), lead + (D, H)) * std,
        "w_g_down": norm(next(k), lead + (D, r)) * std,
        "w_g_up": norm(next(k), lead + (r, H * hd)) * std,
        "o_norm": jnp.ones(lead + (hd,)),
        "w_out": norm(next(k), lead + (H * hd, D)) * std,
    }


def _mla_params(config: KimiLinearConfig, key, lead=()):
    D, H, rkv = config.d_model, config.num_heads, config.kv_lora_rank
    nope, rot, vd = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                     config.v_head_dim)
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(key, 4))
    return {
        "attn_norm": jnp.ones(lead + (D,)),
        "w_q": norm(next(k), lead + (D, H * (nope + rot))) * std,
        "w_dkv": norm(next(k), lead + (D, rkv + rot)) * std,
        "kv_norm": jnp.ones(lead + (rkv,)),
        "w_ukv": norm(next(k), lead + (rkv, H * (nope + vd))) * std,
        "w_o": norm(next(k), lead + (H * vd, D)) * std,
    }


_MIXER_PARAMS = {KDA: _kda_params, MLA: _mla_params}


def _expert_block_params(config: KimiLinearConfig, kind, key, lead):
    """Expert layers of one kind stacked ``lead + (...)``."""
    k_mixer, k_moe = jax.random.split(key)
    n = lead[0] * lead[1]
    moe = jax.vmap(partial(init_moe_params, config.moe))(
        jax.random.split(k_moe, n))
    moe = jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), moe)
    return {**_MIXER_PARAMS[kind](config, k_mixer, lead),
            "mlp_norm": jnp.ones(lead + (config.d_model,)), "moe": moe}


def _run_name(i: int) -> str:
    return f"run{i}"


def init_params(config: KimiLinearConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02, norm weights 1, ``e_score_correction_bias`` 0, and
    the KDA leaves as :func:`_kda_params` has them."""
    D, V, F = config.d_model, config.vocab_size, config.d_ff_dense
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 8 + 2 * len(config.runs)))
    params = {
        "wte": norm(next(k), (V, D)) * std,
        "lead": {**_kda_params(config, next(k)),
                 "mlp_norm": jnp.ones((D,)),
                 "w_gate": norm(next(k), (D, F)) * std,
                 "w_up": norm(next(k), (D, F)) * std,
                 "w_down": norm(next(k), (F, D)) * std},
        "blocks": {},
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }
    for i, (pattern, periods) in enumerate(config.runs):
        params["blocks"][_run_name(i)] = {
            kind: _expert_block_params(config, kind, next(k),
                                       (periods, pattern.count(kind)))
            for kind in dict.fromkeys(pattern)}
    return params


def logical_specs(config: KimiLinearConfig) -> dict:
    lead = (None, None)
    col, row = P(*lead, None, "model"), P(*lead, "model", None)
    # the KDA leaves are whole on every chip, as Qwen3-Next's linear ones
    kda = dict.fromkeys(("attn_norm", "w_qkv", "conv_w", "w_f_down",
                         "w_f_up", "A_log", "dt_bias", "w_beta", "w_g_down",
                         "w_g_up", "o_norm", "w_out"), P())
    mla = {"attn_norm": P(), "w_q": col, "w_dkv": P(), "kv_norm": P(),
           "w_ukv": col, "w_o": row}
    moe = jax.tree.map(lambda spec: P(*lead, *spec),
                       moe_logical_specs(config.moe),
                       is_leaf=lambda s: isinstance(s, P))
    mixer = {KDA: kda, MLA: mla}
    return {
        "wte": P("model", None),
        "lead": {**kda, **dense_mlp_specs()},
        "blocks": {
            _run_name(i): {kind: {**mixer[kind], "mlp_norm": P(), "moe": moe}
                           for kind in dict.fromkeys(pattern)}
            for i, (pattern, _) in enumerate(config.runs)},
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }


# ------------------------------------------------------------------ mixers
def _gated_norm(o, gate, w, eps):
    """``RMSNorm(o; w)`` over the head, then the sigmoid gate: the norm
    first."""
    return _rms_norm(o, w, eps) \
        * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


def kda_mixer(x, layer, config: KimiLinearConfig, segment_ids):
    """``KDA(N(x))``, the branch alone (the caller adds ``x``); the
    caller's scope is ``linear_attn``."""
    B, S, _ = x.shape
    H, hd = config.kda_num_heads, config.kda_head_dim
    f32 = lambda a: a.astype(jnp.float32)
    with jax.named_scope(SCOPE_IN_PROJ):
        h = _rms_norm(x, layer["attn_norm"], config.norm_eps)
        qkv = qdot(h, layer["w_qkv"])
        beta = jax.nn.sigmoid(f32(qdot(h, layer["w_beta"])))
    with jax.named_scope(SCOPE_LOW_RANK_GATE):
        # the decay, one entry a key channel, and the output's gate: each
        # through its own low-rank pair
        g = -jnp.exp(f32(layer["A_log"]))[:, None] * jax.nn.softplus(
            f32(qdot(qdot(h, layer["w_f_down"]), layer["w_f_up"])
                ).reshape(B, S, H, hd)
            + f32(layer["dt_bias"]).reshape(H, hd))
        gate = qdot(qdot(h, layer["w_g_down"]), layer["w_g_up"])
    with jax.named_scope(SCOPE_CONV):
        # q, k and v each from the projection itself and as the array the
        # delta rule takes: no slice of [B, S, 3 H hd] before or after
        q, k, v = (
            causal_conv(qkv, layer["conv_w"][:, first:first + H * hd],
                        segment_ids, activation="silu",
                        first_channel=first).reshape(B, S, H, hd)
            for first in (0, H * hd, 2 * H * hd))
    with jax.named_scope(SCOPE_DELTA_RULE):
        o = gated_delta_rule(q, k, v, g, beta, segment_ids,
                             chunk=config.delta_rule_chunk,
                             l2norm_scales=(hd ** -0.5, 1.0))
    o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope(SCOPE_GATE_NORM):
        y = _gated_norm(o, gate.reshape(B, S, H, hd), layer["o_norm"],
                        config.norm_eps)
    with jax.named_scope(SCOPE_OUT_PROJ):
        return qdot(y.reshape(B, S, H * hd), layer["w_out"])


def _mixed(x, layer, config: KimiLinearConfig, kind, segment_ids):
    """``x + Mixer(N(x))`` of a layer of ``kind``."""
    if kind == KDA:
        with jax.named_scope(SCOPE_LINEAR_ATTN):
            out = kda_mixer(x, layer, config, segment_ids)
            with jax.named_scope(SCOPE_OUT_PROJ):
                return x + out
    out = latent_attention(x, layer, config, segment_ids, rotary=None)
    with jax.named_scope(SCOPE_ATTN), jax.named_scope(SCOPE_OUT_PROJ):
        return x + out


def _dense_ffn(x, layer, config: KimiLinearConfig):
    with jax.named_scope(SCOPE_LEAD_MLP):
        out = dense_mlp(x, layer, config)
        with jax.named_scope(SCOPE_MLP):
            return x + out


def _expert_ffn(x, layer, config: KimiLinearConfig, train, rng=None):
    """-> (x, (router loss, routed rows over ``held_rows_bound``))."""
    return expert_half(
        x, layer["moe"], config.moe,
        lambda x: _rms_norm(x, layer["mlp_norm"], config.norm_eps),
        train, rng)


@jax.named_scope(SCOPE_BLOCK)
def _lead_block(x, layer, config: KimiLinearConfig, segment_ids=None):
    return _dense_ffn(_mixed(x, layer, config, KDA, segment_ids), layer,
                      config)


@jax.named_scope(SCOPE_BLOCK)
def _expert_block(x, layer, config: KimiLinearConfig, kind, train, rng=None,
                  segment_ids=None):
    """-> (x, (router loss, routed rows over ``held_rows_bound``))."""
    return _expert_ffn(_mixed(x, layer, config, kind, segment_ids), layer,
                       config, train, rng)


def _in_two_halves(config: KimiLinearConfig, kind, segment_ids, ffn,
                   **static):
    """``fn(x, layer)`` of one layer as the layer loop calls it, its mixer
    and its feed-forward each a ``layer_block`` of their own: under
    per-layer remat a layer keeps ``x`` and the mixer's output, and its
    backward recomputes and walks the feed-forward, then the mixer — the
    two halves' temporaries never live together (at 16,384 tokens a KDA
    mixer's are 4.6 GiB and a held plan's buffers 0.56 GiB each: together
    they passed the chip, PERF.md section 6, PR 60)."""
    scoped = jax.named_scope(SCOPE_BLOCK)
    mix = layer_block(scoped(_mixed), config, kind=kind,
                      segment_ids=segment_ids)
    ffn = layer_block(scoped(ffn), config, **static)
    return lambda x, layer: ffn(mix(x, layer), layer)


def head_with_aux(params, batch, config: KimiLinearConfig,
                  train: bool = True, rng=None):
    """-> (the head's inputs, router loss summed over the expert layers,
    routed rows over ``held_rows_bound`` summed over them: int32, 0 unless
    the experts held are a subset)."""
    refuse_param_stream(
        "kimi-linear", "a leading dense block and runs of two stacks (kda, "
        "mla) walked period by period")
    seg = segment_ids_of(batch)
    dtype = jnp.dtype(config.dtype)
    x = embed_tokens(params["wte"], batch["input_ids"], dtype)
    x = _in_two_halves(config, KDA, seg, _dense_ffn)(x, params["lead"])
    block_fns = {kind: _in_two_halves(config, kind, seg, _expert_ffn,
                                      train=train, rng=rng)
                 for kind in (KDA, MLA)}
    aux = over = 0
    for i, (pattern, _) in enumerate(config.runs):
        x, (run_aux, run_over) = scan_layer_kinds(
            x, params["blocks"][_run_name(i)], pattern, block_fns)
        aux, over = aux + run_aux, over + run_over
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
    return Head(x, params["lm_head"]), aux, over


def layers_in_order(params, config: KimiLinearConfig):
    """[(kind, that layer's parameters)] of the expert layers, 2.. in the
    stack's order — for diagnostics that write the layers out."""
    take = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)
    layers = []
    for i, (pattern, periods) in enumerate(config.runs):
        stacks = params["blocks"][_run_name(i)]
        for p in range(periods):
            taken = dict.fromkeys(pattern, 0)
            for kind in pattern:
                layers.append((kind, take(stacks[kind], p, taken[kind])))
                taken[kind] += 1
    return layers


def routed_rows(params, batch, config: KimiLinearConfig):
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
        mixed = _mixed(x, layer, config, kind, seg)
        h = _rms_norm(mixed, layer["mlp_norm"], config.norm_eps)
        logits = _routing_logits(layer["moe"],
                                 h.reshape(-1, config.d_model), moe)
        chosen = _route(layer["moe"], logits, moe, True, None).expert_idx
        rows.append(jnp.bincount(chosen.reshape(-1),
                                 length=config.num_experts))
        x, _ = _expert_block(x, layer, config, kind, train=True,
                             segment_ids=seg)
    return jnp.stack(rows)


def count_params(config: KimiLinearConfig) -> int:
    return param_count(partial(init_params, config))


def kimi_linear_model(size: str = "48b-a3b", **overrides) -> Model:
    config = KimiLinearConfig(**{
        **resolve_size(KIMI_LINEAR_SIZES, size, "kimi_linear"), **overrides})
    return held_share_model(
        "kimi-linear", size, config, init_params=init_params,
        logical_specs=logical_specs, head_with_aux=head_with_aux,
        expert_layers=config.expert_layers, expert_matrices=3,
        lookup_params=config.vocab_size * config.d_model,
        serving_needs=(
            "serving needs each sequence's recurrent state (heads x dk x dv, "
            "float32) and three convolution tails for the KDA layers beside "
            "a paged cache of latents for the others, and latent "
            "attention's absorbed form"),
        # every expert's routed rows, layer by layer
        meta={"routed_rows": lambda p, b: routed_rows(p, b, config)})
