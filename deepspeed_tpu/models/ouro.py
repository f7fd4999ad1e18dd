"""Ouro (huggingface.co/ByteDance/Ouro-2.6B, ``model_type: ouro``; Zhu et
al. 2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): a decoder whose **whole stack of layers runs
``total_ut_steps`` times with the same weights**, with the final norm, the
head and a one-output *exit gate* after every pass, trained on the
expectation of the passes' cross-entropies under the distribution the
gates define of where a token leaves.

With ``T = total_ut_steps``, ``L = num_layers``, ``N(x; g) = x rsqrt(mean
x^2 + eps) g`` (float32 inside), packed ``segment_ids``::

    x^0 = E[ids]
    for t = 1..T:                                   the SAME W_l, g_l
        y = x^{t-1}
        for l = 1..L:
            a = N(y; g1_l);  q, k, v = a W_q, a W_k, a W_v   [.., H, hd]
            q, k = rope(q), rope(k)     rotate-half over all hd, by the
                                        position along the sequence
            o = softmax(q k^T / sqrt(hd), causal, one document) v
            y = y + N(o W_o; g2_l)      a norm on the branch's OUTPUT too
            u = N(y; g3_l)
            y = y + N((silu(u W_gate) * (u W_up)) W_down; g4_l)
        x^t = N(y; g_f)     ONE final norm: x^t feeds the head, the gate
                            and pass t + 1
        nll^t_i = -log softmax(x^t_i W_head)[id_{i+1}]
        lam^t_i = sigmoid(x^t_i . w_g + b_g)
    p^t_i = lam^t_i prod_{j<t} (1 - lam^j_i)  (t < T)
    p^T_i = prod_{j<T} (1 - lam^j_i)
    loss  = mean over scored i of [sum_t p^t_i nll^t_i - beta H(p_i)]

**The loop** is ONE ``lax.scan`` over the ``T L`` layer applications, each
reading layer ``i mod L`` of the stacked parameters it closes over — inside
its remat boundary, so that the backward pass saves the ``T L`` carries and
one index each, and sums a leaf's ``T`` uses into ONE stacked gradient
where it lies (a scan of passes around a scan of layers holds a second
whole stacked gradient beside the accumulator while it adds a pass's).
The four uses are summed in the gradient's own dtype (bf16 under the
benchmark's engine config): the rounding a step's micro-batches already
get in every accumulating cell, over four terms here.  The pass's last
application ends in the final norm (inside the same boundary) and writes
its state where the heads and the gate read it.

**The heads** are ONE ``model.head_nll_sum`` over the passes' states side
by side with ``scored * p^t`` as its weights, whose gradient (the
positions' NLL) reaches the gates; never whole logits, and the head's
``T`` uses summed in float32 in its loop (four calls kept four float32
``[D, V]`` gradients, 1.5 GiB at the published widths, from the forward
pass to the backward).  ``apply_fn``'s logits are the last
pass's (the published forward at ``early_exit_threshold`` 1: no token
leaves early).

Not built: serving (a key/value cache a (pass, layer), leaving at a gate
threshold: the entry points raise), ZeRO-3 and parameter streaming (the
loop indexes the stack it closes over; ``maybe_stream`` gathers a scanned
slice), the paper's second stage (the gate alone on a frozen model).
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import _rms_norm, rope
from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        head_nll_sum, layer_block,
                                        next_token_targets, param_count,
                                        qdot, refuse_param_stream,
                                        resolve_size, segment_ids_of)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_EXIT_GATE, SCOPE_HEAD_LOSS, SCOPE_MLP,
    SCOPE_OUT_PROJ, SCOPE_ROPE, SCOPE_SCORES, count_in_step)

#: what leaves the compiled train step beside the loss (int32, rounded:
#: ``engine.step_load()``): the scored positions' probability of leaving
#: after pass 1..4 summed — the four add up to the scored positions, to
#: their roundings — then those positions, and sum_t t * mass_t (their
#: quotient is the pass a token is expected to leave after).  A model of
#: another ``total_ut_steps`` names as many masses (:func:`exit_mass_name`).
EXIT_MASS_1 = "ouro/exit_mass_1"
EXIT_MASS_2 = "ouro/exit_mass_2"
EXIT_MASS_3 = "ouro/exit_mass_3"
EXIT_MASS_4 = "ouro/exit_mass_4"
SCORED_TOKENS = "ouro/scored_tokens"
EXIT_PASS_TOKENS = "ouro/exit_pass_tokens"
STEP_LOAD = (EXIT_MASS_1, EXIT_MASS_2, EXIT_MASS_3, EXIT_MASS_4,
             SCORED_TOKENS, EXIT_PASS_TOKENS)


def exit_mass_name(t: int) -> str:
    """The step load's name for the mass that leaves after pass ``t``
    (from 1)."""
    return f"ouro/exit_mass_{t}"


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    max_seq_len: int = 65536
    num_layers: int = 48
    #: how often the stack is applied, with the same weights
    total_ut_steps: int = 4
    d_model: int = 2048
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    #: the weight of the exit distribution's entropy in the loss (the
    #: paper's first-stage objective)
    exit_entropy_beta: float = 0.1
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.num_layers < 1 or self.total_ut_steps < 1:
            raise ValueError(
                f"ouro: at least one layer and one pass, not "
                f"{self.num_layers} and {self.total_ut_steps}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"ouro: {self.num_heads} query heads are not whole groups "
                f"of {self.num_kv_heads} KV heads")

    @property
    def applications(self) -> int:
        """Layer applications a token passes through."""
        return self.total_ut_steps * self.num_layers


OURO_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=3,
                 total_ut_steps=4, d_model=32, num_heads=4, num_kv_heads=4,
                 head_dim=8, d_ff=64),
    # huggingface.co/ByteDance/Ouro-2.6B config.json: the defaults above.
    # 2,667,974,657 parameters whole; one chip trains the first 12 layers,
    # four times over (benchmarks/configs)
    "2.6b": dict(),
}


# ------------------------------------------------------------- parameters
def init_params(config: OuroConfig, rng) -> dict:
    """Seeded.  Assumed (the published config has no ``initializer_range``
    among the catalog's keys): every matrix normal of std 0.02, norm
    weights 1, the gate's weight normal 0.02 and its bias 0 (every pass
    starts at ``lam`` = 1/2)."""
    D, V, L, F = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_ff)
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    norm = partial(jax.random.normal, dtype=jnp.float32)
    k = iter(jax.random.split(rng, 10))
    std = 0.02
    return {
        "wte": norm(next(k), (V, D)) * std,
        "blocks": {
            "attn_norm": jnp.ones((L, D)),
            "wq": norm(next(k), (L, D, H * hd)) * std,
            "wk": norm(next(k), (L, D, KV * hd)) * std,
            "wv": norm(next(k), (L, D, KV * hd)) * std,
            "wo": norm(next(k), (L, H * hd, D)) * std,
            "attn_out_norm": jnp.ones((L, D)),
            "mlp_norm": jnp.ones((L, D)),
            "w_gate": norm(next(k), (L, D, F)) * std,
            "w_up": norm(next(k), (L, D, F)) * std,
            "w_down": norm(next(k), (L, F, D)) * std,
            "mlp_out_norm": jnp.ones((L, D)),
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
        "exit_gate": {"w": norm(next(k), (D,)) * std,
                      "b": jnp.zeros(())},
    }


def logical_specs(config: OuroConfig) -> dict:
    col, row = P(None, None, "model"), P(None, "model", None)
    return {
        "wte": P("model", None),
        "blocks": {"attn_norm": P(), "wq": col, "wk": col, "wv": col,
                   "wo": row, "attn_out_norm": P(), "mlp_norm": P(),
                   "w_gate": col, "w_up": col, "w_down": row,
                   "mlp_out_norm": P()},
        "final_norm": P(),
        "lm_head": P(None, "model"),
        "exit_gate": {"w": P(), "b": P()},
    }


# ------------------------------------------------------------------ a layer
def _add_branch(y, out, scale, eps):
    """``y + N(out; scale)``: a branch joins the residual stream through a
    norm of its own (the sandwich)."""
    return y + _rms_norm(out, scale, eps)


def _ends_a_pass(i, config: OuroConfig):
    """Whether application ``i`` is a pass's last: the final norm follows."""
    return i % config.num_layers == config.num_layers - 1


@jax.named_scope(SCOPE_BLOCK)
def _block(y, layer, config: OuroConfig, segment_ids):
    """One layer: both branches end in a norm of their own (the sandwich)."""
    B, S, _ = y.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    eps = config.norm_eps
    with jax.named_scope(SCOPE_ATTN):
        a = _rms_norm(y, layer["attn_norm"], eps)
        q = qdot(a, layer["wq"]).reshape(B, S, H, hd)
        k = qdot(a, layer["wk"]).reshape(B, S, KV, hd)
        v = qdot(a, layer["wv"]).reshape(B, S, KV, hd)
        with jax.named_scope(SCOPE_ROPE):
            q = rope(q, config.rope_theta)
            k = rope(k, config.rope_theta)
        with jax.named_scope(SCOPE_SCORES):
            o = causal_attention(q, k, v, impl=config.attention_impl,
                                 segment_ids=segment_ids)
        o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
        with jax.named_scope(SCOPE_OUT_PROJ):
            y = _add_branch(y, qdot(o.reshape(B, S, H * hd), layer["wo"]),
                            layer["attn_out_norm"], eps)
    with jax.named_scope(SCOPE_MLP):
        u = _rms_norm(y, layer["mlp_norm"], eps)
        gated = jax.nn.silu(qdot(u, layer["w_gate"])) \
            * qdot(u, layer["w_up"])
        return _add_branch(y, qdot(gated, layer["w_down"]),
                           layer["mlp_out_norm"], eps)


def _application(y, i, config: OuroConfig, blocks, final_norm, segment_ids):
    """Application ``i`` of ``T L``: layer ``i mod L`` of the stack — taken
    HERE, inside the remat boundary ``layer_block`` draws around this
    function, so that what a step of the loop saves is its carry and ``i``,
    not a copy of the layer — and, where it is a pass's last, the final
    norm."""
    layer = jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i % config.num_layers,
                                           keepdims=False), blocks)
    y = _block(y, layer, config, segment_ids)
    return lax.cond(
        _ends_a_pass(i, config),
        lambda y: _rms_norm(y, final_norm, config.norm_eps),
        lambda y: y, y)


def exit_states(params, batch, config: OuroConfig):
    """-> ``x^1 .. x^T`` [B, T, S, D]: the state after each pass, final
    norm applied — the head's input, the gate's and the next pass's.  A
    sequence's passes lie side by side, so that ``[B, T S, D]`` is the
    same bytes: the heads take them as one longer sequence a row."""
    refuse_param_stream(
        "ouro", "one stack that a loop of total_ut_steps x num_layers "
                "applications indexes")
    T, L = config.total_ut_steps, config.num_layers
    blocks = params["blocks"]
    x = embed_tokens(params["wte"], batch["input_ids"],
                     jnp.dtype(config.dtype))
    B, S, D = x.shape
    count_in_step(layer_loops={"ouro": {
        "name": "ouro", "passes": T, "layers": L, "applications": T * L,
        # the bytes every pass reads again, and what the backward pass is
        # handed: the carry into every application
        "shared_param_bytes": sum(
            a.size * a.dtype.itemsize for a in jax.tree.leaves(blocks)),
        "saved_carry_bytes": T * L * x.size * x.dtype.itemsize}})
    # the "layer" layer_block hands on is the application's index
    apply = layer_block(_application, config, blocks=blocks,
                        final_norm=params["final_norm"],
                        segment_ids=segment_ids_of(batch))

    def step(carry, i):
        y, states = carry
        y = apply(y, i)
        # a pass's slot holds its last write: the normed state
        return (y, lax.dynamic_update_index_in_dim(states, y, i // L, 1)), \
            None

    (_, states), _ = lax.scan(
        step, (x, jnp.zeros((B, T, S, D), x.dtype)),
        jnp.arange(T * L, dtype=jnp.int32))
    return states


# ------------------------------------------------------- gates and the loss
def exit_log_probabilities(states, gate):
    """``log p^t`` [B, T, S] float32 of the distribution over the pass a
    token leaves after: ``lam^t = sigmoid(x^t . w_g + b_g)``; ``p^t = lam^t
    prod_{j<t} (1 - lam^j)`` and the last pass takes what is left, ``p^T =
    prod_{j<T} (1 - lam^j)`` (its own gate decides nothing).  In logs:
    ``log lam = log_sigmoid(z)``, ``log (1 - lam) = log_sigmoid(-z)``."""
    z = jnp.einsum("btsd,d->bts", states, gate["w"].astype(states.dtype),
                   preferred_element_type=jnp.float32) \
        + gate["b"].astype(jnp.float32)
    stays = jax.nn.log_sigmoid(-z)
    survived = jnp.cumsum(stays, axis=1) - stays        # sum over j < t
    leaves = jax.nn.log_sigmoid(z).at[:, -1].set(0.0)
    return leaves + survived


def loss_with_load(params, batch, config: OuroConfig, rng=None):
    """-> (the loss: the mean over the scored positions of ``sum_t p^t
    nll^t - beta H(p)``; the step's load, int32: :data:`STEP_LOAD`'s
    names).  The ``T`` heads are ONE ``head_nll_sum`` over the passes'
    states side by side (``[B, T S, D]``, weights ``scored p^t``): the
    head's ``T`` uses are summed in its loop's float32 carry and rounded
    once, and one float32 ``[D, V]`` waits for the backward pass, not
    ``T``."""
    del rng
    T = config.total_ut_steps
    states = exit_states(params, batch, config)
    B, _, S, D = states.shape
    targets, scored = next_token_targets(batch)
    scored = scored.astype(jnp.float32)[:, None, :]             # [B, 1, S]
    with jax.named_scope(SCOPE_EXIT_GATE):
        log_p = exit_log_probabilities(states, params["exit_gate"])
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=1, keepdims=True)     # [B, 1, S]
        masses = jnp.sum(lax.stop_gradient(p) * scored, axis=(0, 2))
        n_scored = jnp.sum(scored)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        expected = head_nll_sum(
            states.reshape(B, T * S, D),
            params["lm_head"].astype(states.dtype), jnp.tile(targets, (1, T)),
            (scored * p).reshape(B, T * S), False, "exits")
    with jax.named_scope(SCOPE_EXIT_GATE):
        loss = (expected - config.exit_entropy_beta
                * jnp.sum(entropy * scored)) / jnp.maximum(n_scored, 1.0)
        whole = lambda a: jnp.round(a).astype(jnp.int32)
        load = {exit_mass_name(t + 1): whole(masses[t]) for t in range(T)}
        load[SCORED_TOKENS] = whole(n_scored)
        load[EXIT_PASS_TOKENS] = whole(
            jnp.sum(masses * jnp.arange(1, T + 1, dtype=jnp.float32)))
    return loss, load


def exit_logits(params, batch, config: OuroConfig):
    """-> (every pass's logits [B, T, S, V], whole; ``p`` [B, T, S]
    float32) — for evaluation and the tests; a training loss never forms
    them."""
    states = exit_states(params, batch, config)
    w = params["lm_head"].astype(states.dtype)
    return states @ w, jnp.exp(
        exit_log_probabilities(states, params["exit_gate"]))


def forward(params, batch, config: OuroConfig):
    """The last pass's logits: the published forward where no token leaves
    early (``early_exit_threshold`` 1)."""
    return Head(exit_states(params, batch, config)[:, -1],
                params["lm_head"]).logits()


def count_params(config: OuroConfig) -> int:
    return param_count(partial(init_params, config))


def applied_params(config: OuroConfig) -> int:
    """Weights that multiply a token, each as often as it does: ``T`` times
    a layer's seven matrices, the head and the gate's vector; the
    embedding is a lookup and the norms scale."""
    D, hd = config.d_model, config.head_dim
    layer = D * (config.num_heads + 2 * config.num_kv_heads) * hd \
        + config.num_heads * hd * D + 3 * D * config.d_ff
    return config.total_ut_steps * (
        config.num_layers * layer + D * config.vocab_size + D)


def ouro_model(size: str = "2.6b", **overrides) -> Model:
    config = OuroConfig(**{**resolve_size(OURO_SIZES, size, "ouro"),
                           **overrides})
    n_params = count_params(config)
    applied = applied_params(config)

    def with_load(params, batch, rng=None):
        return loss_with_load(params, batch, config, rng)

    def no_serving(what):
        def refuse(*_, **__):
            raise NotImplementedError(
                f"ouro: {what} is not built — serving a looped stack "
                f"needs a key/value cache a (pass, layer) and a step that "
                f"yields a token after as many passes as its exit gate "
                f"asks for (ROADMAP)")
        return refuse

    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=lambda p, b, rng=None: forward(p, b, config),
        loss_fn=lambda p, b, rng=None: with_load(p, b, rng)[0],
        # nothing is left out of the loss, so no ``step_counts``: what
        # leaves the step beside it is its load (``engine.step_load()``)
        loss_with_counts_fn=with_load,
        logical_specs=logical_specs(config),
        # 6 a weight a use: not 6 a parameter, which a looped stack
        # multiplies total_ut_steps times
        flops_per_token=6.0 * applied,
        meta={"name": f"ouro-{size}", "n_params": n_params,
              "applied_params": applied,
              "ut_steps": config.total_ut_steps,
              "exit_logits": lambda p, b: exit_logits(p, b, config)},
        init_cache_fn=no_serving("init_cache"),
        prefill_fn=no_serving("prefill"),
        decode_fn=no_serving("decode"),
        verify_fn=no_serving("verify"),
    )
