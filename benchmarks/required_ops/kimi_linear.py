"""Required operations of Kimi-Linear (models/kimi_linear.py), from shapes
alone: what ``harness/flops.py`` has no function for — mixers of two kinds
by a published list (a delta rule whose decay is a vector a key channel;
latent attention without a query latent), one leading dense layer before
the expert layers, SwiGLU experts of which the chip holds a share.  Every
function takes ``sizes``, the configuration's ``model`` block; recompute
is never counted.  Named ``kimi_linear:<function>`` by the configuration
(``flops.train``) and by the roofline metrics (``params.flops`` /
``params.ops``)."""


def _kinds(sizes):
    """(KDA layers, latent-attention layers) of ``layer_kinds``."""
    kinds = sizes["layer_kinds"][:sizes["num_layers"]]
    return kinds.count("K"), kinds.count("M")


def _held_share(sizes):
    """Routed experts a token passes through HERE: ``top_k`` of
    ``num_experts`` of those held; the absent experts' work is not this
    chip's and is not counted."""
    held = sizes.get("experts_held") or sizes["num_experts"]
    return sizes["top_k"] * held / sizes["num_experts"]


def _score_and_value_widths(sizes):
    """(H * dk, H * dv): the widths of ``q k^T`` and of ``P v``."""
    H = sizes["num_heads"]
    return (H * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]),
            H * sizes["v_head_dim"])


def kda_weights(sizes):
    """Weights of one KDA mixer that multiply a token: q, k, v D * 3 H hd,
    the taps K * 3 H hd, the two low-rank pairs 2 (D r + r H hd), the
    write strength D * H, the output H hd * D."""
    D, H, hd = sizes["d_model"], sizes["kda_num_heads"], sizes["kda_head_dim"]
    r = sizes["kda_gate_rank"]
    return D * 3 * H * hd + sizes["short_conv_kernel_size"] * 3 * H * hd \
        + 2 * (D * r + r * H * hd) + D * H + H * hd * D


def mla_weights(sizes):
    """Weights of one latent-attention mixer that multiply a token: the
    query D * H (nope + rot), one matrix; the key/value pair D * (rkv +
    rot) + rkv * H * (nope + vd); the output H vd * D."""
    D, H, rkv = sizes["d_model"], sizes["num_heads"], sizes["kv_lora_rank"]
    nope, rot, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    return D * H * (nope + rot) + D * (rkv + rot) \
        + rkv * H * (nope + vd) + H * vd * D


def _recurrence_flops_per_token(sizes):
    """Forward, one KDA layer: per head the decay of the state (a
    multiplication an entry: the decay is a vector, a row of the state
    each its own), S^T k, the rank-one write and S^T q, counted 7 * dk *
    dv (the per-token recurrence; the chunked form's extra products are
    how, not what)."""
    return 7.0 * sizes["kda_num_heads"] * sizes["kda_head_dim"] ** 2


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per KDA
    layer :func:`kda_weights`, per latent-attention layer
    :func:`mla_weights`; the leading layer's dense SwiGLU 3 * D * F_dense;
    per expert layer the router D * E over all experts, the shared expert
    3 * D * Fs and the routed experts at ``_held_share`` * 3 * D * F; once,
    the head D * V (the untied embedding is a lookup).  Plus 3 x the
    recurrence of the KDA layers, and causal attention of the
    latent-attention layers over S_eff: ``q k^T`` at the score width and
    ``P v`` at the value width, 3 * H * (dk + dv) * S_eff a layer.  Norms,
    gates' sigmoids and the sigmoid over experts are left out, as
    everywhere in harness/flops.py."""
    D = sizes["d_model"]
    n_kda, n_mla = _kinds(sizes)
    experts = D * sizes["num_experts"] \
        + 3 * D * sizes["shared_expert_d_ff"] \
        + _held_share(sizes) * 3 * D * sizes["d_ff"]
    weights = n_kda * kda_weights(sizes) + n_mla * mla_weights(sizes) \
        + 3 * D * sizes["d_ff_dense"] \
        + (sizes["num_layers"] - 1) * experts + D * sizes["vocab_size"]
    return 6.0 * weights \
        + 3.0 * n_kda * _recurrence_flops_per_token(sizes) \
        + 3.0 * n_mla * sum(_score_and_value_widths(sizes)) * s_eff


def mla_attention_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the layers that HAVE
    softmax attention (``M`` of ``layer_kinds``) with ``q k^T`` at the
    score width H * dk and ``P v`` at the value width H * dv: unmasked, a
    forward call is 2 * S * (H dk + H dv) per token, a backward call twice
    that; the causal mask halves both."""
    per_call = {"fwd": 2.0, "bwd": 4.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * _kinds(sizes)[1] * sum(_score_and_value_widths(sizes)) * s_eff


def kda_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the delta rule with a decay a key channel requires
    for ``tokens`` tokens through the KDA layers, summed over ``passes``
    ("fwd": the recurrence; "bwd": its gradient, twice the operations).
    Bytes are what must cross HBM if the state never leaves the chip: a
    forward call reads q, k and v (H * hd each) in the model's bfloat16,
    the float32 log-decay **at H * hd wide** and the float32 write
    strength a head, and writes o (H * hd); a backward call reads those
    and o's cotangent and writes the five gradients."""
    H, hd = sizes["kda_num_heads"], sizes["kda_head_dim"]
    inputs = 2 * 3 * H * hd + 4 * H * hd + 4 * H
    out = 2 * H * hd
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": inputs + out, "bwd": 2 * inputs + out}
    layers = tokens * _kinds(sizes)[0]
    return (layers * _recurrence_flops_per_token(sizes)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))
