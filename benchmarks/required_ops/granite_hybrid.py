"""Required operations of Granite 4.0-H (models/granite_hybrid.py), from
shapes alone: what ``harness/flops.py`` has no function for — layers of two
sublayers each (a Mamba-2 or attention mixer, then routed SwiGLU experts
beside a shared one), mixers built with this chip's heads over one group's
whole B and C, a tied head.  Every function takes ``sizes``, the
configuration's ``model`` block; recompute is never counted.  Named
``granite_hybrid:<function>`` by the configuration (``flops.train``) and by
the roofline metrics (``params.flops`` / ``params.ops``)."""


def _layers(sizes):
    """(Mamba-2 layers, attention layers) of ``layer_kinds``."""
    kinds = sizes["layer_kinds"][:sizes["num_layers"]]
    return kinds.count("M"), kinds.count("A")


def _held(sizes, held, whole):
    """Heads (or experts) built HERE: ``sizes[held]``, or all."""
    return sizes.get(held) or sizes[whole]


def _held_share(sizes):
    """Routed experts a token passes through HERE: ``top_k`` of
    ``num_experts`` of those held; the absent experts' work is not this
    chip's and is not counted."""
    return sizes["top_k"] * _held(sizes, "experts_held", "num_experts") \
        / sizes["num_experts"]


def _mamba_heads(sizes):
    return _held(sizes, "mamba_heads_held", "mamba_num_heads")


def _recurrence_flops_per_token(sizes):
    """Forward, one Mamba-2 layer: per head held the decay of the state,
    the rank-one write ``dt x (x) B`` and the read ``H C``, counted 6 * P *
    N (the per-token recurrence; a chunked form's extra products are how,
    not what)."""
    return 6.0 * _mamba_heads(sizes) * sizes["mamba_head_dim"] \
        * sizes["ssm_state_size"]


def mixer_weights(sizes):
    """(one Mamba-2 mixer's, one attention mixer's) weights that multiply a
    token, as built here: the input projection D * (2 d_inner + 2 N +
    heads) — z, x and dt for the heads held, B and C of the one group
    whole —, the convolution's K taps over its d_inner + 2 N channels and
    the output projection d_inner * D; q, k, v D * (H + 2 KV) hd and the
    output H hd * D for the query and key/value heads held."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    heads = _mamba_heads(sizes)
    d_inner = heads * sizes["mamba_head_dim"]
    conv_ch = d_inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    H = _held(sizes, "attn_heads_held", "num_heads")
    KV = _held(sizes, "kv_heads_held", "num_kv_heads")
    return (D * (d_inner + conv_ch + heads)
            + sizes["conv_kernel"] * conv_ch + d_inner * D,
            D * (H + 2 * KV) * hd + H * hd * D)


def expert_sublayer_weights(sizes):
    """Weights of one expert sublayer that multiply a token: the router D *
    E over all experts, the shared expert 3 D Fs, and the routed experts at
    ``_held_share`` * 3 D F (SwiGLU: three matrices each)."""
    D = sizes["d_model"]
    return D * sizes["num_experts"] + 3 * D * sizes["shared_expert_d_ff"] \
        + _held_share(sizes) * 3 * D * sizes["d_ff"]


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — every
    layer's mixer (:func:`mixer_weights`, of its kind) AND its expert
    sublayer (:func:`expert_sublayer_weights`); once, the head D * V (the
    table is tied: its lookup is no product, its use as the head is one).
    Plus 3 x the recurrence of the Mamba-2 layers, and causal attention of
    the attention layers over S_eff at the width of the heads held: 6 * H
    * hd * S_eff each.  Norms, gates, the softplus, the four scalars and
    the softmax over experts are left out, as everywhere in
    harness/flops.py."""
    n_ssm, n_attn = _layers(sizes)
    ssm, attn = mixer_weights(sizes)
    weights = n_ssm * ssm + n_attn * attn \
        + sizes["num_layers"] * expert_sublayer_weights(sizes) \
        + sizes["d_model"] * sizes["vocab_size"]
    return 6.0 * weights \
        + 3.0 * n_ssm * _recurrence_flops_per_token(sizes) \
        + 6.0 * n_attn * _held(sizes, "attn_heads_held", "num_heads") \
        * sizes["head_dim"] * s_eff


def attention_layer_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the layers that HAVE
    softmax attention (``A`` in ``layer_kinds``) at the width of the query
    heads held, H * hd: a forward call 4 * S * H * hd per token, a backward
    call 8, halved by the causal mask."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * _layers(sizes)[1] * _held(sizes, "attn_heads_held", "num_heads") \
        * sizes["head_dim"] * s_eff


def held_ffn_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.grouped_ffn_flops over the routed rows whose
    expert is held here, every layer having an expert sublayer:
    ``_held_share`` experts per token per layer, three D x F matrices
    each, a forward call 2 * 3 * D * F per row, a backward call twice
    that; ``"gate_up"`` a forward that stops before the output matrix."""
    per_call = {"fwd": 6.0, "gate_up": 4.0, "bwd": 12.0}
    return sum(per_call[p] for p in passes) * tokens * sizes["num_layers"] \
        * _held_share(sizes) * sizes["d_model"] * sizes["d_ff"]


def ssd_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the state-space scan requires for ``tokens`` tokens
    through the Mamba-2 layers, summed over ``passes`` ("fwd": the
    recurrence; "bwd": its gradient, twice the operations).  Bytes are
    what must cross HBM if the state never leaves the chip: a forward call
    reads x (heads held * P) and the one group's B and C (N each) in the
    model's bfloat16 and the float32 step per head, and writes y (heads *
    P); a backward call reads those and y's cotangent and writes the four
    gradients."""
    heads, P = _mamba_heads(sizes), sizes["mamba_head_dim"]
    group = sizes["n_groups"] * sizes["ssm_state_size"]
    inputs = 2 * (heads * P + 2 * group) + 4 * heads
    out = 2 * heads * P
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": inputs + out, "bwd": 2 * inputs + out}
    layers = tokens * _layers(sizes)[0]
    return (layers * _recurrence_flops_per_token(sizes)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))
