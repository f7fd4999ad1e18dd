"""Required operations of Qwen3-Next (models/qwen3_next.py), from shapes
alone: what ``harness/flops.py`` has no function for — layers of two kinds,
a recurrence that is not attention, experts of which the chip holds a
share.  Every function takes ``sizes``, the configuration's ``model``
block; recompute is never counted.  Named ``qwen3_next:<function>`` by the
configuration (``flops.train``) and by the roofline metrics
(``params.flops`` / ``params.ops``)."""


def _kinds(sizes):
    """(linear layers, full-attention layers)."""
    full = sizes["num_layers"] // sizes["full_attention_interval"]
    return sizes["num_layers"] - full, full


def _held_share(sizes):
    """Routed experts a token passes through HERE: ``top_k`` of
    ``num_experts`` of those held; the absent experts' work is not this
    chip's and is not counted."""
    held = sizes.get("experts_held") or sizes["num_experts"]
    return sizes["top_k"] * held / sizes["num_experts"]


def _recurrence_flops_per_token(sizes):
    """Forward, one linear layer: per value head the decay of the state,
    S^T k, the rank-one write and S^T q, counted 6 * dk * dv (the
    per-token recurrence; the chunked form's extra products are how, not
    what)."""
    return 6.0 * sizes["linear_num_value_heads"] \
        * sizes["linear_key_head_dim"] * sizes["linear_value_head_dim"]


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per
    linear layer the q, k, v, z projection D * (2 Hk dk + 2 Hv dv), the
    decay and write-strength projection D * 2 Hv, the convolution's K taps
    over its channels and the output projection Hv dv * D; per full layer
    the query-and-gate projection D * 2 H hd, k and v D * 2 KV hd and the
    output H hd * D; per layer of either kind the router D * E over all
    experts, the shared expert 3 D Fs + D and the routed experts at
    ``_held_share`` * 3 D F; once, the head D * V (the untied embedding is
    a lookup).  Plus 3 x the recurrence of the linear layers, and causal
    attention of the full layers over S_eff at the heads' width: 6 * H *
    hd * S_eff each.  Norms, rotary, gates and the softmax over experts
    are left out, as everywhere in harness/flops.py."""
    D = sizes["d_model"]
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    n_linear, n_full = _kinds(sizes)
    conv_ch = 2 * Hk * dk + Hv * dv
    linear = D * (conv_ch + Hv * dv) + D * 2 * Hv \
        + sizes["linear_conv_kernel_dim"] * conv_ch + Hv * dv * D
    full = D * 2 * H * hd + D * 2 * KV * hd + H * hd * D
    experts = D * sizes["num_experts"] \
        + 3 * D * sizes["shared_expert_d_ff"] + D \
        + _held_share(sizes) * 3 * D * sizes["d_ff"]
    weights = n_linear * linear + n_full * full \
        + sizes["num_layers"] * experts + D * sizes["vocab_size"]
    return 6.0 * weights \
        + 3.0 * n_linear * _recurrence_flops_per_token(sizes) \
        + 6.0 * n_full * H * hd * s_eff


def full_layer_attention_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the layers that HAVE
    softmax attention (one in ``full_attention_interval``) at the heads'
    width H * hd: a forward call 4 * S * H * hd per token, a backward
    call 8, halved by the causal mask."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * _kinds(sizes)[1] * sizes["num_heads"] * sizes["head_dim"] * s_eff


def held_ffn_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.grouped_ffn_flops over the routed rows whose
    expert is held here: ``_held_share`` experts per token per layer."""
    per_call = {"fwd": 6.0, "bwd": 12.0}
    return sum(per_call[p] for p in passes) * tokens * sizes["num_layers"] \
        * _held_share(sizes) * sizes["d_model"] * sizes["d_ff"]


def delta_rule_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the gated delta rule requires for ``tokens`` tokens
    through the linear layers, summed over ``passes`` ("fwd": the
    recurrence; "bwd": its gradient, twice the operations).  Bytes are
    what must cross HBM if the state never leaves the chip: a forward
    call reads q and k (Hk * dk each) and v (Hv * dv) in the model's
    bfloat16 and the two float32 scalars per value head, and writes o (Hv
    * dv); a backward call reads those and o's cotangent and writes the
    five gradients."""
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    inputs = 2 * (2 * Hk * dk + Hv * dv) + 4 * 2 * Hv
    out = 2 * Hv * dv
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": inputs + out, "bwd": 2 * inputs + out}
    layers = tokens * _kinds(sizes)[0]
    return (layers * _recurrence_flops_per_token(sizes)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))
