"""Required operations of Nemotron-H (models/nemotron_h.py), from shapes
alone: what ``harness/flops.py`` has no function for — layers that are one
mixer each, of three kinds; a state-space recurrence; un-gated experts of
which the chip holds a share.  Every function takes ``sizes``, the
configuration's ``model`` block; recompute is never counted.  Named
``nemotron_h:<function>`` by the configuration (``flops.train``) and by the
roofline metrics (``params.flops`` / ``params.ops``)."""

KINDS = ("M", "E", "*")


def _layers(sizes):
    """(Mamba-2 layers, expert layers, attention layers)."""
    pattern = sizes["hybrid_override_pattern"]
    repeats = sizes["num_layers"] // len(pattern)
    return tuple(repeats * pattern.count(kind) for kind in KINDS)


def _held_share(sizes):
    """Routed experts a token passes through HERE: ``top_k`` of
    ``num_experts`` of those held; the absent experts' work is not this
    chip's and is not counted."""
    held = sizes.get("experts_held") or sizes["num_experts"]
    return sizes["top_k"] * held / sizes["num_experts"]


def _recurrence_flops_per_token(sizes):
    """Forward, one Mamba-2 layer: per head the decay of the state, the
    rank-one write ``dt x (x) B`` and the read ``H C``, counted 6 * P * N
    (the per-token recurrence; the chunked form's extra products are how,
    not what)."""
    return 6.0 * sizes["mamba_num_heads"] * sizes["mamba_head_dim"] \
        * sizes["ssm_state_size"]


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per
    Mamba-2 layer the input projection D * (2 d_inner + 2 G N + heads),
    the convolution's K taps over its d_inner + 2 G N channels and the
    output projection d_inner * D; per attention layer q, k, v D * (H + 2
    KV) hd and the output H hd * D; per expert layer the router D * E over
    all experts, the shared expert 2 D Fs and the routed experts at
    ``_held_share`` * 2 D F (two matrices each: un-gated); once, the head
    D * V (the untied embedding is a lookup).  Plus 3 x the recurrence of
    the Mamba-2 layers, and causal attention of the attention layers over
    S_eff at the heads' width: 6 * H * hd * S_eff each.  Norms, gates,
    the softplus and the sigmoid over experts are left out, as everywhere
    in harness/flops.py."""
    D = sizes["d_model"]
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    d_inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv_ch = d_inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    n_ssm, n_experts, n_attn = _layers(sizes)
    ssm = D * (d_inner + conv_ch + sizes["mamba_num_heads"]) \
        + sizes["conv_kernel"] * conv_ch + d_inner * D
    attn = D * (H + 2 * KV) * hd + H * hd * D
    experts = D * sizes["num_experts"] \
        + 2 * D * sizes["shared_expert_d_ff"] \
        + _held_share(sizes) * 2 * D * sizes["d_ff"]
    weights = n_ssm * ssm + n_attn * attn + n_experts * experts \
        + D * sizes["vocab_size"]
    return 6.0 * weights \
        + 3.0 * n_ssm * _recurrence_flops_per_token(sizes) \
        + 6.0 * n_attn * H * hd * s_eff


def attention_layer_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the layers that HAVE
    softmax attention (``*`` in the pattern) at the heads' width H * hd:
    a forward call 4 * S * H * hd per token, a backward call 8, halved by
    the causal mask."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * _layers(sizes)[2] * sizes["num_heads"] * sizes["head_dim"] * s_eff


def held_relu2_ffn_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.grouped_ffn_flops over the routed rows whose
    expert is held here, for experts of TWO matrices (up, down; no gate
    matrix): ``_held_share`` experts per token per expert layer, a forward
    call 2 * 2 * D * F per row and a backward call twice that."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return sum(per_call[p] for p in passes) * tokens * _layers(sizes)[1] \
        * _held_share(sizes) * sizes["d_model"] * sizes["d_ff"]


def ssd_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the state-space scan requires for ``tokens`` tokens
    through the Mamba-2 layers, summed over ``passes`` ("fwd": the
    recurrence; "bwd": its gradient, twice the operations).  Bytes are
    what must cross HBM if the state never leaves the chip: a forward call
    reads x (heads * P) and B and C (G * N each) in the model's bfloat16
    and the float32 step per head, and writes y (heads * P); a backward
    call reads those and y's cotangent and writes the four gradients."""
    heads, P = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    group = sizes["n_groups"] * sizes["ssm_state_size"]
    inputs = 2 * (heads * P + 2 * group) + 4 * heads
    out = 2 * heads * P
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": inputs + out, "bwd": 2 * inputs + out}
    layers = tokens * _layers(sizes)[0]
    return (layers * _recurrence_flops_per_token(sizes)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))
