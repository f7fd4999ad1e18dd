"""Required operations of JoyAI-LLM-Flash (models/joyai.py), from shapes
alone: what ``harness/flops.py`` has no function for — latent attention,
whose score head (q, k) and value head differ in width and whose
projections are low-rank pairs; one leading dense layer before the expert
layers; SwiGLU experts of which the chip holds a share; a prediction module
that is one more block and a second pass through the head.  Every function
takes ``sizes``, the configuration's ``model`` block; recompute is never
counted.  Named ``joyai:<function>`` by the configuration
(``flops.train``) and by the roofline metrics (``params.flops``)."""


def _held_share(sizes):
    """Routed experts a token passes through HERE: ``top_k`` of
    ``num_experts`` of those held; the absent experts' work is not this
    chip's and is not counted."""
    held = sizes.get("experts_held") or sizes["num_experts"]
    return sizes["top_k"] * held / sizes["num_experts"]


def _blocks(sizes):
    """(blocks with attention, blocks with experts): the main layers and
    the prediction module's one; the leading dense layer has no experts."""
    mtp = sizes.get("num_mtp_layers", 0)
    return sizes["num_layers"] + mtp, sizes["num_layers"] - 1 + mtp


def _score_and_value_widths(sizes):
    """(H * dk, H * dv): the widths of ``q k^T`` and of ``P v``."""
    H = sizes["num_heads"]
    return (H * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]),
            H * sizes["v_head_dim"])


def attention_weights(sizes):
    """Weights of one layer's latent attention that multiply a token: the
    query pair D * rq + rq * H * (nope + rot), the key/value pair D * (rkv
    + rot) + rkv * H * (nope + vd), the output H * vd * D."""
    D, H = sizes["d_model"], sizes["num_heads"]
    rq, rkv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rot, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    return D * rq + rq * H * (nope + rot) + D * (rkv + rot) \
        + rkv * H * (nope + vd) + H * vd * D


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per
    block (the prediction module's among them) the attention's
    :func:`attention_weights`; the leading layer's dense SwiGLU 3 * D *
    F_dense; per expert block the router D * E over all experts, the shared
    expert 3 * D * Fs and the routed experts at ``_held_share`` * 3 * D * F;
    the module's projection 2 D * D; the head D * V once per prediction
    depth (the untied embedding is a lookup).  Plus causal attention over
    S_eff: ``q k^T`` at the score width and ``P v`` at the value width,
    2 * S * (H dk + H dv) per token forward, three times that with the
    backward, halved by the mask: 3 * H * (dk + dv) * S_eff a block.
    Norms, rotary and the sigmoid over experts are left out, as everywhere
    in harness/flops.py."""
    D = sizes["d_model"]
    mtp = sizes.get("num_mtp_layers", 0)
    n_attn, n_experts = _blocks(sizes)
    experts = D * sizes["num_experts"] \
        + 3 * D * sizes["shared_expert_d_ff"] \
        + _held_share(sizes) * 3 * D * sizes["d_ff"]
    weights = n_attn * attention_weights(sizes) \
        + 3 * D * sizes["d_ff_dense"] + n_experts * experts \
        + mtp * 2 * D * D + (1 + mtp) * D * sizes["vocab_size"]
    return 6.0 * weights \
        + 3.0 * n_attn * sum(_score_and_value_widths(sizes)) * s_eff


def mla_attention_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the blocks that have
    attention (every main layer and the module's block) with ``q k^T`` at
    the score width H * dk and ``P v`` at the value width H * dv: unmasked,
    a forward call is 2 * S * (H dk + H dv) per token, a backward call (dQ,
    dK at the score width, dV, dP at the value width, and the recomputed
    scores) twice that; the causal mask halves both."""
    per_call = {"fwd": 2.0, "bwd": 4.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * _blocks(sizes)[0] * sum(_score_and_value_widths(sizes)) * s_eff


def held_swiglu_ffn_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.grouped_ffn_flops over the routed rows whose expert
    is held here: ``_held_share`` experts per token per expert block, three
    D x F matrices each, a forward call 2 * 3 * D * F per row and a
    backward call twice that."""
    per_call = {"fwd": 6.0, "bwd": 12.0}
    return sum(per_call[p] for p in passes) * tokens * _blocks(sizes)[1] \
        * _held_share(sizes) * sizes["d_model"] * sizes["d_ff"]
