"""Required operations of Laguna-S-2.1 (models/laguna.py), from shapes
alone: what ``harness/flops.py`` has no function for — attention layers of
two kinds with different head counts in one stack, one of them under a
causal window; a per-head gate; one leading dense layer before the expert
layers; SwiGLU experts of which the chip holds a share.  Every function
takes ``sizes``, the configuration's ``model`` block; recompute is never
counted.  Named ``laguna:<function>`` by the configuration
(``flops.train``) and by the roofline metrics (``params.flops``)."""


def _held_share(sizes):
    """Routed experts a token passes through HERE: ``top_k`` of
    ``num_experts`` of those held; the absent experts' work is not this
    chip's and is not counted."""
    held = sizes.get("experts_held") or sizes["num_experts"]
    return sizes["top_k"] * held / sizes["num_experts"]


def layer_kinds(sizes):
    """(full layers, sliding layers): layer l is a full one where ``l %
    full_attention_interval == 0``, the leading dense layer among them."""
    full = -(-sizes["num_layers"] // sizes["full_attention_interval"])
    return full, sizes["num_layers"] - full


def attention_weights(sizes, heads):
    """Weights of one attention layer of ``heads`` query heads that
    multiply a token: W_q and W_o D * H hd each, W_k and W_v D * KV hd
    each, the gate D * H."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    return 2 * D * heads * hd + 2 * D * sizes["num_kv_heads"] * hd \
        + D * heads


def window_keys_times_two(span, window):
    """``2 * sum_{i=1..span} min(i, window) / span``: what S_eff is to a
    causal layer (twice the mean number of keys a query attends over), for
    ONE span of ``span`` positions under a window."""
    if span <= window:
        return span + 1.0
    return (window * (window + 1.0) + 2.0 * window * (span - window)) / span


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per
    attention layer :func:`attention_weights` at its kind's head count; the
    leading layer's dense SwiGLU 3 * D * F_dense; per expert layer the
    router D * E over all experts, the shared expert 3 * D * Fs and the
    routed experts at ``_held_share`` * 3 * D * F; the head D * V (the
    untied embedding is a lookup).  Plus attention: ``q k^T`` and ``P v``
    at H * hd, 4 * H * hd per key forward, three times that with the
    backward, over S_eff / 2 keys a query in a full layer (6 * H hd *
    S_eff) and over the window's in a sliding one.

    This function is handed S_eff alone (``mfu_pct`` has no sample of the
    traffic), so the sliding layers are counted at the closed form for ONE
    span of S_eff positions (:func:`window_keys_times_two`): by Jensen an
    upper bound of the sample's mean — 451 keys a query against the 393 the
    traffic's own sample gives at S 8192 (layer_metrics/readers/
    window_roofline.py counts those), 15% over on a term that is about 4%
    of the count, so ``mfu_pct`` reads about 0.6% (relative) high.  Norms,
    rotary, the gate's sigmoid and the softmax over experts are left out,
    as everywhere in harness/flops.py."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    Hf, Hs = sizes["num_heads_full"], sizes["num_heads_sliding"]
    n_full, n_sliding = layer_kinds(sizes)
    experts = D * sizes["num_experts"] \
        + 3 * D * sizes["shared_expert_d_ff"] \
        + _held_share(sizes) * 3 * D * sizes["d_ff"]
    weights = n_full * attention_weights(sizes, Hf) \
        + n_sliding * attention_weights(sizes, Hs) \
        + 3 * D * sizes["d_ff_dense"] \
        + (sizes["num_layers"] - 1) * experts + D * sizes["vocab_size"]
    return 6.0 * weights + 6.0 * n_full * Hf * hd * s_eff \
        + 6.0 * n_sliding * Hs * hd * window_keys_times_two(
            s_eff, sizes["sliding_window"])


def full_layer_attention_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the full layers alone
    (the ``ds_flash_*`` calls) at their own width ``num_heads_full *
    head_dim``: unmasked, a forward call is 4 * S * H hd per token, a
    backward call twice that; the causal mask halves both."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * layer_kinds(sizes)[0] * sizes["num_heads_full"] \
        * sizes["head_dim"] * s_eff


def window_layer_attention_flops(tokens, sizes, keys_times_two, passes):
    """The same for the sliding layers (the ``ds_flash_win_*`` calls) at
    ``num_heads_sliding * head_dim``.  ``keys_times_two`` is NOT S_eff: it
    is twice the mean number of keys a query must attend over, inside its
    document AND its window (layer_metrics/readers/window_roofline.py hands
    it over from the traffic's own sample); keys a kernel visits beyond
    those are not required work."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * layer_kinds(sizes)[1] * sizes["num_heads_sliding"] \
        * sizes["head_dim"] * keys_times_two


def held_swiglu_ffn_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.grouped_ffn_flops over the routed rows whose expert
    is held here: ``_held_share`` experts per token per expert layer, three
    D x F matrices each, a forward call 2 * 3 * D * F per row and a
    backward call twice that."""
    per_call = {"fwd": 6.0, "bwd": 12.0}
    return sum(per_call[p] for p in passes) * tokens \
        * (sizes["num_layers"] - 1) * _held_share(sizes) \
        * sizes["d_model"] * sizes["d_ff"]
