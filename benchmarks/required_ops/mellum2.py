"""Required operations of Mellum 2 (models/mellum.py), from shapes alone:
what ``harness/flops.py`` has no function for — attention layers of two
kinds at one head count, one of them under a causal window, the full one
last in its period; every layer a sparse one, all its experts present
(spread over the chips of the ``expert`` axis: a chip's tokens pass through
``top_k`` experts wherever they live); the whole head; and the bytes the
exchange has to move.  Every function takes ``sizes``, the configuration's
``model`` block; recompute is never counted.  Named ``mellum2:<function>``
by the configuration (``flops.train``) and by the roofline metrics
(``params.flops``)."""


def layer_kinds(sizes):
    """(full layers, sliding layers): layer l is a full one where ``l %
    full_attention_interval`` is the interval's last."""
    full = sizes["num_layers"] // sizes["full_attention_interval"]
    return full, sizes["num_layers"] - full


def attention_weights(sizes):
    """Weights of one attention layer that multiply a token: W_q and W_o D
    * H hd each, W_k and W_v D * KV hd each."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    return 2 * D * sizes["num_heads"] * hd \
        + 2 * D * sizes["num_kv_heads"] * hd


def window_keys_times_two(span, window):
    """``2 * sum_{i=1..span} min(i, window) / span``: what S_eff is to a
    causal layer (twice the mean number of keys a query attends over), for
    ONE span of ``span`` positions under a window."""
    if span <= window:
        return span + 1.0
    return (window * (window + 1.0) + 2.0 * window * (span - window)) / span


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per layer
    :func:`attention_weights`, the router D * E over all experts and
    ``top_k`` experts of 3 * D * F; the head D * V (the untied embedding is
    a lookup).  Plus attention: ``q k^T`` and ``P v`` at H * hd, 4 * H * hd
    per key forward, three times that with the backward, over S_eff / 2
    keys a query in a full layer (6 * H hd * S_eff) and over the window's
    in a sliding one.

    This function is handed S_eff alone (``mfu_pct`` has no sample of the
    traffic), so the sliding layers are counted at the closed form for ONE
    span of S_eff positions (:func:`window_keys_times_two`): by Jensen an
    upper bound of the sample's mean (layer_metrics/readers/
    window_roofline.py counts the sample's own keys for the kernels'
    rooflines) on a term that is a few percent of the count.  Norms,
    rotary and the softmax over experts are left out, as everywhere in
    harness/flops.py; so is everything the exchange does (it multiplies
    nothing)."""
    D, H, hd = sizes["d_model"], sizes["num_heads"], sizes["head_dim"]
    n_full, n_sliding = layer_kinds(sizes)
    layer = attention_weights(sizes) + D * sizes["num_experts"] \
        + sizes["top_k"] * 3 * D * sizes["d_ff"]
    weights = sizes["num_layers"] * layer + D * sizes["vocab_size"]
    return 6.0 * weights + 6.0 * n_full * H * hd * s_eff \
        + 6.0 * n_sliding * H * hd * window_keys_times_two(
            s_eff, sizes["sliding_window"])


def full_layer_attention_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the full layers alone
    (the ``ds_flash_*`` calls) at ``num_heads * head_dim``: unmasked, a
    forward call is 4 * S * H hd per token, a backward call twice that; the
    causal mask halves both."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * layer_kinds(sizes)[0] * sizes["num_heads"] * sizes["head_dim"] \
        * s_eff


def window_layer_attention_flops(tokens, sizes, keys_times_two, passes):
    """The same for the sliding layers (the ``ds_flash_win_*`` calls).
    ``keys_times_two`` is NOT S_eff: it is twice the mean number of keys a
    query must attend over, inside its document AND its window
    (layer_metrics/readers/window_roofline.py hands it over from the
    traffic's own sample); keys a kernel visits beyond those are not
    required work."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * layer_kinds(sizes)[1] * sizes["num_heads"] * sizes["head_dim"] \
        * keys_times_two


def swiglu_ffn_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.grouped_ffn_flops over the rows a chip's experts
    receive: under the exchange every chip sends ``tokens * top_k`` rows
    and, summed over the chips, as many arrive, so a chip's required work
    is that of ITS OWN tokens' rows — ``top_k`` experts a token a layer,
    three D x F matrices each, a forward call 2 * 3 * D * F per row and a
    backward call twice that; ``"gate_up"`` is the recompute of an
    exchanged layer, which multiplies no output matrix: 2 * 2 * D * F.
    Rows of padding (a group's last tile, the bound's empty part) are not
    required."""
    per_call = {"fwd": 6.0, "gate_up": 4.0, "bwd": 12.0}
    return sum(per_call[p] for p in passes) * tokens * sizes["num_layers"] \
        * sizes["top_k"] * sizes["d_model"] * sizes["d_ff"]


def exchange_wire_bytes(tokens, sizes, chips, passes=6, itemsize=2):
    """Bytes ONE chip must put on the wire for ``tokens`` of its own
    tokens: each of its ``tokens * top_k`` routed rows a layer is ``d_model
    * itemsize`` bytes, ``(chips - 1) / chips`` of them go to another chip
    under even routing, and a row crosses ``passes`` times a layer (out
    and back in the forward pass, in the recompute and in the backward
    pass).  Padding of any kind (a buffer's empty part, a re-tiled copy)
    is not required bytes."""
    return float(passes) * tokens * sizes["num_layers"] * sizes["top_k"] \
        * sizes["d_model"] * itemsize * (chips - 1) / chips
