"""Required operations of Ouro (models/ouro.py), from shapes alone: what
``harness/flops.py`` has no function for — a stack of ``num_layers`` layers
that a token passes ``total_ut_steps`` times with the same weights, a head
and a one-output gate after every pass.  ``6 * n_params`` counts each
layer once and the embedding's lookup as a product: nearly four times too
low at four passes.  Every function takes ``sizes``, the configuration's
``model`` block; recompute is never counted.  Named ``ouro:<function>`` by
the configuration (``flops.train``) and by the roofline metrics
(``params.flops``)."""


def layer_weights(sizes):
    """One layer's weights that multiply a token: q, k, v ``D (H + 2 KV)
    hd``, the output ``H hd D`` and the SwiGLU's three ``D F`` (51,380,224
    as published); the four norms scale."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    return D * (sizes["num_heads"] + 2 * sizes["num_kv_heads"]) * hd \
        + sizes["num_heads"] * hd * D + 3 * D * sizes["d_ff"]


def applied_weights(sizes):
    """Weights a token is multiplied by, each as often as it is:
    ``total_ut_steps`` times the layers', the head's ``D V`` and the gate's
    ``D`` (a head and a gate after EVERY pass); the embedding is a lookup."""
    D = sizes["d_model"]
    return sizes["total_ut_steps"] * (
        sizes["num_layers"] * layer_weights(sizes)
        + D * sizes["vocab_size"] + D)


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight a use (:func:`applied_weights`),
    plus causal attention of every layer APPLICATION over S_eff at the
    heads' width: 6 * H * hd * S_eff each, ``total_ut_steps * num_layers``
    of them.  Norms, rotary, the sigmoid and the softmax are left out, as
    everywhere in harness/flops.py."""
    applications = sizes["total_ut_steps"] * sizes["num_layers"]
    return 6.0 * applied_weights(sizes) \
        + 6.0 * applications * sizes["num_heads"] * sizes["head_dim"] * s_eff


def attention_layer_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops over the calls the step
    really makes: one a layer APPLICATION, ``total_ut_steps * num_layers``
    a forward pass (``causal_attention_flops`` counts ``num_layers``: a
    quarter of them).  A forward call 4 * S * H * hd per token, a backward
    call 8, halved by the causal mask."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * sizes["total_ut_steps"] * sizes["num_layers"] \
        * sizes["num_heads"] * sizes["head_dim"] * s_eff
