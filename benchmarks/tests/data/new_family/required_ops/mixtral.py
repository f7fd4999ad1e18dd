"""Required operations of the Mixtral family, as a family brings its own
when no function of ``harness/flops.py`` fits it (this one is counted
apart from ``moe_train_flops_per_token`` so that the test can hold the
two to one hand count).  A configuration names it
``"flops": {"train": "mixtral:train_flops_per_token"}``."""


def train_flops_per_token(sizes, s_eff):
    """6 per weight a token is multiplied by — attention projections, the
    router, ``top_k`` of the experts, the head; not the other experts and
    not the embedding table — plus causal attention, 6 * layers * heads'
    width * S_eff."""
    heads = sizes["num_heads"] * sizes["head_dim"]
    kv = sizes["num_kv_heads"] * sizes["head_dim"]
    D = sizes["d_model"]
    weights = sizes["num_layers"] * (
        D * heads + 2 * D * kv + heads * D + D * sizes["num_experts"]
        + sizes["top_k"] * 3 * D * sizes["d_ff"]) + D * sizes["vocab_size"]
    return 6.0 * weights + 6.0 * sizes["num_layers"] * heads * s_eff
