"""Operations the algorithm needs, from shapes alone (recompute is never
counted).  Kept with the benchmark so that no later PR can move them."""


def train_flops_per_token(n_params, num_layers, d_model, s_eff):
    """Forward + backward of a dense causal LM: 6 per parameter for the
    matrix multiplications, plus causal attention — QK^T and PV are
    4*S*D per token per layer forward, 12*S*D with the backward, halved
    by the causal mask: 6*L*D*S_eff.  (bench.py train_flops_per_token,
    with S_eff in the place of S; see harness/datagen.effective_context.)"""
    return 6.0 * n_params + 6.0 * num_layers * d_model * s_eff


def causal_attention_flops(tokens, num_layers, d_model, s_eff, passes):
    """Required FLOPs of the attention products alone, for the kernel's own
    roofline (not MFU): ``tokens`` tokens through ``num_layers`` layers,
    summed over the calls the step really makes.  Unmasked, a forward call
    is QK^T and PV = 4*S*D per token; a backward call is dQ, dK, dV and dP
    = 8*S*D; the causal mask halves both.  ``passes`` lists the calls,
    e.g. ["fwd", "fwd", "bwd"] under full remat, where the forward kernel
    runs twice."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) \
        * tokens * num_layers * d_model * s_eff
