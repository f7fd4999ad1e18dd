"""Operations the algorithm needs, from shapes alone (recompute is never
counted).  Kept with the benchmark so that no later PR can move them.

Every function takes ``sizes``: the configuration's ``model`` block, under
the names of the program's config object (the driver has checked each
against the model that was built, and adds ``n_params`` as counted).  A
configuration names the function its ``mfu_pct`` is computed with under
``"flops": {"train": ...}`` (absent = ``train_flops_per_token``); a
roofline metric names a kernel's under ``params.flops``.  A bare name is a
function of this file; ``<file>:<function>`` is one of
``required_ops/<file>.py``, which a family that nothing here fits brings
as a new file (README, "Adding things")."""
import importlib


def resolve(name):
    """The function a configuration or a metric file names."""
    module, _, function = name.rpartition(":")
    if not module:
        return globals()[function]
    return getattr(importlib.import_module("required_ops." + module),
                   function)


def train_flops_per_token(sizes, s_eff):
    """Forward + backward of a dense causal LM: 6 per parameter for the
    matrix multiplications, plus causal attention — QK^T and PV are
    4*S*D per token per layer forward, 12*S*D with the backward, halved
    by the causal mask: 6*L*D*S_eff.  (bench.py train_flops_per_token,
    with S_eff in the place of S; see harness/datagen.effective_context.)"""
    return 6.0 * sizes["n_params"] \
        + 6.0 * sizes["num_layers"] * sizes["d_model"] * s_eff


def moe_train_flops_per_token(sizes, s_eff):
    """Forward + backward of a decoder whose feed-forward is ``top_k`` of
    ``num_experts`` routed SwiGLU experts.  6 per weight that multiplies a
    token, which is not 6 per parameter: per layer the q, k and v
    projections D*(H + 2*KV)*hd, the output projection H*hd*D, the router
    D*E, and the gate, up and down matrices of the top_k experts a token
    is sent to, top_k*3*D*F (the other E - top_k experts hold parameters
    and do nothing for this token); once, the output head D*V.  An untied
    embedding table is a lookup and is not counted; a tied one is the
    head.  Causal attention as in the dense function with the heads'
    width H*hd in the place of D: 6*L*H*hd*S_eff.  Norms, rotary and the
    softmax over experts are left out, as the dense function leaves out
    norms and biases."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    H, KV = sizes["num_heads"], sizes["num_kv_heads"]
    per_layer = D * (H + 2 * KV) * hd + H * hd * D \
        + D * sizes["num_experts"] + sizes["top_k"] * 3 * D * sizes["d_ff"]
    return 6.0 * (sizes["num_layers"] * per_layer
                  + D * sizes["vocab_size"]) \
        + 6.0 * sizes["num_layers"] * H * hd * s_eff


def causal_attention_flops(tokens, sizes, s_eff, passes):
    """Required FLOPs of the attention products alone, for the kernel's own
    roofline (not MFU): ``tokens`` tokens through ``num_layers`` layers,
    summed over the calls the step really makes.  Unmasked, a forward call
    is QK^T and PV = 4*S*D per token; a backward call is dQ, dK, dV and dP
    = 8*S*D; the causal mask halves both.  ``passes`` lists the calls,
    e.g. ["fwd", "fwd", "bwd"] under full remat, where the forward kernel
    runs twice.

    What it assumes: EVERY one of ``num_layers`` layers attends, at
    ``num_heads * head_dim = d_model``, with one width for scores and
    values and no window.  A family with layers of another kind, a latent
    width, two head counts, a window or a score narrower than its value
    brings its own count in ``required_ops/<file>.py`` and lists its own
    pair of rooflines; ``manifest.lint`` refuses a metric that names this
    function for a cell whose configuration needed such a file for its
    ``mfu_pct`` (read 5.9 x high in one such cell and 3 x low in another
    while it was listed for all)."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) \
        * tokens * sizes["num_layers"] * sizes["d_model"] * s_eff


def grouped_ffn_flops(tokens, sizes, s_eff, passes):
    """Required FLOPs of the routed experts' matrix multiplications alone,
    for a grouped GEMM kernel's roofline: each token goes through
    ``top_k`` SwiGLU experts, three D x F matrices each, so a forward
    call is 2*top_k*3*D*F = 6*top_k*D*F per token per layer, and a
    backward call (dx and dw of each) twice that.  ``"gate_up"`` is a
    forward pass that stops before the output matrix, 4*top_k*D*F: all a
    recompute runs where the backward needs no output of the down product
    (a row weighted by its gate before it).  Rows a kernel pads a group
    with are not required work.  ``s_eff`` plays no part: the signature
    is the one the roofline readers call."""
    per_call = {"fwd": 6.0, "gate_up": 4.0, "bwd": 12.0}
    return sum(per_call[p] for p in passes) * tokens * sizes["num_layers"] \
        * sizes["top_k"] * sizes["d_model"] * sizes["d_ff"]
