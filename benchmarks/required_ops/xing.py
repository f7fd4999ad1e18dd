"""Required operations of Xing4.0 (models/xing.py), from shapes alone: the
DeepSeek-V3 layout that required_ops/joyai.py counts — latent attention
whose score head and value head differ in width, leading dense layers,
SwiGLU experts of which the chip holds a share, a prediction module that is
one more block and a second pass through the head — under a residual of n
streams whose mixing is counted here.  Every function takes ``sizes``, the
configuration's ``model`` block; recompute is never counted.  Named
``xing:<function>`` by the configuration (``flops.train``) and by the
stream's roofline metric (``params.ops``).  The kernels' shares of this
configuration (flash, grouped GEMM) are priced by ``joyai:``'s functions,
which read the same keys and hold for one leading dense layer, as the
cut has."""
from required_ops.joyai import (  # noqa: F401 (attention_weights: as there)
    _held_share, _score_and_value_widths, attention_weights)


def _blocks(sizes):
    """(blocks with attention, leading dense blocks, blocks with
    experts): the main layers and the prediction module's one."""
    mtp = sizes.get("num_mtp_layers", 0)
    dense = sizes["num_dense_layers"]
    return sizes["num_layers"] + mtp, dense, sizes["num_layers"] - dense + mtp


def sublayer_calls(sizes):
    """Hyper-connected sublayers a forward pass runs: an attention and a
    feed-forward a block (``tracing.hc_calls`` sums to the same)."""
    return 2 * _blocks(sizes)[0]


def stream_multiply_adds(sizes):
    """Multiply-adds a token of ONE hyper-connected sublayer's stream
    work, forward: the projection ``r Phi`` n C (2 n + n^2), the read n C,
    the write (n^2 + n) C.  The Sinkhorn sweeps (40 n^2 divisions), the
    flattened norm and the sigmoids are left out, as norms are
    everywhere."""
    n, C = sizes["hc_mult"], sizes["d_model"]
    return n * C * (2 * n + n * n) + (n * n + 2 * n) * C


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per
    block (the prediction module's among them) the attention's
    :func:`attention_weights`; a leading layer's dense SwiGLU 3 * D *
    F_dense; per expert block the router D * E over all experts, the shared
    expert 3 * D * Fs and the routed experts at ``_held_share`` * 3 * D *
    F; the module's projection 2 D * D; the head D * V once per prediction
    depth (the untied embedding is a lookup) — and 6 per multiply-add of
    the stream (:func:`stream_multiply_adds`, two sublayers a block: each
    forward product has two in the backward, its operands both being
    differentiated).  Plus causal attention over S_eff as
    required_ops/joyai.py: 3 * H * (dk + dv) * S_eff a block."""
    D = sizes["d_model"]
    mtp = sizes.get("num_mtp_layers", 0)
    n_attn, n_dense, n_experts = _blocks(sizes)
    experts = D * sizes["num_experts"] \
        + 3 * D * sizes["shared_expert_d_ff"] \
        + _held_share(sizes) * 3 * D * sizes["d_ff"]
    weights = n_attn * attention_weights(sizes) \
        + n_dense * 3 * D * sizes["d_ff_dense"] + n_experts * experts \
        + mtp * 2 * D * D + (1 + mtp) * D * sizes["vocab_size"] \
        + sublayer_calls(sizes) * stream_multiply_adds(sizes)
    return 6.0 * weights \
        + 3.0 * n_attn * sum(_score_and_value_widths(sizes)) * s_eff


def hc_stream_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the stream's read and write require for ``tokens``
    tokens through every hyper-connected sublayer, summed over ``passes``.
    The coefficients' pass is not in it (scope ``hc/coeff`` is not in the
    metric's time either).  Bytes are what must cross HBM in the stream's
    bfloat16 if each of the two is one pass: forward, the read takes the
    stream in (n C) and writes ``h`` (C), the write takes the stream and
    ``y`` in (n C + C) and writes the stream (n C): (3 n + 2) C elements a
    token and sublayer.  Backward, the write's takes the stream's
    cotangent, the stream and ``y`` in (2 n C + C) and writes the stream's
    and ``y``'s cotangents (n C + C); the read's takes ``h``'s cotangent
    and the stream in (C + n C) and adds into the stream's cotangent
    (n C written; the sum with the write's share is a fusion's epilogue in
    the floor): (5 n + 3) C.  FLOPs: 2 (n^2 + 2 n) C forward, twice that
    backward — three orders under the bytes' floor, which is the bound."""
    del s_eff
    n, C = sizes["hc_mult"], sizes["d_model"]
    elements = {"fwd": 3 * n + 2, "bwd": 5 * n + 3}
    flops = {"fwd": 2.0, "bwd": 4.0}
    calls = tokens * sublayer_calls(sizes)
    return (calls * (n * n + 2 * n) * C * sum(flops[p] for p in passes),
            calls * 2.0 * C * sum(elements[p] for p in passes))
