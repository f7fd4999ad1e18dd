"""Required operations of Phi-4-mini-flash (models/phi4flash.py), from
shapes alone: what ``harness/flops.py`` has no function for — a dense
decoder whose mixers are of five kinds laid out by the depth alone (Mamba-1,
differential attention under a window, in full, and on another layer's keys
and values, gated memory units); a selective scan that is elementwise work;
attention whose every head is two softmax maps at a score width of
``head_dim`` and a value width of twice that.  Every function takes
``sizes``, the configuration's ``model`` block; recompute is never counted.
Named ``phi4flash:<function>`` by the configuration (``flops.train``) and by
the roofline metrics (``params.flops`` / ``params.ops``)."""
from required_ops.laguna import window_keys_times_two


def layer_counts(sizes):
    """{kind: layers}: ``l`` even — Mamba-1 up to ``L/2``, gated memory
    units after; ``l`` odd — windowed below ``L/2``, the full layer at
    ``L/2 + 1``, cross layers after."""
    quarter = sizes["num_layers"] // 4
    return {"mamba": quarter + 1, "swa": quarter, "full": 1,
            "gmu": quarter - 1, "cross": quarter - 1}


def _recurrence_flops_per_token(sizes):
    """Forward, one Mamba-1 layer: per channel and state the decay of the
    state, the write ``(delta u) B`` and the read ``h C``, counted 6 a
    (channel, state) — the multiply-adds of the recurrence; the ``exp`` of
    the decay and the softplus of the step are left out, as norms are."""
    return 6.0 * sizes["mamba_expand"] * sizes["d_model"] \
        * sizes["mamba_d_state"]


def _map_width(sizes):
    """What ``d_model`` is to plain attention's 4 * S * D a token: a layer
    is ``num_heads`` softmax maps (two a differential head), each ``q
    k^T`` at ``head_dim`` and ``P V`` at ``2 head_dim`` — 2 * (hd + 2 hd)
    a key, so 4 * S * (1.5 * H * hd)."""
    return 1.5 * sizes["num_heads"] * sizes["head_dim"]


def multiplying_weights(sizes):
    """Weights that multiply a token, the whole model's: per layer the MLP
    3 * D * F; per Mamba-1 layer W_in D * 2 d_inner, the convolution's
    taps, W_x d_inner * (R + 2 N), W_dt R * d_inner and W_out d_inner * D;
    per gated memory unit 2 * D * d_inner; per attention layer with its
    own keys D * (H + 2 KV) hd and H hd * D, per cross layer W_q and W_o
    alone; the tied table once, as the head."""
    D, F = sizes["d_model"], sizes["d_ff"]
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    d_in = sizes["mamba_expand"] * D
    N, R = sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    n = layer_counts(sizes)
    mamba = 2 * D * d_in + sizes["mamba_d_conv"] * d_in \
        + d_in * (R + 2 * N) + R * d_in + d_in * D
    own = D * (H + 2 * KV) * hd + H * hd * D
    cross = 2 * D * H * hd
    return sizes["num_layers"] * 3 * D * F + n["mamba"] * mamba \
        + n["gmu"] * 2 * D * d_in + (n["swa"] + n["full"]) * own \
        + n["cross"] * cross + D * sizes["vocab_size"]


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token
    (:func:`multiplying_weights`), plus 3 x the recurrence of the Mamba-1
    layers, plus attention: three times the forward's 4 * keys *
    :func:`_map_width` a layer, over S_eff / 2 keys a query in the full and
    the cross layers (6 * width * S_eff) and over the window's in the
    windowed ones — at the closed form for ONE span of S_eff positions
    (``mfu_pct`` has no sample of the traffic; by Jensen an upper bound of
    the sample's mean, on a term that is under 1% of the count here).
    Norms, gates, the lambda combine, softplus and exp are left out, as
    everywhere in harness/flops.py."""
    n = layer_counts(sizes)
    width = _map_width(sizes)
    return 6.0 * multiplying_weights(sizes) \
        + 3.0 * n["mamba"] * _recurrence_flops_per_token(sizes) \
        + 6.0 * (n["full"] + n["cross"]) * width * s_eff \
        + 6.0 * n["swa"] * width * window_keys_times_two(
            s_eff, sizes["sliding_window"])


def diff_full_attention_flops(tokens, sizes, s_eff, passes):
    """As harness/flops.causal_attention_flops for the calls of the full
    layer and of the cross layers (the ``ds_flash_*`` kernels: two maps a
    layer, ``num_heads / 2`` heads each, score width ``head_dim``, value
    width twice that): unmasked a forward call is 4 * S * width a token,
    a backward call twice that; the causal mask halves both."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    n = layer_counts(sizes)
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * (n["full"] + n["cross"]) * _map_width(sizes) * s_eff


def diff_window_attention_flops(tokens, sizes, keys_times_two, passes):
    """The same for the windowed layers (the ``ds_flash_win_*`` kernels).
    ``keys_times_two`` is NOT S_eff: it is twice the mean number of keys a
    query must attend over, inside its document AND its window
    (layer_metrics/readers/window_roofline.py hands it over from the
    traffic's own sample); keys a kernel visits beyond those are not
    required work."""
    per_call = {"fwd": 4.0, "bwd": 8.0}
    return 0.5 * sum(per_call[p] for p in passes) * tokens \
        * layer_counts(sizes)["swa"] * _map_width(sizes) * keys_times_two


def selective_scan_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the selective scan requires for ``tokens`` tokens
    through the Mamba-1 layers, summed over ``passes`` ("fwd": the
    recurrence; "bwd": its gradient, twice the operations).  Bytes are
    what must cross HBM if the state never leaves the chip: a forward call
    reads u and the raw step (d_inner each) and B and C (N each) in the
    model's bfloat16 and writes y (d_inner); a backward call reads those
    and y's cotangent and writes the four gradients.  The FLOPs are priced
    at the matrix unit's peak by the reader (``peaks.json`` has no vector
    peak), so the floor is the memory's: the kernels are vector-unit work
    and cannot reach it."""
    d_in = sizes["mamba_expand"] * sizes["d_model"]
    N = sizes["mamba_d_state"]
    inputs = 2 * (2 * d_in + 2 * N)
    out = 2 * d_in
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": inputs + out, "bwd": 2 * inputs + out}
    layers = tokens * layer_counts(sizes)["mamba"]
    return (layers * _recurrence_flops_per_token(sizes)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))
