"""Required operations of MiniCPM-SALA (models/minicpm_sala.py), from
shapes alone: what ``harness/flops.py`` has no function for — a dense
decoder whose mixers are of two kinds by a published list (attention over
the key blocks each query keeps; Lightning linear attention), an untied
head.  Every function takes ``sizes``, the configuration's ``model`` block;
recompute is never counted.  Named ``minicpm_sala:<function>`` by the
configuration (``flops.train``) and by the roofline metrics
(``params.ops``).

**An attention whose pattern is data is counted at the keys kept, never at
the keys a lowering visits.**  A query at position p of a span of n tokens
(a document's piece inside a row) multiplies, in step 5 of the equations,
the keys ``s <= p`` of its kept blocks: ``p + 1`` of them while it has at
most ``topk`` causal blocks or its span is under ``dense_len``, and ``(topk
- 1) * block_size + p % block_size + 1`` beyond (every kept block whole but
its own).  That is averaged over the spans of the traffic mix — the same
fixed sample ``harness/datagen.effective_context`` averages S_eff over,
found again from the S_eff a function is handed (:func:`_span_lengths`).
"""
import functools
import glob
import json
import os

import numpy as np


def _kinds(sizes):
    """(sparse layers, Lightning layers) of ``layer_kinds``."""
    kinds = sizes["layer_kinds"][:sizes["num_layers"]]
    return kinds.count("S"), kinds.count("L")


@functools.lru_cache(maxsize=None)
def _span_lengths(s_eff):
    """The attention spans S_eff is the mean over, as an array of lengths:
    the traffic file whose ``datagen.effective_context`` is exactly
    ``s_eff``, sampled as that function samples it (its own fixed
    generator, never a run's seed).  A function of required operations is
    handed S_eff and not the mix; two mixes with one S_eff to the last bit
    are one distribution.  Where no file of the benchmark is that mix (an
    unpacked row, whose S_eff is its sequence length; a rehearsal's
    shortened mix): one span of S_eff tokens.  **Where a file is, and this
    copy of the sampling no longer gives its S_eff, that is an error**:
    ``effective_context``'s sample has moved and this one has to follow,
    or the step's required operations and the attend stage's floor would
    be counted over one span of S_eff tokens without a word."""
    from harness import datagen
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in sorted(glob.glob(os.path.join(here, "traffic", "*.json"))):
        with open(path) as f:
            traffic = json.load(f)
        if not traffic.get("segment_ids") \
                or datagen.effective_context(traffic) != s_eff:
            continue
        documents = datagen.Documents(np.random.default_rng(0),
                                      traffic["documents"])
        lens = np.array([n for _ in range(4096)
                         for n, _ in documents.row(traffic["seq_len"])],
                        np.float64)
        if float((lens ** 2).sum() / lens.sum()) != s_eff:
            raise RuntimeError(
                f"{os.path.basename(path)} has S_eff {s_eff} by "
                f"harness/datagen.effective_context and "
                f"{float((lens ** 2).sum() / lens.sum())} by the sample "
                f"of required_ops/minicpm_sala.py: sample here as there")
        return lens.astype(np.int64)
    return np.array([max(1, int(round(s_eff)))], np.int64)


def _per_token(sizes, s_eff, of_position):
    """The mean over the mix's tokens of ``of_position(p, short)``: ``p`` a
    token's position in its span, ``short`` whether the span is under
    ``dense_len`` — two running sums over positions, read at each span's
    length."""
    lens = _span_lengths(s_eff)
    p = np.arange(lens.max())
    upto = {short: np.concatenate([[0.0], np.cumsum(
        np.asarray(of_position(p, short), np.float64))])
        for short in (True, False)}
    short = lens < sizes["dense_len"]
    return float(upto[True][lens[short]].sum()
                 + upto[False][lens[~short]].sum()) / float(lens.sum())


def kept_keys_per_query(sizes, s_eff):
    """Keys a query's softmax runs over in a sparse layer (step 5), a mean
    over the mix's tokens."""
    block, topk = sizes["block_size"], sizes["topk"]

    def kept(p, short):
        if short:
            return p + 1
        return np.where(p // block + 1 > topk,
                        (topk - 1) * block + p % block + 1, p + 1)
    return _per_token(sizes, s_eff, kept)


def scored_windows_per_query(sizes, s_eff):
    """Pooled keys a query scores in a sparse layer (step 2): the windows
    of its document that end at or before it."""
    stride, kernel = sizes["kernel_stride"], sizes["kernel_size"]
    return _per_token(
        sizes, s_eff,
        lambda p, short: np.maximum(0, (p - kernel + 1) // stride + 1))


def sparse_weights(sizes):
    """Weights of one sparse mixer that multiply a token: q, the output
    gate and the output D * H hd each, k and v D * G hd each."""
    D, hd = sizes["d_model"], sizes["head_dim"]
    return 3 * D * sizes["num_heads"] * hd \
        + 2 * D * sizes["num_kv_heads"] * hd


def lightning_weights(sizes):
    """Weights of one Lightning mixer that multiply a token: q, k, v, the
    output gate and the output, D * H hd each."""
    return 5 * sizes["d_model"] * sizes["lightning_heads"] \
        * sizes["lightning_head_dim"]


def _recurrence_flops_per_token(sizes):
    """Forward, one Lightning layer: per head the state's decay, the
    rank-one write ``k^T v`` and the read ``q S``, counted 4 * hd * hd (the
    per-token recurrence; the chunked form's extra products are how, not
    what)."""
    return 4.0 * sizes["lightning_heads"] * sizes["lightning_head_dim"] ** 2


def _attend_flops_per_token(sizes, s_eff):
    """Forward, one sparse layer's step 5: ``q k^T`` and ``P v`` at H * hd
    over the keys kept."""
    return 4.0 * sizes["num_heads"] * sizes["head_dim"] \
        * kept_keys_per_query(sizes, s_eff)


def train_flops_per_token(sizes, s_eff):
    """Forward + backward: 6 per weight that multiplies a token — per
    sparse layer :func:`sparse_weights`, per Lightning layer
    :func:`lightning_weights`, every layer's SwiGLU 3 * D * F, once the
    head D * V (the untied embedding is a lookup).  Plus 3 x the sparse
    layers' attention **over the keys kept**, 3 x the Lightning layers'
    recurrence, and once (no gradient flows through the selection) the
    sparse layers' scores against pooled keys, 2 * H * hd a window.  Norms,
    the gates' sigmoids, the softmaxes, the max-pool and the top-k are
    left out, as everywhere in harness/flops.py."""
    D = sizes["d_model"]
    n_sparse, n_lightning = _kinds(sizes)
    weights = n_sparse * sparse_weights(sizes) \
        + n_lightning * lightning_weights(sizes) \
        + sizes["num_layers"] * 3 * D * sizes["d_ff"] \
        + D * sizes["vocab_size"]
    return 6.0 * weights \
        + 3.0 * n_sparse * _attend_flops_per_token(sizes, s_eff) \
        + 3.0 * n_lightning * _recurrence_flops_per_token(sizes) \
        + n_sparse * 2.0 * sizes["num_heads"] * sizes["head_dim"] \
        * scored_windows_per_query(sizes, s_eff)


def sparse_attend_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) step 5 requires for ``tokens`` tokens through the
    sparse layers, summed over ``passes`` ("fwd": the attention over the
    keys kept; "bwd": its gradient, twice the operations).  Bytes are what
    must cross HBM if scores never leave the chip: a forward call reads q
    (H * hd), k and v (G * hd each) in the model's bfloat16 and the kept
    blocks (G * topk int32), and writes o (H * hd); a backward call reads
    those, o and o's cotangent and writes the three gradients."""
    H, G, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    inputs = 2 * (H + 2 * G) * hd + 4 * G * sizes["topk"]
    out = 2 * H * hd
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": inputs + out,
              "bwd": inputs + 2 * out + 2 * (H + 2 * G) * hd}
    layers = tokens * _kinds(sizes)[0]
    return (layers * _attend_flops_per_token(sizes, s_eff)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))


def lightning_scan_ops(tokens, sizes, s_eff, passes):
    """(FLOPs, bytes) the Lightning recurrence requires for ``tokens``
    tokens through the Lightning layers, summed over ``passes``.  Bytes if
    the state never leaves the chip: a forward call reads q, k and v and
    writes o (H * hd each, bfloat16); a backward call reads q, k, v and o's
    cotangent and writes three gradients."""
    width = 2 * sizes["lightning_heads"] * sizes["lightning_head_dim"]
    flops = {"fwd": 1.0, "bwd": 2.0}
    nbytes = {"fwd": 4 * width, "bwd": 7 * width}
    layers = tokens * _kinds(sizes)[1]
    return (layers * _recurrence_flops_per_token(sizes)
            * sum(flops[p] for p in passes),
            layers * float(sum(nbytes[p] for p in passes)))
