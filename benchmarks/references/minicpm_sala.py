"""Plain reference for MiniCPM-SALA (huggingface.co/openbmb/MiniCPM-SALA,
``model_type: minicpm_sala``; the sparse layers are InfLLM-V2 — MiniCPM4,
arXiv:2506.07900, arXiv:2509.24663 — the linear ones Lightning Attention-2,
arXiv:2401.04658): forward pass and loss in ``jax.numpy`` and float32 — no
kernel, no chunked scan, no slots or columns, no remat, no mixed precision.
Gradients are ``jax.grad`` of :func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * w`` (eps ``norm_eps``).  ``h_0 = scale_emb *
E[ids]``; each sublayer ``h <- h + c f(N(h))`` with ``c = scale_depth /
sqrt(depth_scale_layers)`` (the published depth, 32, whatever is built);
``MLP(u) = (silu(u W_gate) * u W_up) W_down``; logits ``= N(h) / (d_model /
dim_model_base) W_head``; the loss is the cross-entropy of the positions
whose next token is in the same document.  Layer ``l`` has the mixer
``layer_kinds[l]``:

``L`` — Lightning attention (H = ``lightning_heads`` heads of hd =
``lightning_head_dim``): ``q, k, v = u W_q, u W_k, u W_v``; ``q <- N(q;
w_q)``, ``k <- N(k; w_k)`` over each head's hd; rotary (``rope_theta``,
channel i paired with i + hd/2, positions the sequence's) on q and k; per
head, token by token, a state ``S`` [hd, hd] that is zero before a
document's first token:

    S_t = exp(-s_h) S_{t-1} + k_t^T v_t       o_t = (q_t / sqrt(hd)) S_t
    s_h = 2^(-8 h / H), h = 1..H

written as the literal recurrence, a ``lax.scan`` over tokens; ``out =
(sigmoid(u W_g) * N(o; w_o)) W_o`` with that norm over the joined H * hd.

``S`` — attention over the blocks a query picks (H = ``num_heads`` query
heads, G = ``num_kv_heads`` key/value heads, R = H / G; query head h reads
key/value head h // R): q, k, v and the two norms as above, nothing
rotated.  For a query at position p of a document of n tokens that starts
at a (positions from the document's first token):

1. ``K_j = mean(k[a + stride j : a + stride j + kernel])`` for the windows
   with ``stride j + kernel <= n``;
2. ``P[h, j] = softmax_j(q_h . K_j / sqrt(hd))`` over the windows with
   ``stride j + kernel - 1 <= p``; ``A[g, j] = sum of P over the heads of
   g`` (all zero where no window has ended yet);
3. ``score[g, b] = max(A[g, j])`` over ``j = per b - reach .. per b + per
   - 1`` that exist (``per = block / stride``, ``reach = kernel / stride -
   1``: the windows that touch block b);
4. blocks ``b < init_blocks`` and ``b > p // block - window_size / block``
   score +inf; of the blocks ``b <= p // block`` the ``topk`` highest are
   kept, the lower index first among equals (a stable sort); every causal
   block is kept where ``n < dense_len``;
5. ``o_h = softmax_s(q_h . k_s / sqrt(hd)) v_s`` over the keys s of the
   document with ``s <= t`` whose block is kept; ``out = (sigmoid(u W_g) *
   o) W_o``.

Steps 1-4 are computed on ``stop_gradient`` of q and k, a block of queries
at a time, each query gathering its own document's windows: a per-query
``[windows, hd]`` gather, a per-query mask over all keys.

Departures from the source, each also in the configuration's ``assumed``:
the source's kernels estimate step 2's normaliser from coarser windows
where this is the exact softmax; the source's inference code applies
``dense_len`` to a whole request, here it is per document of a packed
row.

It runs on the engine's own parameter tree (``layers = {"00": {...}, ...}``),
one sequence at a time through the mixers, a block of tokens at a time
through the MLP and the head.

``matmul_dtype`` is for the control only: every matrix product's operands
(projections, the selection's and the attention's scores, the maps times
the values, what enters the recurrence, the head) are rounded to that type
first (float32 accumulation); the recurrence's state stays float32.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats, of a step of
#: 16,384 tokens.  From two readings on the chip at the cell's own size
#: (PERF.md section 2, PR 64): the engine moved the first loss by -1.8e-5
#: to +2.0e-5 over 33 runs at 33 seeds (a spread about zero of 8e-6); the
#: reference with every product's operands rounded to fp8 e4m3 by -7.1e-4
#: to +7.2e-4 over 9 seeds, the smallest 6.9e-5 (the bf16 control: 0 to
#: 5.7e-6, inside, as the precision the configuration states has to be).
#: 5e-5 is 2.5 times the engine's largest reading and 0.73 of the fp8
#: control's smallest — and no limit has more room above it than the
#: control's seeds leave: its reading is signed about zero too (a spread of
#: 4.5e-4), so some seed reads as near zero as one likes.  What holds fp8
#: out in every seed is TOKEN_NLL_RMS_ATOL; this limit holds it out in 9
#: of 9.  It is far under the accepted cells' 1e-3 to 2.5e-3 because this
#: model's logits start at std 0.08 (normal 0.02 weights under the muP
#: divisor of 16: the loss is ln 9181 + 0.003) and a mean over 16,384
#: tokens averages a token's rounding down by 128; a rehearsal's 128
#: tokens stay inside because its ``dim_model_base`` keeps the divisor
#: (the configuration's ``rehearsal``).
LOSS_ATOL = 5e-5

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From readings on the chip at the cell's size (PERF.md
#: section 2, PR 64): the engine read 8.0e-4 to 8.6e-4 over 33 runs at
#: steps 12 to 16 (8.6e-4 to 8.7e-4 at step 0; the reference rounded to
#: bf16, at step 0: 5.2e-4 to 5.5e-4); the program with a WRONG selection
#: planted in every row that chooses (the scores' sign turned, or nothing
#: chosen beside the forced blocks: scripts/sparse_selection_check.py
#: --plant) 4.1e-3 to 4.4e-3; the reference rounded to fp8 e4m3 2.7e-2 to
#: 3.1e-2.  2.5e-3 is 2.9 times the engine's largest reading (which moves
#: by 6% over its seeds), 0.61 of the planted selections' smallest and
#: 0.09 of the fp8 control's.  A selection wrong in one row of five reads
#: 1.9e-3 to 2.0e-3 and passes: rows are held by the script below.
TOKEN_NLL_RMS_ATOL = 2.5e-3

#: the least share of (token, key/value head) rows whose kept blocks are
#: exactly this reference's, of the rows that have a choice to make (more
#: causal blocks than ``topk`` in a document of ``dense_len`` or more):
#: what scripts/sparse_selection_check.py holds the program's selection to
#: on the chip.  A top-k is discrete and seeded weights score blocks
#: nearly alike, so rounding moves the 64th block: the program (bfloat16 q
#: and k, float32 scores) read 0.919 to 0.947 over eight seeds and the
#: reference rounded to bf16 0.906 to 0.940, a row that differs differing
#: in one block of its 64 (1.03 to 1.05 on average); rounded to fp8 e4m3
#: 0.16 to 0.31, 1.5 to 1.9 blocks a row (PERF.md section 2, PR 64).
#: 0.85 lies between, 0.07 under the program's lowest.
SELECTION_AGREEMENT_MIN = 0.85

#: the same share when this file's steps 1-4 (:func:`select`) are given
#: the PROGRAM's own bfloat16 q and k: the projections' rounding is then
#: out of it, and a row that differs is the selection's own doing — its
#: slots and columns, its ``lax.top_k`` — or two scores that float32 sums
#: of another shape put the other way round.  On a CPU, whose float32
#: products are float32, 0.99996 at the cell's size (1 row of 24,704); on
#: the chip, whose ``highest`` products are passes of bfloat16, 0.959 to
#: 0.963 over four seeds (one block a row).  Held by the same script;
#: 0.93 is 0.03 under the chip's lowest; a selection planted wrong in one
#: row of five leaves 0.74 of the rows as the float32 reference has them.
SELECTION_SAME_INPUTS_MIN = 0.93

QUERY_BLOCK = 128       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through the MLP, or the head, at a time
STATE_BLOCK = 64        # tokens of the recurrence between kept states


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _matmul(matmul_dtype):
    if matmul_dtype is None:
        return jnp.matmul, (lambda a: a)
    rounded = lambda a: a.astype(matmul_dtype).astype(jnp.float32)
    return (lambda a, b: jnp.matmul(rounded(a), rounded(b))), rounded


def documents(seg):
    """(first position, length) of each token's document, [S] each."""
    S = seg.shape[0]
    idx = jnp.arange(S)
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    last = jnp.concatenate([seg[1:] != seg[:-1], jnp.ones((1,), bool)])
    start = jax.lax.cummax(jnp.where(first, idx, 0))
    end = jax.lax.cummin(jnp.where(last, idx + 1, S), reverse=True)
    return start, end - start


def select(q, k, seg, sizes, mm=jnp.matmul, q_block=QUERY_BLOCK):
    """Steps 1-4 for one sequence: q [S, H, hd], k [S, G, hd], seg [S] ->
    (blocks [G, S, topk] int32 ascending, -1 where unused; how many [G,
    S])."""
    S, H, hd = q.shape
    G = k.shape[1]
    R = H // G
    stride, kernel, block = (sizes["kernel_stride"], sizes["kernel_size"],
                             sizes["block_size"])
    per, reach = block // stride, kernel // stride - 1
    local = sizes["window_size"] // block
    J, Bn = S // stride, -(-S // block)
    K = min(sizes["topk"], Bn)
    q, k = jax.lax.stop_gradient((q, k))
    start, length = documents(seg)
    # the mean of the kernel keys from every position on (zeros past the end)
    padded = jnp.concatenate([k, jnp.zeros((kernel,) + k.shape[1:])])
    means = sum(padded[i:i + S] for i in range(kernel)) / kernel
    j = jnp.arange(J)
    # which windows touch block b: [Bn, reach + per], and whether each exists
    touch = per * jnp.arange(Bn)[:, None] - reach \
        + jnp.arange(reach + per)[None, :]
    exists = (touch >= 0) & (touch < J)
    b_idx = jnp.arange(Bn)

    def some_queries(args):
        qb, t, a, n = args                              # [Q, H, hd], [Q] x 3
        p = t - a
        ended = (stride * j[None, :] + kernel <= n[:, None]) \
            & (stride * j[None, :] + kernel - 1 <= p[:, None])    # [Q, J]
        where = jnp.clip(a[:, None] + stride * j[None, :], 0, S - 1)
        pooled = means[where]                                 # [Q, J, G, hd]
        scores = mm(qb.reshape(-1, G, R, hd), pooled.transpose(0, 2, 3, 1)
                    ) / jnp.sqrt(float(hd))                   # [Q, G, R, J]
        seen = ended[:, None, None, :]
        top = jnp.max(jnp.where(seen, scores, -jnp.inf), -1, keepdims=True)
        e = jnp.where(seen, jnp.exp(scores - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        den = jnp.sum(e, -1, keepdims=True)
        A = jnp.sum(e / jnp.where(den > 0, den, 1.0), axis=2)    # [Q, G, J]
        by_block = jnp.max(jnp.where(
            exists, A[:, :, jnp.clip(touch, 0, J - 1)], 0.0), axis=-1)
        own = (p // block)[:, None, None]                     # [Q, 1, 1]
        causal = b_idx <= own
        forced = (b_idx < sizes["init_blocks"]) | (b_idx > own - local)
        score = jnp.where(causal, jnp.where(forced, jnp.inf, by_block),
                          -jnp.inf)                           # [Q, G, Bn]
        order = jnp.argsort(-score, axis=-1, stable=True)[..., :K]
        kept = jnp.take_along_axis(score, order, axis=-1) > -jnp.inf
        chosen = jnp.sort(jnp.where(kept, order, Bn), axis=-1)
        return jnp.where(chosen < Bn, chosen, -1), jnp.sum(kept, axis=-1)

    Q = _fit(S, q_block)
    split = lambda x: x.reshape((-1, Q) + x.shape[1:])
    blocks, count = jax.lax.map(some_queries, (
        split(q), split(jnp.arange(S)), split(start), split(length)))
    blocks = blocks.reshape(S, G, K).transpose(1, 0, 2)
    if K < sizes["topk"]:
        blocks = jnp.pad(blocks, ((0, 0), (0, 0), (0, sizes["topk"] - K)),
                         constant_values=-1)
    return blocks.astype(jnp.int32), count.reshape(S, G).T


def attend(q, k, v, blocks, seg, sizes, mm=jnp.matmul, keep=lambda f: f,
           q_block=QUERY_BLOCK):
    """Step 5 for one sequence: q [S, H, hd], k, v [S, G, hd], blocks [G,
    S, topk] -> [S, H, hd]."""
    S, H, hd = q.shape
    G = k.shape[1]
    R = H // G
    block = sizes["block_size"]
    Bn = -(-S // block)
    start, length = documents(seg)
    kT = k.transpose(1, 2, 0)                                 # [G, hd, S]
    vT = v.transpose(1, 0, 2)                                 # [G, S, hd]
    s_idx = jnp.arange(S)

    @keep
    def some_queries(args):
        qb, blk, t, a, n, seg_q = args
        Q = t.shape[0]
        # the kept blocks as a mask over the document's blocks
        spot = jnp.where(blk >= 0, blk, Bn)                   # [G, Q, topk]
        kept = jnp.zeros((G, Q, Bn + 1), bool).at[
            jnp.arange(G)[:, None, None], jnp.arange(Q)[None, :, None],
            spot].set(True)[..., :Bn]
        kept = kept | (n < sizes["dense_len"])[None, :, None]
        of_key = jnp.clip((s_idx[None, :] - a[:, None]) // block, 0, Bn - 1)
        seen = jnp.take_along_axis(
            kept, jnp.broadcast_to(of_key[None], (G, Q, S)), axis=-1)
        seen = seen & ((seg_q[:, None] == seg[None, :])
                       & (s_idx[None, :] <= t[:, None]))[None]
        qg = qb.reshape(Q, G, R, hd).transpose(1, 2, 0, 3)    # [G, R, Q, hd]
        scores = mm(qg, kT[:, None]) / jnp.sqrt(float(hd))    # [G, R, Q, S]
        probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf),
                               axis=-1)
        return mm(probs, vT[:, None]).transpose(2, 0, 1, 3).reshape(
            Q, H, hd)

    Q = _fit(S, q_block)
    split = lambda x: x.reshape((-1, Q) + x.shape[1:])
    out = jax.lax.map(some_queries, (
        split(q), blocks.reshape(G, -1, Q, blocks.shape[-1]).transpose(
            1, 0, 2, 3), split(s_idx), split(start), split(length),
        split(seg)))
    return out.reshape(S, H, hd)


def slopes(heads):
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1) / heads)


def rotary(x, theta):
    """x [S, H, hd]: channel i turns with channel i + hd/2 at ``theta^(-i /
    (hd/2))`` radians a position."""
    S, _, hd = x.shape
    angles = jnp.arange(S)[:, None] \
        * theta ** (-jnp.arange(hd // 2) / (hd // 2))[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=False,
                     upto=None):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, every position's negative log
    likelihood of the next token [b, S] and which positions are scored
    (:func:`token_losses`); ``upto="selection"``: instead, the first sparse
    layer's kept blocks [b, G, S, topk] (:func:`selection`).
    Differentiable in ``params``; ``remat`` keeps only each layer's, each
    block of tokens', each block of queries' and every ``STATE_BLOCK``-th
    token's inputs for the gradient (the same arithmetic)."""
    keep = jax.checkpoint if remat else (lambda fn: fn)
    f32 = lambda a: a.astype(jnp.float32)
    mm, rounded = _matmul(matmul_dtype)
    b, S = ids.shape
    T = b * S
    eps, D = sizes["norm_eps"], sizes["d_model"]
    c = sizes["scale_depth"] / np.sqrt(sizes["depth_scale_layers"])
    block = _fit(T, block)
    s_block = _fit(S, STATE_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    @keep
    def mlp(x, p):
        @keep
        def some_tokens(xb):
            u = _norm(xb, p["mlp_norm"], eps)
            return mm(jax.nn.silu(mm(u, f32(p["w_gate"])))
                      * mm(u, f32(p["w_up"])), f32(p["w_down"]))
        return x + c * jax.lax.map(
            some_tokens, x.reshape(-1, block, D)).reshape(x.shape)

    def projections(x, p, heads, kv_heads, hd):
        """-> u [T, D], q [b, S, heads, hd], k, v [b, S, kv_heads, hd], q
        and k normalised a head."""
        u = _norm(x, p["attn_norm"], eps)
        q = _norm(mm(u, f32(p["w_q"])).reshape(b, S, heads, hd),
                  p["q_norm"], eps)
        k = _norm(mm(u, f32(p["w_k"])).reshape(b, S, kv_heads, hd),
                  p["k_norm"], eps)
        return u, q, k, mm(u, f32(p["w_v"])).reshape(b, S, kv_heads, hd)

    def recurrence(q, k, v, first):
        """One sequence, token by token: q, k, v [S, H, hd], first [S]."""
        H, hd = q.shape[1:]
        decay = jnp.exp(-slopes(H))[:, None, None]

        def token(state, xs):
            q_t, k_t, v_t, first_t = xs
            state = jnp.where(first_t, 0.0, decay * state) \
                + k_t[:, :, None] * v_t[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                     precision=jax.lax.Precision.HIGHEST)

        @keep
        def some_tokens(state, xs):
            return jax.lax.scan(token, state, xs)

        split = lambda a: a.reshape((-1, s_block) + a.shape[1:])
        _, o = jax.lax.scan(some_tokens, jnp.zeros((H, hd, hd), jnp.float32),
                            tuple(split(a) for a in (q, k, v, first)))
        return o.reshape(S, H, hd)

    @keep
    def lightning(x, p):
        H, hd = sizes["lightning_heads"], sizes["lightning_head_dim"]
        u, q, k, v = projections(x, p, H, H, hd)

        def one_sequence(args):
            q_s, k_s, v_s, seg = args
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     seg[1:] != seg[:-1]])
            theta = sizes["rope_theta"]
            return recurrence(
                rounded(rotary(q_s, theta) / jnp.sqrt(float(hd))),
                rounded(rotary(k_s, theta)), rounded(v_s), first)

        o = jax.lax.map(one_sequence, (q, k, v, segments))
        o = _norm(o.reshape(T, H * hd), p["o_norm"], eps)
        return mm(jax.nn.sigmoid(mm(u, f32(p["w_g"]))) * o, f32(p["w_o"]))

    def chosen(q, k):
        """The kept blocks [b, G, S, topk] of every sequence."""
        return jax.lax.map(
            lambda args: select(*args, sizes, mm)[0], (q, k, segments))

    @keep
    def sparse(x, p):
        H, G, hd = sizes["num_heads"], sizes["num_kv_heads"], \
            sizes["head_dim"]
        u, q, k, v = projections(x, p, H, G, hd)
        o = jax.lax.map(
            lambda args: attend(*args, sizes, mm, keep),
            (q, k, v, chosen(q, k), segments))
        return mm(jax.nn.sigmoid(mm(u, f32(p["w_g"])))
                  * o.reshape(T, H * hd), f32(p["w_o"]))

    x = sizes["scale_emb"] * f32(params["wte"][ids.reshape(T)])
    for l, kind in enumerate(sizes["layer_kinds"][:sizes["num_layers"]]):
        p = params["layers"][f"{l:02d}"]
        if kind == "S" and upto == "selection":
            _, q, k, _ = projections(x, p, sizes["num_heads"],
                                     sizes["num_kv_heads"],
                                     sizes["head_dim"])
            return chosen(q, k)
        x = mlp(x + c * (sparse if kind == "S" else lightning)(x, p), p)
    x = _norm(x, params["final_norm"], eps) \
        / (D / sizes["dim_model_base"])
    head = f32(params["lm_head"])

    def some_tokens(args):
        xb, target = args
        logits = mm(xb, head)
        return jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]

    # position t is scored against token t+1 where both are of one
    # document; a sequence's last position has no next token
    nll = jax.lax.map(some_tokens, (
        x.reshape(-1, block, D),
        jnp.roll(ids, -1, axis=1).reshape(-1, block))).reshape(b, S)
    scored = (segments == jnp.roll(segments, -1, axis=1)) \
        & (jnp.arange(S) < S - 1)[None, :]
    if per_token:
        return nll, scored
    scored = scored.astype(jnp.float32)
    return jnp.sum(nll * scored) / jnp.maximum(scored.sum(), 1.0)


SIZES = ("num_layers", "layer_kinds", "d_model", "num_heads", "num_kv_heads",
         "head_dim", "block_size", "kernel_size", "kernel_stride", "topk",
         "init_blocks", "window_size", "dense_len", "lightning_heads",
         "lightning_head_dim", "rope_theta", "scale_emb", "scale_depth",
         "depth_scale_layers", "dim_model_base", "norm_eps")


def _jitted(sizes, chunk, seq_len, **kwargs):
    return jax.jit(functools.partial(
        micro_batch_loss, sizes={k: sizes[k] for k in SIZES},
        block=min(chunk * seq_len, TOKEN_BLOCK), **kwargs))


def step_loss(params, batch, sizes, chunk, put=None, matmul_dtype=None):
    """The loss ``engine.train_batch`` reports for ``batch`` (leaves
    [gas, B, S]) at ``params``: the mean over the gas micro-batches.
    ``chunk`` (sequences, as the driver counts) bounds the block of tokens
    that the MLP and the head take at a time, at ``chunk`` sequences or
    ``TOKEN_BLOCK`` tokens, whichever is less.  ``put`` places a host array
    on the devices (the engine's batch sharding)."""
    put = put or (lambda x: x)
    ids = np.asarray(batch["input_ids"])
    seg = batch.get("segment_ids")
    fn = _jitted(sizes, chunk, ids.shape[-1], matmul_dtype=matmul_dtype)
    with jax.default_matmul_precision("highest"):
        return float(np.mean([
            float(fn(params, put(ids[g]),
                     None if seg is None else put(np.asarray(seg)[g])))
            for g in range(ids.shape[0])]))


def token_losses(params, micro_batch, sizes, chunk, matmul_dtype=None):
    """Every position's negative log likelihood of its next token for one
    micro-batch (leaves [b, S]) at ``params``, float32 [b, S], and the
    positions that are scored, bool [b, S]: what the mean of
    :func:`step_loss` averages away.  ``chunk`` as there."""
    ids = jnp.asarray(micro_batch["input_ids"])
    seg = micro_batch.get("segment_ids")
    fn = _jitted(sizes, chunk, ids.shape[-1], matmul_dtype=matmul_dtype,
                 per_token=True)
    with jax.default_matmul_precision("highest"):
        nll, scored = fn(params, ids, None if seg is None
                         else jnp.asarray(seg))
    return np.asarray(nll), np.asarray(scored)


def selection(params, micro_batch, sizes, matmul_dtype=None):
    """The first sparse layer's kept blocks for one micro-batch (leaves
    [b, S]) at ``params``: int32 [b, G, S, topk], a document's block
    indices ascending, -1 where a query has fewer causal blocks — what the
    program's ``select_blocks`` is held to."""
    ids = jnp.asarray(micro_batch["input_ids"])
    seg = micro_batch.get("segment_ids")
    fn = _jitted(sizes, 1, ids.shape[-1], matmul_dtype=matmul_dtype,
                 upto="selection")
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(params, ids, None if seg is None
                             else jnp.asarray(seg)))
