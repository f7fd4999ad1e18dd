"""Plain reference for Phi-4-mini-flash (huggingface.co/microsoft/Phi-4-mini-
flash-reasoning, ``model_type: phi4flash``; SambaY with differential
attention: Ren et al. 2025, arXiv:2507.06607; differential attention: Ye et
al. 2024, arXiv:2410.05258; the state-space layer: Gu & Dao 2023, "Mamba",
arXiv:2312.00752): forward pass and loss in ``jax.numpy`` and float32 — no
kernel, no chunked scan, no scan over layers, no remat, no mixed precision.
Gradients are ``jax.grad`` of :func:`micro_batch_loss`.

``LN(x; w, b)`` is LayerNorm, eps ``layer_norm_eps``.  Layer ``l`` of ``L``
(0-based) is ``x <- x + Mixer_l(LN(x)); x <- x + (silu(g) * h) W_down`` with
``[g | h] = LN(x) W_gate_up``; a final ``LN``; the head is the embedding
table.  No position signal of any kind.  The mixer by ``l`` and ``L``:

``l`` even, ``l <= L/2`` — Mamba-1 (d_inner = ``mamba_expand * d_model``
channels, N = ``mamba_d_state``, R = ``mamba_dt_rank``):

    [u | z] = h W_in
    u <- silu(conv(u) + b_c)      depthwise, causal, ``mamba_d_conv`` taps;
        the last tap on the current token; a tap in another document reads 0
    [delta | B | C] = u W_x       widths R | N | N
    Delta_t = softplus(delta_t W_dt + b_dt)     A = -exp(A_log)  [d_inner, N]
    H_0 = 0;  H_t = exp(Delta_t A) . H_{t-1} + (Delta_t u_t) B_t^T
    y_t = H_t C_t + D . u_t
    out = (y * silu(z)) W_out

written as the literal per-token recurrence, a ``lax.scan`` over tokens with
the state [d_inner, N]; at a document's first token ``H`` is zero before the
write.  Layer ``L/2`` keeps ``m = y`` (before the gate).

``l`` even, ``l >= L/2 + 2`` — gated memory unit: ``out = (m * silu(h W_1))
W_2``.

``l`` odd — differential attention (H query heads, KV key and value heads,
hd wide; ``rep = H / KV``): ``[q | k | v] = h W_qkv + b``.  For
differential head j of H/2 with its pair g = j // rep: ``A1 = softmax(q_2j
k_2g^T / sqrt(hd) + mask)``, ``A2 = softmax(q_2j+1 k_2g+1^T / sqrt(hd) +
mask)`` — each formed on its own as a masked [queries, S] softmax, a block
of one sequence's queries at a time — ``o_j = (A1 - lambda A2) [v_2g |
v_2g+1]``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_j <- o_j / rms(o_j) * w (1 -
lambda_init)`` (eps ``subln_eps``); ``out = concat(o) W_o + b_o``.  The
mask: key <= query, one document, and for ``l < L/2`` also ``query - key <
sliding_window``.  Layer ``L/2 + 1`` keeps its ``k``, ``v``; for ``l >= L/2
+ 3`` the projection is ``W_q`` alone and ``k``, ``v`` are those.

Loss of a micro-batch: cross-entropy over the positions whose next token is
in the same document.  The loss of a step is the mean over its
micro-batches.

Departures from the source, each also in the configuration's ``assumed``:
dropout (``embd_pdrop``, ``resid_pdrop``: 0 in the source) is left out; the
source computes its four flash calls ``attn11/12/21/22`` and joins them,
which is the pair of maps above on the joined value halves; the source
shares a cache between layers where this hands over plain values.

It runs on the engine's own parameter tree (``layers = {"00": {...}, ...}``),
one sequence at a time through the mixers, a block of tokens at a time
through the MLP and the head.

``matmul_dtype`` is for the control only: every matrix product's operands
(projections, scores, the maps times the values, the head) are rounded to
that type first (float32 accumulation); the recurrence is elementwise and
stays float32.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (8 layers at the published
#: widths, one packed sequence of 16,384 tokens; PERF.md section 2, PR 54).
#: The engine (bfloat16 products, float32 scan state, softmax and loss)
#: moved the loss by at most 2.8e-4 over 17 runs at 16 seeds (4.2e-4 over
#: three more at other draws of the weights); the reference with every
#: product's operands rounded to bf16, the
#: engine's own arithmetic, by 5.2e-5 to 1.9e-4 (inside); rounded to fp8
#: e4m3, the nearest precision below, by 5.0e-3 to 7.8e-3 — outside in
#: every seed.  The limit lies between the two readings: 3.6 times the
#: engine's largest, 0.3 of the control's smallest.
LOSS_ATOL = 1.5e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 54): the engine read 1.79e-2 to 1.96e-2 over 20 runs (the
#: reference rounded to bf16: 1.16e-2 to 1.27e-2); the reference rounded to
#: fp8 e4m3 0.354 to 0.385, outside in every seed.  0.08 is 4.1 times the
#: engine's largest reading and 0.23 of the control's smallest.
TOKEN_NLL_RMS_ATOL = 0.08

QUERY_BLOCK = 256       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through the MLP, or the head, at a time
STATE_BLOCK = 64        # tokens of the recurrence between kept states


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def layer_kind(l, L):
    half = L // 2
    if l % 2 == 0:
        return "mamba" if l <= half else "gmu"
    if l < half:
        return "swa"
    return "full" if l == half + 1 else "cross"


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=False):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, every position's negative log
    likelihood of the next token [b, S] and which positions are scored
    (:func:`token_losses`).  Differentiable in ``params``; ``remat`` keeps
    only each layer's, each block of tokens', each block of queries' and
    every ``STATE_BLOCK``-th token's inputs for the gradient (the same
    arithmetic: what ``jax.grad`` at the published widths needs to fit one
    chip, scripts/olmoe_grad_check.py)."""
    keep = jax.checkpoint if remat else (lambda fn: fn)
    f32 = lambda a: a.astype(jnp.float32)
    if matmul_dtype is None:
        mm = jnp.matmul
    else:
        mm = lambda a, b: jnp.matmul(f32(a.astype(matmul_dtype)),
                                     f32(b.astype(matmul_dtype)))
    b, S = ids.shape
    T = b * S
    L = sizes["num_layers"]
    eps = sizes["layer_norm_eps"]
    D, F = sizes["d_model"], sizes["d_ff"]
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    d_in = sizes["mamba_expand"] * D
    N, R, K = (sizes["mamba_d_state"], sizes["mamba_dt_rank"],
               sizes["mamba_d_conv"])
    window = sizes["sliding_window"]
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    s_block = _fit(S, STATE_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    # ---------------------------------------------------------------- MLP
    @keep
    def mlp(x, p):
        @keep
        def some_tokens(xb):
            gate_up = mm(_ln(xb, p["ln2_w"], p["ln2_b"], eps),
                         f32(p["w_gate_up"]))
            return mm(jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:],
                      f32(p["w_down"]))
        return x + jax.lax.map(some_tokens,
                               x.reshape(-1, block, D)).reshape(x.shape)

    # ------------------------------------------------------------ Mamba-1
    def recurrence(u, delta, A, Bt, Ct, first):
        """One sequence, token by token: u, delta [S, d_inner], A [d_inner,
        N], Bt, Ct [S, N], first [S] (a document's first token).  -> H_t
        C_t [S, d_inner]."""

        def token(state, xs):
            u_t, delta_t, B_t, C_t, first_t = xs
            decay = jnp.where(first_t, 0.0, jnp.exp(delta_t[:, None] * A))
            state = decay * state + (delta_t * u_t)[:, None] * B_t[None, :]
            return state, jnp.sum(state * C_t[None, :], axis=-1)

        @keep
        def some_tokens(state, xs):
            return jax.lax.scan(token, state, xs)

        split = lambda a: a.reshape((-1, s_block) + a.shape[1:])
        _, y = jax.lax.scan(
            some_tokens, jnp.zeros((d_in, N), jnp.float32),
            tuple(split(a) for a in (u, delta, Bt, Ct, first)))
        return y.reshape(S, d_in)

    def conv(x, w, bias, seg):
        """x [S, C], w [K, C], bias [C], seg [S]."""
        y = x * w[K - 1]
        for back in range(1, K):
            past = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
            same = jnp.concatenate([jnp.zeros((back,), bool),
                                    seg[back:] == seg[:-back]])
            y = y + jnp.where(same[:, None], past, 0.0) * w[K - 1 - back]
        return y + bias

    @keep
    def mamba(x, p):
        """-> (the mixer's output [T, D], y [b, S, d_inner])."""
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        uz = mm(h, f32(p["w_in"])).reshape(b, S, 2 * d_in)
        A = -jnp.exp(f32(p["A_log"]))

        def one_sequence(args):
            row, seg = args
            u = jax.nn.silu(conv(row[:, :d_in], f32(p["conv_w"]),
                                 f32(p["conv_b"]), seg))
            dbc = mm(u, f32(p["w_x"]))
            delta = jax.nn.softplus(mm(dbc[:, :R], f32(p["w_dt"]))
                                    + f32(p["dt_bias"]))
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     seg[1:] != seg[:-1]])
            return recurrence(u, delta, A, dbc[:, R:R + N], dbc[:, R + N:],
                              first) + f32(p["D"]) * u

        y = jax.lax.map(one_sequence, (uz, segments))          # [b, S, d_in]
        gated = y * jax.nn.silu(uz[..., d_in:])
        return mm(gated.reshape(T, d_in), f32(p["w_out"])), y

    @keep
    def gmu(x, p, memory):
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        gate = jax.nn.silu(mm(h, f32(p["w_1"])))
        return mm(memory.reshape(T, d_in) * gate, f32(p["w_2"]))

    # ------------------------------------------- differential attention
    def one_map(q, k, v, seg, windowed):
        """One sequence, one softmax map a head: q [S, H/2, hd], k [S,
        KV/2, hd], v [S, KV/2, 2 hd], seg [S] -> [S, H/2, 2 hd]."""
        rep = H // KV
        kT = jnp.repeat(k, rep, axis=1).transpose(1, 2, 0)     # [h, hd, S]
        vT = jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)     # [h, S, 2hd]

        @keep
        def some_queries(args):
            qb, pos, seg_q = args
            scores = mm(qb.transpose(1, 0, 2), kT) / jnp.sqrt(float(hd))
            ago = pos[:, None] - jnp.arange(S)[None, :]
            seen = (ago >= 0) & (seg_q[:, None] == seg[None, :])
            if windowed:
                seen = seen & (ago < window)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, vT).transpose(1, 0, 2)
        out = jax.lax.map(some_queries, (
            q.reshape(-1, q_block, H // 2, hd),
            jnp.arange(S).reshape(-1, q_block), seg.reshape(-1, q_block)))
        return out.reshape(S, H // 2, 2 * hd)

    def diff_attn(x, p, l, kind, kv):
        """-> (the mixer's output [T, D], (k, v) [b, S, KV, hd] each)."""
        return keep(functools.partial(_diff_attn, l=l, kind=kind))(x, p, kv)

    def _diff_attn(x, p, kv, l, kind):
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        if kind == "cross":
            q = mm(h, f32(p["w_q"])) + f32(p["b_q"])
            k, v = kv
        else:
            qkv = mm(h, f32(p["w_qkv"])) + f32(p["b_qkv"])
            q = qkv[:, :H * hd]
            k = qkv[:, H * hd:(H + KV) * hd].reshape(b, S, KV, hd)
            v = qkv[:, (H + KV) * hd:].reshape(b, S, KV, hd)
        q = q.reshape(b, S, H // 2, 2, hd)
        kp = k.reshape(b, S, KV // 2, 2, hd)
        vp = v.reshape(b, S, KV // 2, 2 * hd)      # [v_2g | v_2g+1]
        init = lambda_init(l)
        lam = jnp.exp(jnp.sum(f32(p["lambda_q1"]) * f32(p["lambda_k1"]))) \
            - jnp.exp(jnp.sum(f32(p["lambda_q2"]) * f32(p["lambda_k2"]))) \
            + init

        def one_sequence(args):
            q_s, k_s, v_s, seg = args
            a1 = one_map(q_s[:, :, 0], k_s[:, :, 0], v_s, seg, kind == "swa")
            a2 = one_map(q_s[:, :, 1], k_s[:, :, 1], v_s, seg, kind == "swa")
            return a1 - lam * a2

        o = jax.lax.map(one_sequence, (q, kp, vp, segments))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + sizes["subln_eps"]) \
            * f32(p["subln"]) * (1.0 - init)
        return mm(o.reshape(T, H * hd), f32(p["w_o"])) + f32(p["b_o"]), \
            (k, v)

    x = f32(params["wte"][ids.reshape(T)])
    memory = kv = None
    for l in range(L):
        p = params["layers"][f"{l:02d}"]
        kind = layer_kind(l, L)
        if kind == "mamba":
            out, y = mamba(x, p)
            if l == L // 2:
                memory = y
        elif kind == "gmu":
            out = gmu(x, p, memory)
        else:
            out, own = diff_attn(x, p, l, kind, kv)
            if kind == "full":
                kv = own
        x = mlp(x + out, p)
    x = _ln(x, params["lnf_w"], params["lnf_b"], eps)
    head = f32(params["wte"]).T

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


SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
         "d_ff", "sliding_window", "mamba_d_state", "mamba_d_conv",
         "mamba_expand", "mamba_dt_rank", "layer_norm_eps", "subln_eps")


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
