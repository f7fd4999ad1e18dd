"""Plain reference for Nemotron-H (huggingface.co/nvidia/NVIDIA-Nemotron-3-
Nano-30B-A3B-BF16, ``model_type: nemotron_h``; the family: arXiv:2504.03624;
the state-space layer: Dao & Gu 2024, "Transformers are SSMs",
arXiv:2405.21060): forward pass and loss in ``jax.numpy`` and float32 — no
kernel, no chunked scan, no sort, no plan, no remat, no mixed precision.
Gradients are ``jax.grad`` of :func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  Layer ``i`` is of the kind
``hybrid_override_pattern[i % len(pattern)]`` names, and is ONE mixer: ``x
<- x + Mixer(N(x))``.  No biases but the convolution's.  Untied head, final
``N``.

``M``, Mamba-2 (Hm heads x P, G groups x N; d_inner = Hm * P):

    [z | xBC | dt] = h W_in           widths d_inner | d_inner + 2 G N | Hm
    xBC <- silu(conv(xBC) + b_conv)   depthwise, causal, width
        ``conv_kernel``; tap K-1 on the current token
    [x | B | C] = xBC                 x [Hm, P]; B, C [G, N]; head h reads
        group h // (Hm / G)
    dt_t <- softplus(dt_t + dt_bias)  A = -exp(A_log)     per head, float32
    H_0 = 0;  H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t     H [P, N]
    y_t = H_t C_t + D x_t
    y <- y * silu(z);  y <- N(y; w_g) over each group of d_inner / G
        channels (the gate first);  out = y W_out

written as the literal per-token recurrence, a ``lax.scan`` over tokens.
Packed documents: at a document's first token ``H`` is zero before the
write, and the convolution reads zero for a tap in another document.

``*``, attention (H query heads, KV key-value heads, width hd): ``q = h
W_q``, ``k = h W_k``, ``v = h W_v``; causal softmax attention at
1/sqrt(hd) inside a document; ``out = attn W_o``.  **No rotary embedding
and no other position signal.**

``E``, experts: ``s = sigmoid(h W_r)`` over all ``num_experts``; the
``top_k`` largest of ``s + e_score_correction_bias`` are chosen; their
weights are ``s`` (without the bias) divided by the chosen ones' sum, times
``routed_scaling_factor``; ``MoE(h) = sum_{e chosen, held} w_e W_down,e
relu(W_up,e h)^2 + W_down,s relu(W_up,s h)^2``.  **The sum runs over the
experts held here only** (``expert_offset`` .. ``+ experts_held``; the
parameter tree holds just those), the shared expert whole: one chip's share
of an expert-parallel layer, the partial result going on to the next layer,
as in the program.

Loss of a micro-batch: cross-entropy over the positions whose next token is
in the same document + ``aux_loss_coef`` * sum_{E layers} num_experts *
sum_e f_e * P_e over ALL experts (f_e = (token, choice) pairs sent to e /
tokens, all k choices counted; P_e = mean over the micro-batch's tokens of
``s_e / sum_e' s_e'``).  The loss of a step is the mean over its
micro-batches.

Assumed (the configuration's ``assumed`` says why): no rotary embedding;
``W_in``'s columns in the order above; the router loss's form and that it is
per layer over this micro-batch; ``e_score_correction_bias`` is whatever the
parameter tree holds (the program never moves it).

It runs on the engine's own parameter tree (``blocks = {"ssm": [P, n, ...],
"experts": ..., "attn": ...}``), one sequence at a time through the mixers,
a block of tokens at a time through the held experts (one expert at a time,
the weight 0 where it was not chosen) and the shared one, the head over
blocks.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation); the state's read
``H C`` is a matrix product too.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "ssm", "E": "experts", "*": "attn"}

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (nine layers at the
#: published widths, 2 micro-batches of 2 x 8,192 packed tokens; PERF.md
#: section 2, PR 34).  The engine (bfloat16 products, float32 state,
#: decays, router and loss) moved the loss by at most 3.99e-4 over 25 runs
#: and 23 seeds; the reference with every product's operands rounded to
#: bf16, the engine's own arithmetic, by at most 2.4e-4 over 10 seeds
#: (inside).  The limit is 2.5 times the engine's largest reading.  Rounded
#: to fp8 e4m3, the nearest precision below, the reference's mean loss read
#: 2.3e-4 to 1.1e-2 from the float32 one over the same 10 seeds, eight of
#: them outside: a mean over 32,768 tokens averages much of the rounding
#: away.  TOKEN_NLL_RMS_ATOL is the limit that catches it in every seed.
LOSS_ATOL = 1e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 34): the engine read 2.68e-2 to 3.68e-2 over 25 runs and
#: 23 seeds (the reference rounded to bf16: 2.62e-2 to 3.33e-2 over 10
#: seeds); the reference rounded to fp8 e4m3, the nearest precision below,
#: 0.240 to 0.261 over the same 10 seeds (outside, every seed).  0.1 is 2.7
#: times the engine's largest reading and 0.42 of the control's smallest.
TOKEN_NLL_RMS_ATOL = 0.1

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time
STATE_BLOCK = 64        # tokens of the recurrence between kept states


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=False):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, every position's negative log
    likelihood of the next token [b, S] and which positions are scored
    (:func:`token_losses`).  Differentiable in ``params``; ``remat`` keeps
    only each layer's, each expert's, each block of queries' and every
    ``STATE_BLOCK``-th token's inputs for the gradient (the same
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
    eps = sizes["norm_eps"]
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    Hm, Pd = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    G, N = sizes["n_groups"], sizes["ssm_state_size"]
    K = sizes["conv_kernel"]
    d_in = Hm * Pd
    conv_ch = d_in + 2 * G * N
    E, top_k = sizes["num_experts"], sizes["top_k"]
    held = sizes.get("experts_held") or E
    offset = sizes.get("expert_offset", 0)
    pattern = sizes["hybrid_override_pattern"]
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    s_block = _fit(S, STATE_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    # ------------------------------------------------------------ experts
    @keep
    def experts_layer(x, p):
        m = _norm(x, p["norm"], eps)
        moe = p["moe"]
        scores = jax.nn.sigmoid(mm(m, f32(moe["router"])))    # [T, E]
        _, chosen = jax.lax.top_k(
            scores + f32(moe["e_score_correction_bias"]), top_k)
        sent = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(1)  # [T, E]
        picked = scores * sent
        weights = picked / picked.sum(-1, keepdims=True) \
            * sizes["routed_scaling_factor"]
        mine = weights[:, offset:offset + held]   # the rest is held elsewhere

        def relu2(mb, w_in, w_out):
            return mm(jnp.square(jax.nn.relu(mm(mb, f32(w_in)))), f32(w_out))

        @keep
        def some_tokens(args):
            mb, weight_b = args                   # [block, D], [block, held]

            @keep
            def one_expert(out, held_expert):
                w_in, w_out, weight = held_expert     # weight 0: not chosen
                return out + weight[:, None] * relu2(mb, w_in, w_out), None

            routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(mb), (
                moe["w_in"], moe["w_out"], weight_b.T))
            return routed + relu2(mb, moe["shared_in"], moe["shared_out"])

        out = jax.lax.map(some_tokens, (
            m.reshape(-1, block, m.shape[-1]),
            mine.reshape(-1, block, held))).reshape(x.shape)
        share = scores / scores.sum(-1, keepdims=True)
        return x + out, E * jnp.sum(sent.mean(0) * share.mean(0))

    # ---------------------------------------------------- state-space layer
    def recurrence(x, dt, A, Bh, Ch, first):
        """One sequence, token by token: x [S, Hm, P], dt [S, Hm], A [Hm],
        Bh, Ch [S, Hm, N] (each head's group's), first [S] (a document's
        first token).  -> H_t C_t [S, Hm, P]."""

        def token(state, xs):
            x_t, dt_t, B_t, C_t, first_t = xs
            state = state * jnp.where(first_t, 0.0,
                                      jnp.exp(dt_t * A))[:, None, None] \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
            return state, mm(state, C_t[:, :, None])[:, :, 0]

        @keep
        def some_tokens(state, xs):
            return jax.lax.scan(token, state, xs)

        split = lambda a: a.reshape((-1, s_block) + a.shape[1:])
        _, y = jax.lax.scan(
            some_tokens, jnp.zeros((Hm, Pd, N), jnp.float32),
            tuple(split(a) for a in (x, dt, Bh, Ch, first)))
        return y.reshape(S, Hm, Pd)

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
    def ssm_layer(x, p):
        n = _norm(x, p["norm"], eps)
        zxbcdt = mm(n, f32(p["w_in"])).reshape(b, S, -1)
        A = -jnp.exp(f32(p["A_log"]))
        D = f32(p["D"])

        def one_sequence(args):
            row, seg = args
            xbc = jax.nn.silu(conv(row[:, d_in:d_in + conv_ch],
                                   f32(p["conv_w"]), f32(p["conv_b"]), seg))
            dt = jax.nn.softplus(row[:, d_in + conv_ch:] + f32(p["dt_bias"]))
            xs = xbc[:, :d_in].reshape(S, Hm, Pd)
            Bh, Ch = (jnp.repeat(t.reshape(S, G, N), Hm // G, axis=1)
                      for t in (xbc[:, d_in:d_in + G * N],
                                xbc[:, d_in + G * N:]))
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     seg[1:] != seg[:-1]])
            y = recurrence(xs, dt, A, Bh, Ch, first) + D[:, None] * xs
            gated = (y.reshape(S, d_in) * jax.nn.silu(row[:, :d_in])) \
                .reshape(S, G, d_in // G)
            return _norm(gated, p["gate_norm"].reshape(G, d_in // G),
                         eps).reshape(S, d_in)

        y = jax.lax.map(one_sequence, (zxbcdt, segments))
        return x + mm(y.reshape(T, d_in), f32(p["w_out"])), 0.0

    # ------------------------------------------------------------ attention
    def attention(q, k, v, seg):
        """One sequence: q [S, H, hd], k and v [S, KV, hd], seg [S]."""
        k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
        kT, vT = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # per head

        @keep
        def some_queries(args):
            qb, pos, seg_q = args                 # [qb, H, hd], [qb], [qb]
            scores = mm(qb.transpose(1, 0, 2), kT) / jnp.sqrt(float(hd))
            seen = (pos[:, None] >= jnp.arange(S)[None, :]) \
                & (seg_q[:, None] == seg[None, :])
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, vT).transpose(1, 0, 2)           # [qb, H, hd]

        out = jax.lax.map(some_queries, (
            q.reshape(-1, q_block, H, hd),
            jnp.arange(S).reshape(-1, q_block), seg.reshape(-1, q_block)))
        return out.reshape(S, H, hd)

    @keep
    def attn_layer(x, p):
        n = _norm(x, p["norm"], eps)
        q = mm(n, f32(p["wq"])).reshape(b, S, H, hd)
        k = mm(n, f32(p["wk"])).reshape(b, S, KV, hd)
        v = mm(n, f32(p["wv"])).reshape(b, S, KV, hd)
        attn = jax.lax.map(lambda a: attention(*a), (q, k, v, segments))
        return x + mm(attn.reshape(T, H * hd), f32(p["wo"])), 0.0

    layer_fns = {"ssm": ssm_layer, "experts": experts_layer,
                 "attn": attn_layer}
    x = f32(params["wte"][ids.reshape(T)])
    stacks = params["blocks"]
    balance = 0.0
    for i in range(sizes["num_layers"]):
        period, at = divmod(i, len(pattern))
        kind = KINDS[pattern[at]]
        j = sum(KINDS[c] == kind for c in pattern[:at])
        x, bal = layer_fns[kind](
            x, jax.tree.map(lambda a: a[period, j], stacks[kind]))
        balance = balance + bal
    x = _norm(x, params["final_norm"], eps)
    head = f32(params["lm_head"])

    def some_tokens(args):
        xb, target = args
        logits = mm(xb, head)
        return jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]

    # position t is scored against token t+1 where both are of one
    # document; a sequence's last position has no next token
    nll = jax.lax.map(some_tokens, (
        x.reshape(-1, block, x.shape[-1]),
        jnp.roll(ids, -1, axis=1).reshape(-1, block))).reshape(b, S)
    scored = (segments == jnp.roll(segments, -1, axis=1)) \
        & (jnp.arange(S) < S - 1)[None, :]
    if per_token:
        return nll, scored
    scored = scored.astype(jnp.float32)
    ce = jnp.sum(nll * scored) / jnp.maximum(scored.sum(), 1.0)
    return ce + sizes["aux_loss_coef"] * balance


SIZES = ("num_layers", "hybrid_override_pattern", "num_heads",
         "num_kv_heads", "head_dim", "mamba_num_heads", "mamba_head_dim",
         "n_groups", "ssm_state_size", "conv_kernel", "num_experts", "top_k",
         "routed_scaling_factor", "expert_offset", "experts_held",
         "norm_eps", "aux_loss_coef")


def _jitted(sizes, chunk, seq_len, **kwargs):
    return jax.jit(functools.partial(
        micro_batch_loss, sizes={k: sizes[k] for k in SIZES},
        block=min(chunk * seq_len, TOKEN_BLOCK), **kwargs))


def step_loss(params, batch, sizes, chunk, put=None, matmul_dtype=None):
    """The loss ``engine.train_batch`` reports for ``batch`` (leaves
    [gas, B, S]) at ``params``: the mean over the gas micro-batches.  A
    micro-batch goes through whole, because the router's statistics are
    its own; ``chunk`` (sequences, as the driver counts) bounds the block
    of tokens that the experts and the head take at a time, at ``chunk``
    sequences or ``TOKEN_BLOCK`` tokens, whichever is less.  ``put``
    places a host array on the devices (the engine's batch sharding)."""
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
