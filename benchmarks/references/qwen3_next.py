"""Plain reference for Qwen3-Next (huggingface.co/Qwen/Qwen3-Next-80B-A3B-
Instruct; the delta rule is Yang, Kautz & Hatamizadeh 2024, "Gated Delta
Networks", arXiv:2412.06464): forward pass and loss in ``jax.numpy`` and
float32 — no kernel, no chunked scan, no sort, no plan, no remat, no mixed
precision.  Gradients are ``jax.grad`` of :func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * (1 + w)``, eps ``rms_norm_eps``.  Layer ``i`` is a
full-attention layer when ``(i + 1) % full_attention_interval == 0``, else
a linear one.  Every layer: ``x <- x + Mixer(N(x))``, ``x <- x +
MoE(N(x))``.  No biases.  Untied head, final ``N``.

Gated DeltaNet mixer (Hk key heads x dk, Hv value heads x dv):

    [q, k, v, z] = h W_qkvz          [b, a] = h W_ba
    [q, k, v] <- silu(conv(concat(q, k, v)))     depthwise, causal, width
        ``linear_conv_kernel_dim``, no bias; tap K-1 on the current token
    beta_t = sigmoid(b_t)      g_t = -exp(A_log) * softplus(a_t + dt_bias)
    q~ = l2norm(q) / sqrt(dk)  k~ = l2norm(k)    key head h serves value
        heads h*Hv/Hk ...; l2norm(x) = x / sqrt(sum x^2 + 1e-6)
    S_0 = 0;  S <- exp(g_t) S_{t-1};  S_t = S + k~_t (x) beta_t (v_t - S^T k~_t)
    o_t = S_t^T q~_t                              S [dk, dv] per value head
    y_t = RMSNorm(o_t; w_o, over the head's dv, plain weight) * silu(z_t)
    out = y W_out

written as the literal per-token recurrence, a ``lax.scan`` over tokens.
Packed documents: at a document's first token ``S`` is zero before the
write, and the convolution reads zero for a tap in another document.

Gated full attention (H query heads, KV key-value heads, width hd):

    [q, gate] = h W_q  (per head: query, then gate)   k = h W_k   v = h W_v
    q <- N(q; w_q), k <- N(k; w_k) per head; rotate-half rotary (dim i
        with i + rot/2) on the first rot = partial_rotary_factor * hd
        dimensions, base ``rope_theta``, positions 0..S-1 over the packed
        sequence (not reset per document)
    out = (causal-softmax-attention(q, k, v) * sigmoid(gate)) W_o
        scale 1/sqrt(hd), inside a document

Experts: ``p = softmax(h W_r)`` over all ``num_experts``; the ``top_k``
largest, their ``p`` divided by their sum; ``MoE(h) = sum_{e in top-k,
held} p^_e SwiGLU_e(h) + sigmoid(h w_sg) SwiGLU_shared(h)``.  **The sum
runs over the experts held here only** (``expert_offset`` ..
``+ experts_held``; the parameter tree holds just those), the shared
expert whole: this is one chip's share of an expert-parallel layer, and
the partial result is what goes on to the next layer, as in the program.

Loss of a micro-batch: cross-entropy over the positions whose next token
is in the same document + ``aux_loss_coef`` * sum_layers E * sum_e f_e *
P_e over ALL experts (P_e = mean of p_e over the micro-batch's tokens; f_e
= (token, choice) pairs sent to e / tokens, all k choices counted: Hugging
Face ``load_balancing_loss_func``).  The loss of a step is the mean over
its micro-batches.

Departures from Hugging Face's modelling file, each a choice a random
initialisation cannot tell apart: ``W_qkvz``'s columns are [q | k | v | z]
and ``W_ba``'s [b | a], where the file interleaves them per key head; the
router loss is per layer over this micro-batch, summed over layers.  Not
built: multi-token prediction (the catalog's ``described_as`` mentions
"MTP 1"; the published config has no key for it).

It runs on the engine's own parameter tree (``blocks = {"linear": [P, n,
...], "full": [P, 1, ...]}``), one sequence at a time through the mixers,
a block of tokens at a time through the held experts (one expert at a
time, the gate 0 where it was not chosen) and the shared one, the head
over blocks.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation); the state's own
products are matrix products too.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (one period at the
#: published widths, 2 micro-batches of 2 x 8,192 packed tokens; PERF.md
#: section 2, PR 32).  The engine (bfloat16 products, float32 state,
#: softmax, router and loss) moved the loss by at most 5.97e-4 over 53 runs
#: and 51 seeds; the reference with every product's operands rounded to
#: bf16, the engine's own arithmetic, by at most 3.7e-4 over 12 seeds
#: (inside).  The limit is 1.7 times the engine's largest reading.  It does
#: NOT separate the precision below: rounded to fp8 e4m3 the reference's
#: mean loss read 2.0e-5 to 2.9e-3 from the float32 one over 10 seeds, six
#: of them inside — zero-centred norms and small residual branches leave
#: the head's logits at initialisation nearly the embedding's, and a mean
#: over 32,768 tokens averages the rounding away.  TOKEN_NLL_RMS_ATOL is the
#: limit that catches it.
LOSS_ATOL = 1e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 32): the engine read 3.60e-2 to 4.24e-2 over 12 runs and 12
#: seeds (the reference rounded to bf16: 2.79e-2 to 3.49e-2 over 10 seeds);
#: the reference rounded to fp8 e4m3, the nearest precision below, 0.279 to
#: 0.295 over the same 10 seeds (outside, every seed).  0.1 is 2.4 times
#: the engine's largest reading and 0.36 of the control's smallest.
TOKEN_NLL_RMS_ATOL = 0.1

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time
STATE_BLOCK = 64        # tokens of the recurrence between kept states


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    """Zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _plain_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _rope(x, theta, rot):
    """x [S, H, hd]: the first ``rot`` dimensions rotated, dim i paired
    with i + rot/2, positions 0..S-1; the rest passed through."""
    S = x.shape[0]
    freqs = theta ** (-jnp.arange(0, rot // 2) / (rot // 2))
    angles = jnp.arange(S)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def _blocks(fn, x, block):
    out = jax.lax.map(fn, x.reshape((-1, block) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=False):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, every position's negative log
    likelihood of the next token [b, S] and which positions are scored
    (:func:`token_losses`).  Differentiable in ``params``; ``remat`` keeps only
    each layer's, each expert's, each block of queries' and every
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
    eps = sizes["rms_norm_eps"]
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    rot = int(hd * sizes["partial_rotary_factor"])
    theta = float(sizes["rope_theta"])
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    K = sizes["linear_conv_kernel_dim"]
    E, top_k = sizes["num_experts"], sizes["top_k"]
    held = sizes.get("experts_held") or E
    offset = sizes.get("expert_offset", 0)
    interval = sizes["full_attention_interval"]
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    s_block = _fit(S, STATE_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    # ------------------------------------------------------------ experts
    def experts(x, p):
        m = _norm(x, p["mlp_norm"], eps)
        moe = p["moe"]
        logits = mm(m, f32(moe["router"]))                    # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top, chosen = jax.lax.top_k(probs, top_k)
        sent = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(1)  # [T, E]
        gates = probs * sent / top.sum(-1, keepdims=True)
        mine = gates[:, offset:offset + held]     # the rest is held elsewhere

        def swiglu(mb, w_gate, w_in, w_out):
            return mm(jax.nn.silu(mm(mb, f32(w_gate))) * mm(mb, f32(w_in)),
                      f32(w_out))

        @keep
        def some_tokens(args):
            mb, gate_b = args                     # [block, D], [block, held]

            @keep
            def one_expert(out, held_expert):
                w_gate, w_in, w_out, gate = held_expert   # gate 0: not chosen
                return out + gate[:, None] * swiglu(mb, w_gate, w_in,
                                                    w_out), None

            routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(mb), (
                moe["w_gate"], moe["w_in"], moe["w_out"], gate_b.T))
            shared = swiglu(mb, moe["shared_gate"], moe["shared_in"],
                            moe["shared_out"])
            return routed + jax.nn.sigmoid(
                mm(mb, f32(moe["shared_router"]))) * shared

        out = jax.lax.map(some_tokens, (
            m.reshape(-1, block, m.shape[-1]),
            mine.reshape(-1, block, held))).reshape(x.shape)
        balance = E * jnp.sum(sent.mean(0) * probs.mean(0))
        return x + out, balance

    # ------------------------------------------------------ gated delta rule
    def delta_rule(q, k, v, g, beta, first):
        """One sequence, token by token: q, k [S, Hv, dk], v [S, Hv, dv],
        g, beta [S, Hv], first [S] (a document's first token)."""

        def token(state, xs):
            q_t, k_t, v_t, g_t, b_t, first_t = xs
            state = state * jnp.where(first_t, 0.0, jnp.exp(g_t))[:, None,
                                                                  None]
            read = mm(k_t[:, None, :], state)[:, 0]           # S^T k
            state = state + k_t[:, :, None] \
                * (b_t[:, None] * (v_t - read))[:, None, :]
            return state, mm(q_t[:, None, :], state)[:, 0]    # S^T q

        @keep
        def some_tokens(state, xs):
            return jax.lax.scan(token, state, xs)

        split = lambda a: a.reshape((-1, s_block) + a.shape[1:])
        _, o = jax.lax.scan(
            some_tokens, jnp.zeros((Hv, dk, dv), jnp.float32),
            tuple(split(a) for a in (q, k, v, g, beta, first)))
        return o.reshape(S, Hv, dv)

    def conv(x, w, seg):
        """x [S, C], w [K, C], seg [S]."""
        y = x * w[K - 1]
        for back in range(1, K):
            past = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
            same = jnp.concatenate([jnp.zeros((back,), bool),
                                    seg[back:] == seg[:-back]])
            y = y + jnp.where(same[:, None], past, 0.0) * w[K - 1 - back]
        return y

    @keep
    def linear_layer(x, p):
        n = _norm(x, p["attn_norm"], eps)
        qkvz = mm(n, f32(p["w_qkvz"]))
        ba = mm(n, f32(p["w_ba"]))
        conv_ch = 2 * Hk * dk + Hv * dv
        beta = jax.nn.sigmoid(ba[:, :Hv])
        g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
            ba[:, Hv:] + f32(p["dt_bias"]))

        def one_sequence(args):
            qkv, g_s, beta_s, seg = args
            qkv = jax.nn.silu(conv(qkv, f32(p["conv_w"]), seg))
            q = qkv[:, :Hk * dk].reshape(S, Hk, dk)
            k = qkv[:, Hk * dk:2 * Hk * dk].reshape(S, Hk, dk)
            v = qkv[:, 2 * Hk * dk:].reshape(S, Hv, dv)
            q = jnp.repeat(_l2norm(q) / jnp.sqrt(float(dk)), Hv // Hk, 1)
            k = jnp.repeat(_l2norm(k), Hv // Hk, 1)
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     seg[1:] != seg[:-1]])
            return delta_rule(q, k, v, g_s, beta_s, first)

        o = jax.lax.map(one_sequence, (
            qkvz[:, :conv_ch].reshape(b, S, -1), g.reshape(b, S, Hv),
            beta.reshape(b, S, Hv), segments))                # [b,S,Hv,dv]
        y = _plain_norm(o, p["o_norm"], eps) * jax.nn.silu(
            qkvz[:, conv_ch:].reshape(b, S, Hv, dv))
        return experts(x + mm(y.reshape(T, Hv * dv), f32(p["w_out"])), p)

    # ------------------------------------------------------- full attention
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
    def full_layer(x, p):
        n = _norm(x, p["attn_norm"], eps)
        qg = mm(n, f32(p["wq"])).reshape(b, S, H, 2, hd)
        k = mm(n, f32(p["wk"])).reshape(b, S, KV, hd)
        v = mm(n, f32(p["wv"])).reshape(b, S, KV, hd)

        def one_sequence(args):
            qs, ks, vs, seg = args
            return attention(_rope(_norm(qs, p["q_norm"], eps), theta, rot),
                             _rope(_norm(ks, p["k_norm"], eps), theta, rot),
                             vs, seg)

        attn = jax.lax.map(one_sequence, (qg[..., 0, :], k, v, segments))
        gated = attn * jax.nn.sigmoid(qg[..., 1, :])
        return experts(x + mm(gated.reshape(T, H * hd), f32(p["wo"])), p)

    x = f32(params["wte"][ids.reshape(T)])
    stacks = params["blocks"]
    balance = 0.0
    for i in range(sizes["num_layers"]):
        period, at = divmod(i, interval)
        kind, fn, j = ("full", full_layer, 0) if at == interval - 1 \
            else ("linear", linear_layer, at)
        x, bal = fn(x, jax.tree.map(lambda a: a[period, j], stacks[kind]))
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


SIZES = ("num_layers", "full_attention_interval", "num_heads",
         "num_kv_heads", "head_dim", "partial_rotary_factor", "rope_theta",
         "linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "num_experts", "top_k", "expert_offset",
         "experts_held", "rms_norm_eps", "aux_loss_coef")


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
    fn = jax.jit(functools.partial(
        micro_batch_loss, sizes={k: sizes[k] for k in SIZES},
        block=min(chunk * ids.shape[-1], TOKEN_BLOCK),
        matmul_dtype=matmul_dtype))
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
    fn = jax.jit(functools.partial(
        micro_batch_loss, sizes={k: sizes[k] for k in SIZES},
        block=min(chunk * ids.shape[-1], TOKEN_BLOCK),
        matmul_dtype=matmul_dtype, per_token=True))
    with jax.default_matmul_precision("highest"):
        nll, scored = fn(params, ids, None if seg is None
                         else jnp.asarray(seg))
    return np.asarray(nll), np.asarray(scored)
