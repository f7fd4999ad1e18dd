"""Plain reference for Kimi-Linear-48B-A3B (huggingface.co/moonshotai/Kimi-
Linear-48B-A3B-Instruct, ``model_type: kimi_linear``; Kimi Team 2025, "Kimi
Linear: An Expressive, Efficient Attention Architecture", arXiv:2510.26692
sections 3-4; the parent rule: Gated DeltaNet, arXiv:2412.06464): forward
pass and loss in ``jax.numpy`` and float32 — no kernel, no chunked form, no
scan over layers, no sort, no plan, no mixed precision.  Gradients are
``jax.grad`` of :func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  No bias anywhere.  Layer i
(from 1) has the mixer ``layer_kinds[i - 1]`` names: ``K`` Kimi Delta
Attention, ``M`` latent attention.  Every layer: ``x <- x + Mixer(N(x))``,
``x <- x + FFN(N(x))``.  Untied head, final ``N``.

KDA mixer (H heads of ``hd = kda_head_dim`` for keys and values alike), ``h
= N(x)``:

    [q | k | v] = silu(conv(h W_qkv))     three depthwise causal convolutions
        of ``short_conv_kernel_size`` taps, no bias; tap K-1 on the current
        token; a tap that reaches into the previous document reads zero
    q~ = l2norm(q) / sqrt(hd)   k~ = l2norm(k)
        l2norm(x) = x / sqrt(sum x^2 + 1e-6)
    g_t = -exp(A_log[head]) * softplus((h W_f_down) W_f_up + dt_bias)
        in R^hd: the log-decay is a VECTOR, one entry a key channel
    beta_t = sigmoid(h W_beta)[head]
    S_0 = 0;  S <- Diag(exp(g_t)) S_{t-1}
    S_t = S + k~_t (x) beta_t (v_t - S^T k~_t)
    o_t = S_t^T q~_t                               S [hd, hd] per head
    y_t = RMSNorm(o_t; w_o, over the head) * sigmoid((h W_g_down) W_g_up)
    out = y W_out

written as the literal per-token recurrence, a ``lax.scan`` over tokens
that carries ``[H, hd, hd]``.  At a document's first token ``S`` is zero
before the write.

Latent attention without positions (``mla_use_nope``; H heads; ``nope``,
``rot``, ``vd`` = ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``):

    q = h W_q   per head nope + rot wide: one matrix, no query latent
    [c | k_s] = h W_dkv  (kv_lora_rank | rot)      c <- N(c)
    [k_n | v] = c W_ukv  per head;  k = [k_n ; k_s], k_s ONE part for all heads
    NOTHING is rotated
    P = causal softmax of q k^T / sqrt(nope + rot) inside a document
    out = concat_heads(P v) W_o

written as the plain masked ``[queries, S]`` softmax, a block of one
sequence's queries at a time against all of the sequence's keys.

Layer 1: a KDA mixer, then ``x + W_down(silu(W_gate h) * W_up h)``.  Layers
2..: the layer's mixer, then experts: ``s = sigmoid(h W_r)`` over all
``num_experts``; the ``top_k`` largest of ``s + e_score_correction_bias``
are chosen; their weights are ``s`` (without the bias) over the chosen
ones' sum, times ``routed_scaling_factor``; ``MoE(h) = sum_{e chosen,
held} w_e SwiGLU_e(h) + SwiGLU_shared(h)``.  **The sum runs over the
experts held here only** (``expert_offset`` .. ``+ experts_held``; the
parameter tree holds just those), the shared expert whole: one chip's
share of an expert-parallel layer, the partial result going on to the next
layer, as in the program.

Loss of a micro-batch: the mean cross-entropy over the positions t whose
next token is in the same document + ``aux_loss_coef * sum_{expert layers}
num_experts * sum_e f_e * P_e`` over ALL experts (f_e = (token, choice)
pairs sent to e / tokens; P_e = mean over the micro-batch's tokens of ``s_e
/ sum_e' s_e'``).  The loss of a step is the mean over its micro-batches.

It runs on the engine's own parameter tree: ``lead``, and ``blocks`` =
{``run<i>``: {``kda``: [periods, KDA layers of a period, ...], ``mla``:
[periods, 1, ...]}} where a period is the KDA layers up to and with the
next latent-attention layer and equal periods in a row are one run
(:func:`layers_in_order` walks it from ``layer_kinds`` alone).  A block of
tokens at a time goes through the held experts (one expert at a time, the
weight 0 where it was not chosen), the shared one and the head.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation); the state's own
products are matrix products too.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (the first eight layers at
#: the published widths, one micro-batch of 16,384 packed tokens; PERF.md
#: section 2, PR 60).  The engine (bfloat16 products, float32 decays,
#: inverse, state, norms, router, softmax and loss) moved the loss by at
#: most 4.53e-4 over 24 runs and 24 seeds (-4.53e-4 ... +3.64e-4); the
#: reference with every product's operands rounded to bf16, the engine's own
#: arithmetic, by 4.5e-5 to 1.2e-4 over 3 seeds (inside).  1.5e-3 is 3.3
#: times the engine's largest reading.  It does NOT separate the precision
#: below in every seed: rounded to fp8 e4m3 the reference's mean loss read
#: 1.7e-4, 3.8e-3 and 5.7e-3 from the float32 one, one seed of three inside
#: - a mean over 16,384 tokens averages rounding away, as in the other
#: hybrids.  TOKEN_NLL_RMS_ATOL is the limit that catches it.
LOSS_ATOL = 1.5e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 60): the engine read 2.9e-2 to 3.8e-2 over 24 runs and 24
#: seeds (the reference rounded to bf16: 1.68e-2 to 1.79e-2 over 3 seeds);
#: the reference rounded to fp8 e4m3, the nearest precision below, 0.213 to
#: 0.216 over the same 3 seeds (outside, every seed).  0.1 is 2.6 times the
#: engine's largest reading and 0.47 of the control's smallest.
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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def layers_in_order(blocks, kinds):
    """[(letter, that layer's parameters)] of layers 2.. from the engine's
    ``blocks`` and ``kinds`` (``layer_kinds`` cut to the depth): periods
    end with their ``M``; equal periods in a row share a run's stacks."""
    periods, current = [], ""
    for letter in kinds[1:]:
        current += letter
        if letter == "M":
            periods.append(current)
            current = ""
    if current:
        periods.append(current)
    layers, run, at = [], -1, 0
    for i, period in enumerate(periods):
        if i == 0 or period != periods[i - 1]:
            run, at = run + 1, 0
        stacks = blocks[f"run{run}"]
        seen = {"K": 0, "M": 0}
        for letter in period:
            stack = stacks[{"K": "kda", "M": "mla"}[letter]]
            j = seen[letter]
            layers.append((letter, jax.tree.map(
                lambda a, at=at, j=j: a[at, j], stack)))
            seen[letter] += 1
        at += 1
    return layers


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
    Hk, hd = sizes["kda_num_heads"], sizes["kda_head_dim"]
    K = sizes["short_conv_kernel_size"]
    H, rkv = sizes["num_heads"], sizes["kv_lora_rank"]
    nope, rot, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    E, top_k = sizes["num_experts"], sizes["top_k"]
    held = sizes.get("experts_held") or E
    offset = sizes.get("expert_offset", 0)
    kinds = sizes["layer_kinds"][:sizes["num_layers"]]
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    s_block = _fit(S, STATE_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    # ------------------------------------------------- Kimi Delta Attention
    def delta_rule(q, k, v, g, beta, first):
        """One sequence, token by token: q, k, v, g [S, Hk, hd], beta [S,
        Hk], first [S] (a document's first token)."""

        def token(state, xs):
            q_t, k_t, v_t, g_t, b_t, first_t = xs
            # a row of the state a key channel: each decays by its own
            state = state * jnp.where(first_t, 0.0, jnp.exp(g_t))[:, :, None]
            read = mm(k_t[:, None, :], state)[:, 0]           # S^T k
            state = state + k_t[:, :, None] \
                * (b_t[:, None] * (v_t - read))[:, None, :]
            return state, mm(q_t[:, None, :], state)[:, 0]    # S^T q

        @keep
        def some_tokens(state, xs):
            return jax.lax.scan(token, state, xs)

        split = lambda a: a.reshape((-1, s_block) + a.shape[1:])
        _, o = jax.lax.scan(
            some_tokens, jnp.zeros((Hk, hd, hd), jnp.float32),
            tuple(split(a) for a in (q, k, v, g, beta, first)))
        return o.reshape(S, Hk, hd)

    def conv(x, w, seg):
        """x [S, C], w [K, C], seg [S]."""
        y = x * w[K - 1]
        for back in range(1, K):
            past = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
            same = jnp.concatenate([jnp.zeros((back,), bool),
                                    seg[back:] == seg[:-back]])
            y = y + jnp.where(same[:, None], past, 0.0) * w[K - 1 - back]
        return y

    def kda(x, p):
        n = _norm(x, p["attn_norm"], eps)
        qkv = mm(n, f32(p["w_qkv"]))
        beta = jax.nn.sigmoid(mm(n, f32(p["w_beta"])))
        g = -jnp.exp(f32(p["A_log"]))[:, None] * jax.nn.softplus(
            mm(mm(n, f32(p["w_f_down"])), f32(p["w_f_up"])).reshape(T, Hk, hd)
            + f32(p["dt_bias"]).reshape(Hk, hd))
        gate = jax.nn.sigmoid(
            mm(mm(n, f32(p["w_g_down"])), f32(p["w_g_up"])))

        def one_sequence(args):
            qkv, g_s, beta_s, seg = args
            qkv = jax.nn.silu(conv(qkv, f32(p["conv_w"]), seg))
            q, k, v = (qkv[:, i * Hk * hd:(i + 1) * Hk * hd].reshape(
                S, Hk, hd) for i in range(3))
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     seg[1:] != seg[:-1]])
            return delta_rule(_l2norm(q) / jnp.sqrt(float(hd)), _l2norm(k),
                              v, g_s, beta_s, first)

        o = jax.lax.map(one_sequence, (
            qkv.reshape(b, S, -1), g.reshape(b, S, Hk, hd),
            beta.reshape(b, S, Hk), segments))                # [b,S,Hk,hd]
        y = _norm(o, p["o_norm"], eps) * gate.reshape(b, S, Hk, hd)
        return x + mm(y.reshape(T, Hk * hd), f32(p["w_out"]))

    # ------------------------------------------------------ latent attention
    def attention(q, k, v, seg):
        """One sequence: q, k [S, H, nope + rot], v [S, H, vd], seg [S]."""
        kT, vT = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # per head

        @keep
        def some_queries(args):
            qb, pos, seg_q = args
            scores = mm(qb.transpose(1, 0, 2), kT) \
                / jnp.sqrt(float(nope + rot))
            seen = (pos[:, None] >= jnp.arange(S)[None, :]) \
                & (seg_q[:, None] == seg[None, :])
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, vT).transpose(1, 0, 2)           # [qb, H, vd]

        out = jax.lax.map(some_queries, (
            q.reshape(-1, q_block, H, nope + rot),
            jnp.arange(S).reshape(-1, q_block), seg.reshape(-1, q_block)))
        return out.reshape(S, H, vd)

    def mla(x, p):
        h = _norm(x, p["attn_norm"], eps)
        q = mm(h, f32(p["w_q"])).reshape(b, S, H, nope + rot)
        down = mm(h, f32(p["w_dkv"])).reshape(b, S, rkv + rot)
        c = _norm(down[..., :rkv], p["kv_norm"], eps)
        kv = mm(c, f32(p["w_ukv"])).reshape(b, S, H, nope + vd)

        def one_sequence(args):
            q, kv, k_s, seg = args
            k = jnp.concatenate(
                [kv[..., :nope], jnp.repeat(k_s[:, None, :], H, axis=1)],
                axis=-1)
            return attention(q, k, kv[..., nope:], seg)

        attn = jax.lax.map(one_sequence, (q, kv, down[..., rkv:], segments))
        return x + mm(attn.reshape(T, H * vd), f32(p["w_o"]))

    mixer = {"K": kda, "M": mla}

    # ---------------------------------------------------------- feed-forward
    def swiglu(m, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(m, f32(w_gate))) * mm(m, f32(w_up)),
                  f32(w_down))

    @keep
    def lead_layer(x, p):
        x = kda(x, p)
        m = _norm(x, p["mlp_norm"], eps)
        return x + jax.lax.map(
            lambda mb: swiglu(mb, p["w_gate"], p["w_up"], p["w_down"]),
            m.reshape(-1, block, m.shape[-1])).reshape(x.shape)

    def experts(x, p):
        m = _norm(x, p["mlp_norm"], eps)
        moe = p["moe"]
        scores = jax.nn.sigmoid(mm(m, f32(moe["router"])))    # [T, E]
        _, chosen = jax.lax.top_k(
            scores + f32(moe["e_score_correction_bias"]), top_k)
        sent = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(1)  # [T, E]
        picked = scores * sent
        weights = picked / picked.sum(-1, keepdims=True) \
            * sizes["routed_scaling_factor"]
        mine = weights[:, offset:offset + held]   # the rest is held elsewhere

        @keep
        def some_tokens(args):
            mb, weight_b = args                   # [block, D], [block, held]

            @keep
            def one_expert(out, held_expert):
                w_gate, w_up, w_down, weight = held_expert  # 0: not chosen
                return out + weight[:, None] * swiglu(
                    mb, w_gate, w_up, w_down), None

            routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(mb), (
                moe["w_gate"], moe["w_in"], moe["w_out"], weight_b.T))
            return routed + swiglu(mb, moe["shared_gate"], moe["shared_in"],
                                   moe["shared_out"])

        out = jax.lax.map(some_tokens, (
            m.reshape(-1, block, m.shape[-1]),
            mine.reshape(-1, block, held))).reshape(x.shape)
        share = scores / scores.sum(-1, keepdims=True)
        return x + out, E * jnp.sum(sent.mean(0) * share.mean(0))

    expert_layer = {letter: keep(lambda x, p, mix=mix: experts(mix(x, p), p))
                    for letter, mix in mixer.items()}

    # ------------------------------------------------------------ the stack
    if kinds[0] != "K":
        raise ValueError("kimi_linear reference: layer 1's mixer is KDA")
    x = lead_layer(f32(params["wte"][ids.reshape(T)]), params["lead"])
    balance = 0.0
    for letter, layer in layers_in_order(params["blocks"], kinds):
        x, bal = expert_layer[letter](x, layer)
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


SIZES = ("num_layers", "layer_kinds", "kda_num_heads", "kda_head_dim",
         "short_conv_kernel_size", "num_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts",
         "top_k", "routed_scaling_factor", "expert_offset", "experts_held",
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
