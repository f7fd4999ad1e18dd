"""Plain reference for Granite 4.0-H (huggingface.co/ibm-granite/granite-4.0-
h-small, ``model_type: granitemoehybrid``; the state-space layer: Dao & Gu
2024, "Transformers are SSMs", arXiv:2405.21060): forward pass and loss in
``jax.numpy`` and float32 — no kernel, no chunked scan, no sort, no plan,
no remat, no mixed precision.  Gradients are ``jax.grad`` of
:func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  No biases but the
convolution's.  The head is the embedding table.

    x_0 = embedding_multiplier * E[ids]
    layer l:  x <- x + residual_multiplier * Mixer_l(N(x; w_l))
              x <- x + residual_multiplier * (MoE(h) + Shared(h)),
                                                      h = N(x; w'_l)
    logits = N(x_L; w) E^T / logits_scaling

Layer ``l``'s mixer is of the kind ``layer_kinds[l]`` names (``M`` Mamba-2,
``A`` attention).

``M``, Mamba-2 (Hm heads x P held here, ONE group of N; d_inner = Hm * P):

    [z | x | B | C | dt] = h W_in      widths d_inner | d_inner | N | N | Hm
    [x B C] <- silu(conv([x B C]) + b) depthwise, causal, ``conv_kernel``
        taps, tap K-1 on the current token
    dt_t <- softplus(dt_t + dt_bias)   A = -exp(A_log)    per head, float32
    H_0 = 0;  H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t      H [P, N]
    y_t = H_t C_t + D x_t
    g = y * silu(z);  out = N(g; w_g) W_out     the mean square over the
        d_inner channels held here

written as the literal per-token recurrence, a ``lax.scan`` over tokens —
it has no chunk.  Packed documents: at a document's first token ``H`` is
zero before the write, and the convolution reads zero for a tap in another
document.

``A``, attention (H query heads, KV key/value heads held here, width hd):
``q = h W_q``, ``k = h W_k``, ``v = h W_v``; ``softmax(q k^T *
attention_multiplier) v`` over the keys of the same document at or before
the query — the multiplier in the place of 1/sqrt(hd), **no rotary
embedding and no other position signal**; ``out = attn W_o``.

Experts: ``l = h W_r`` over all ``num_experts``, float32; the ``top_k``
largest are chosen; their weights are a softmax over those ``top_k``
logits; ``MoE(h) = sum_{e chosen, held} w_e W_down,e (silu(W_gate,e h) *
W_up,e h)``, ``Shared`` the same form, added as it is.  **The sum runs over
the experts held here only** (``expert_offset`` .. ``+ experts_held``; the
parameter tree holds just those), the shared expert whole.

**One chip's share.**  The parameter tree is the share's: the Mamba-2 heads,
query heads and key/value heads it was built with, the experts it holds, B,
C, router, shared expert and norms whole.  What the other shares' heads and
experts would have added to a token is left out, in the program and here
alike, and the partial result goes on to the next layer.  The gated norm's
mean square is over the channels held (``mean_square`` hands
:func:`mamba_mixer` another: the share test's, the whole group's).

Loss of a micro-batch: cross-entropy over the positions whose next token is
in the same document + ``aux_loss_coef`` * sum_{layers} num_experts *
sum_e f_e * P_e over ALL experts (f_e = (token, choice) pairs sent to e /
tokens, all k choices counted; P_e = mean over the micro-batch's tokens of
softmax(l)_e over all experts).  The loss of a step is the mean over its
micro-batches.

Assumed (the configuration's ``assumed`` says why): the router loss's form
and that it is per layer over this micro-batch; ``W_in``'s column order.

It runs on the engine's own parameter tree (``blocks = {"ssm": [P, n, ...],
"attn": [P, n, ...]}``, a layer's mixer and expert sublayer together), one
sequence at a time through the mixers, a block of tokens at a time through
the held experts (one expert at a time, the weight 0 where it was not
chosen) and the shared one, the head over blocks.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation); the state's read
``H C`` is a matrix product too.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "ssm", "A": "attn"}

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (ten layers at the published
#: widths as one chip's share, 4,096 packed tokens; PERF.md section 2,
#: PR 66).  The engine (bfloat16 products, float32 state, decays, router
#: and loss) moved the first loss by -4.29e-5 ... +4.48e-5 over 30 runs at
#: 30 seeds and 13 draws of the weights (standard deviation 1.9e-5); the reference with every product's
#: operands rounded to bf16, the engine's own arithmetic, by -1.5e-5 ...
#: +2.4e-5 over six seeds (inside).  The limit is 2.2 times the engine's
#: largest reading and 5 of its standard deviations.  Rounded to fp8 e4m3, the nearest precision below, the
#: reference's mean loss read -5.97e-4 ... +1.34e-4 over the same six
#: seeds, four of them outside and two inside (5.1e-5, 1.9e-6): the
#: reading is signed about zero — the logits start at std 0.08 and a mean
#: over 4,096 tokens averages a token's rounding away — so no limit on the
#: mean holds fp8 out in every seed.  TOKEN_NLL_RMS_ATOL is the limit that
#: does.
LOSS_ATOL = 1e-4

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 66): the engine read 3.4e-4 ... 4.7e-4 at steps 44-82
#: (28 runs) and 1.32e-3 ... 1.37e-3 at step 12 (two runs at 8 warm-up
#: steps), the reference rounded to bf16 7.4e-4 ... 7.8e-4 at step 0 over
#: six seeds; the reference rounded to fp8 e4m3, the nearest precision
#: below, 1.53e-2 ... 1.58e-2 over the same six seeds (outside, every
#: seed).  5e-3 is 3.6 times the engine's largest reading and 0.33 of the
#: control's smallest.
TOKEN_NLL_RMS_ATOL = 5e-3

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time
STATE_BLOCK = 64        # tokens of the recurrence between kept states


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _f32(a):
    return a.astype(jnp.float32)


def _norm(x, w, eps, mean_square=None):
    if mean_square is None:
        mean_square = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * _f32(w)


def _mm(matmul_dtype):
    if matmul_dtype is None:
        return jnp.matmul
    return lambda a, b: jnp.matmul(_f32(a.astype(matmul_dtype)),
                                   _f32(b.astype(matmul_dtype)))


def _keep(remat):
    return jax.checkpoint if remat else (lambda fn: fn)


def _held(sizes, held, whole):
    return sizes.get(held) or sizes[whole]


# ------------------------------------------------------------------ experts
def expert_sublayer(h, moe, sizes, block=TOKEN_BLOCK, matmul_dtype=None,
                    remat=False, shared=True):
    """``MoE(h) + Shared(h)`` over the experts ``moe`` holds, ``h`` [T, D]
    already normalised -> (the branch [T, D], this layer's E * sum_e f_e *
    P_e).  ``shared`` False leaves the shared expert out (the share test
    counts it once)."""
    mm, keep = _mm(matmul_dtype), _keep(remat)
    E, top_k = sizes["num_experts"], sizes["top_k"]
    held = _held(sizes, "experts_held", "num_experts")
    offset = sizes.get("expert_offset", 0)
    block = _fit(h.shape[0], block)
    logits = mm(h, _f32(moe["router"]))                       # [T, E]
    top, chosen = jax.lax.top_k(logits, top_k)
    sent = jax.nn.one_hot(chosen, E, dtype=jnp.float32)       # [T, k, E]
    weights = jnp.einsum("tk,tke->te", jax.nn.softmax(top, axis=-1), sent)
    mine = weights[:, offset:offset + held]   # the rest is held elsewhere

    def swiglu(mb, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(mb, _f32(w_gate))) * mm(mb, _f32(w_up)),
                  _f32(w_down))

    @keep
    def some_tokens(args):
        mb, weight_b = args                       # [block, D], [block, held]

        @keep
        def one_expert(out, held_expert):
            w_gate, w_up, w_down, weight = held_expert   # 0: not chosen
            return out + weight[:, None] * swiglu(mb, w_gate, w_up,
                                                  w_down), None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(mb), (
            moe["w_gate"], moe["w_in"], moe["w_out"], weight_b.T))
        if not shared:
            return routed
        return routed + swiglu(mb, moe["shared_gate"], moe["shared_in"],
                               moe["shared_out"])

    out = jax.lax.map(some_tokens, (
        h.reshape(-1, block, h.shape[-1]),
        mine.reshape(-1, block, held))).reshape(h.shape)
    share = jax.nn.softmax(logits, axis=-1)
    return out, E * jnp.sum(sent.sum(1).mean(0) * share.mean(0))


# -------------------------------------------------------- state-space layer
def _recurrence(x, dt, A, Bt, Ct, first, mm, keep):
    """One sequence, token by token: x [S, Hm, P], dt [S, Hm], A [Hm], Bt,
    Ct [S, N] (the one group's), first [S] (a document's first token).
    -> H_t C_t [S, Hm, P]."""
    S, Hm, Pd = x.shape
    s_block = _fit(S, STATE_BLOCK)

    def token(state, xs):
        x_t, dt_t, B_t, C_t, first_t = xs
        state = state * jnp.where(first_t, 0.0,
                                  jnp.exp(dt_t * A))[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return state, mm(state, C_t[:, None])[:, :, 0]

    @keep
    def some_tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    split = lambda a: a.reshape((-1, s_block) + a.shape[1:])
    _, y = jax.lax.scan(
        some_tokens, jnp.zeros((Hm, Pd, Bt.shape[-1]), jnp.float32),
        tuple(split(a) for a in (x, dt, Bt, Ct, first)))
    return y.reshape(S, Hm, Pd)


def _conv(x, w, bias, seg):
    """x [S, C], w [K, C], bias [C], seg [S]."""
    K = w.shape[0]
    y = x * w[K - 1]
    for back in range(1, K):
        past = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
        same = jnp.concatenate([jnp.zeros((back,), bool),
                                seg[back:] == seg[:-back]])
        y = y + jnp.where(same[:, None], past, 0.0) * w[K - 1 - back]
    return y + bias


def mamba_gated(h, p, sizes, segments, matmul_dtype=None, remat=False):
    """``y * silu(z)`` before the norm, of the heads ``p`` was built with:
    ``h`` [b, S, D] already normalised, ``segments`` [b, S] -> [b, S,
    d_inner]."""
    mm, keep = _mm(matmul_dtype), _keep(remat)
    b, S, _ = h.shape
    Hm = _held(sizes, "mamba_heads_held", "mamba_num_heads")
    Pd, N = sizes["mamba_head_dim"], sizes["ssm_state_size"]
    d_in = Hm * Pd
    conv_ch = d_in + 2 * N
    zxbcdt = mm(h, _f32(p["w_in"]))
    A, D = -jnp.exp(_f32(p["A_log"])), _f32(p["D"])

    def one_sequence(args):
        row, seg = args
        xbc = jax.nn.silu(_conv(row[:, d_in:d_in + conv_ch],
                                _f32(p["conv_w"]), _f32(p["conv_b"]), seg))
        dt = jax.nn.softplus(row[:, d_in + conv_ch:] + _f32(p["dt_bias"]))
        xs = xbc[:, :d_in].reshape(S, Hm, Pd)
        first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
        y = _recurrence(xs, dt, A, xbc[:, d_in:d_in + N], xbc[:, d_in + N:],
                        first, mm, keep) + D[:, None] * xs
        return y.reshape(S, d_in) * jax.nn.silu(row[:, :d_in])

    return jax.lax.map(one_sequence, (zxbcdt, segments))


def mamba_mixer(h, p, sizes, segments, mean_square=None, matmul_dtype=None,
                remat=False):
    """The Mamba-2 branch of the heads ``p`` was built with: ``N(g; w_g)
    W_out`` [b, S, D].  ``mean_square`` [b, S, 1]: the statistic the gated
    norm divides by (None: this tree's own channels')."""
    gated = mamba_gated(h, p, sizes, segments, matmul_dtype, remat)
    return _mm(matmul_dtype)(
        _norm(gated, p["gate_norm"], sizes["norm_eps"], mean_square),
        _f32(p["w_out"]))


# ---------------------------------------------------------------- attention
def attention_mixer(h, p, sizes, segments, matmul_dtype=None, remat=False):
    """The attention branch of the heads ``p`` was built with: ``h`` [b, S,
    D] already normalised -> ``attn W_o`` [b, S, D]."""
    mm, keep = _mm(matmul_dtype), _keep(remat)
    b, S, _ = h.shape
    H = _held(sizes, "attn_heads_held", "num_heads")
    KV = _held(sizes, "kv_heads_held", "num_kv_heads")
    hd = sizes["head_dim"]
    q_block = _fit(S, QUERY_BLOCK)

    def attention(q, k, v, seg):
        """One sequence: q [S, H, hd], k and v [S, KV, hd], seg [S]."""
        k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
        kT, vT = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # per head

        @keep
        def some_queries(args):
            qb, pos, seg_q = args                 # [qb, H, hd], [qb], [qb]
            scores = mm(qb.transpose(1, 0, 2), kT) \
                * sizes["attention_multiplier"]
            seen = (pos[:, None] >= jnp.arange(S)[None, :]) \
                & (seg_q[:, None] == seg[None, :])
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, vT).transpose(1, 0, 2)           # [qb, H, hd]

        out = jax.lax.map(some_queries, (
            q.reshape(-1, q_block, H, hd),
            jnp.arange(S).reshape(-1, q_block), seg.reshape(-1, q_block)))
        return out.reshape(S, H, hd)

    q = mm(h, _f32(p["wq"])).reshape(b, S, H, hd)
    k = mm(h, _f32(p["wk"])).reshape(b, S, KV, hd)
    v = mm(h, _f32(p["wv"])).reshape(b, S, KV, hd)
    attn = jax.lax.map(lambda a: attention(*a), (q, k, v, segments))
    return mm(attn.reshape(b, S, H * hd), _f32(p["wo"]))


# ------------------------------------------------------------------ the loss
def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=False,
                     multipliers=None):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, every position's negative log
    likelihood of the next token [b, S] and which positions are scored
    (:func:`token_losses`).  Differentiable in ``params``; ``remat`` keeps
    only each sublayer's, each expert's, each block of queries' and every
    ``STATE_BLOCK``-th token's inputs for the gradient (the same
    arithmetic: what ``jax.grad`` at the published widths needs to fit one
    chip, scripts/olmoe_grad_check.py).  ``multipliers``: the four muP
    scalars by their ``sizes`` names, laid over the configuration's (the
    tests' controls)."""
    keep, mm = _keep(remat), _mm(matmul_dtype)
    sizes = {**sizes, **(multipliers or {})}
    b, S = ids.shape
    T = b * S
    D = params["wte"].shape[-1]
    eps, res = sizes["norm_eps"], sizes["residual_multiplier"]
    kinds = sizes["layer_kinds"]
    block = _fit(T, block)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)
    mixers = {"ssm": mamba_mixer, "attn": attention_mixer}

    def layer(kind):
        @keep
        def mixed(x, p):
            return x + res * mixers[kind](
                _norm(x, p["norm"], eps), p, sizes, segments,
                matmul_dtype=matmul_dtype, remat=remat)

        @keep
        def fed(x, p):
            out, balance = expert_sublayer(
                _norm(x, p["mlp_norm"], eps).reshape(T, D), p["moe"], sizes,
                block, matmul_dtype, remat)
            return x + res * out.reshape(b, S, D), balance

        return lambda x, p: fed(mixed(x, p), p)

    x = sizes["embedding_multiplier"] * _f32(params["wte"][ids])
    # the stacks are [periods, layers of the kind in a period, ...]: layer
    # l is the next of its kind, periods outermost
    seen = dict.fromkeys(KINDS.values(), 0)
    balance = 0.0
    for letter in kinds[:sizes["num_layers"]]:
        kind = KINDS[letter]
        stack = params["blocks"][kind]
        per_period = jax.tree.leaves(stack)[0].shape[1]
        at = divmod(seen[kind], per_period)
        seen[kind] += 1
        x, bal = layer(kind)(x, jax.tree.map(lambda a: a[at], stack))
        balance = balance + bal
    x = _norm(x, params["final_norm"], eps).reshape(T, D) \
        / sizes["logits_scaling"]
    head = _f32(params["wte"]).T

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
    ce = jnp.sum(nll * scored) / jnp.maximum(scored.sum(), 1.0)
    return ce + sizes["aux_loss_coef"] * balance


SIZES = ("num_layers", "layer_kinds", "num_heads", "num_kv_heads",
         "head_dim", "attn_heads_held", "kv_heads_held", "mamba_num_heads",
         "mamba_heads_held", "mamba_head_dim", "ssm_state_size",
         "num_experts", "top_k", "expert_offset", "experts_held",
         "embedding_multiplier", "residual_multiplier",
         "attention_multiplier", "logits_scaling", "norm_eps",
         "aux_loss_coef")


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
