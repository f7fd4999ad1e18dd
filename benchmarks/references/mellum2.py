"""Plain reference for Mellum 2 (huggingface.co/JetBrains/
Mellum2-12B-A2.5B-Instruct, ``model_type: mellum``): forward pass and loss
in ``jax.numpy`` and float32 — no kernel, no scan over layers, no sort, no
plan, no exchange, no mixed precision, dense masks.  Gradients are
``jax.grad`` of :func:`micro_batch_loss`.  The equations follow the
published config's keys; the conventions it does not state are ``assumed``
(the configuration's file lists them): ``silu`` as the hidden activation,
no norm on q or k, no prediction module (the file has no key for one).

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  No bias anywhere.

Layer l is *full* where ``l % full_attention_interval ==
full_attention_interval - 1`` (the last of its period) and *sliding*
otherwise; H = ``num_heads`` query heads in both kinds, KV =
``num_kv_heads``, hd = ``head_dim``:

    h = N(x)    q = h W_q [S, H, hd]    k = h W_k, v = h W_v [S, KV, hd]
    rotary on all hd dimensions, dim i with i + hd/2, angle pos * f_i, pos
    the position along the sequence (not reset at a document):
      sliding: f_i = sliding_rope_theta^(-2i/hd)
      full:    with e_i = rope_theta^(-2i/hd), f_i = e_i (1 - r_i) + e_i /
               rope_factor * r_i, r_i = clip((i - low) / (high - low), 0,
               1), low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
               c(t) = hd * ln(original_max_position_embeddings / (2 pi
               t)) / (2 ln rope_theta)  [YaRN, arXiv:2309.00071]; cos and
               sin times attention_factor
    P = softmax of q k^T / sqrt(hd) over the keys j <= i of i's document
        and, in a sliding layer, with i - j < sliding_window;
        query head n reads KV head n // (H / KV)
    x <- x + concat_heads(P v) W_o

written as the plain masked ``[queries, S]`` softmax, a block of one
sequence's queries at a time against all of the sequence's keys.

Then, in every layer: ``h' = N(x)``, ``p = softmax(h' W_r)`` over all
``num_experts``; the ``top_k`` largest; weights ``p_e / sum_chosen p``;
``x <- x + sum_{e chosen} w_e SwiGLU_e(h')``.  **The sum runs over every
expert**: nothing is held back here, so this is the uncut layer, and what
the chips of an expert-parallel host compute together has to equal it.
Final ``N``, untied head.

Loss of a micro-batch: mean cross-entropy over the positions t whose next
token is in the same document ``+ aux_loss_coef * sum_{layers}
num_experts * sum_e f_e * P_e`` over all experts (f_e = (token, choice)
pairs sent to e / tokens; P_e = mean over the micro-batch's tokens of
p_e; the micro-batch is every chip's sequences together).  The loss of a
step is the mean over its micro-batches.

Departures from a literal transcription, none of which changes a number
beyond float32 rounding: it runs on the engine's own parameter tree
(``blocks`` = two stacks, sliding ``[periods, interval - 1, ...]`` and
full ``[periods, 1, ...]``; ``tail``), sharded as the engine keeps it (the
partitioner gathers what a product needs), walked in the stack's order;
every token goes through every expert, one expert at a time (the weight 0
where it was not chosen), the head takes a block of every sequence's
positions at a time and attention a block of a sequence's queries, the
sequences side by side — so that on the engine's four chips each works on
the sequence it holds.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  From two
#: readings on the chip at the cell's own size (PERF.md section 2, PR 47):
#: the engine moved it by at most 1.64e-4 over 8 runs at 8 seeds, and the
#: control (scripts/reference_control.py, this file with every product's
#: operands rounded) by at most 2.3e-4 in bf16 - inside - and by 6.1e-4,
#: 8.9e-3 and 1.7e-2 in fp8 e4m3: not outside in every seed, so the limit
#: that holds fp8 out in every seed is the next one.
LOSS_ATOL = 1e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  Between its two readings on the chip (PERF.md section 2,
#: PR 47): the engine 1.07e-2 ... 1.15e-2 over 8 runs (the bf16 control
#: 9.5e-3), the fp8 e4m3 control 0.129 ... 0.158: 3.5 x above the first,
#: 3.2 x below the second.
TOKEN_NLL_RMS_ATOL = 0.04

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def inverse_frequencies(sizes, full):
    """(f_i [rot / 2] float64, the factor on cos and sin) of a layer kind,
    as the docstring writes them."""
    hd = sizes["head_dim"]
    if not full:
        return sizes["sliding_rope_theta"] ** (
            -np.arange(0, hd, 2, dtype=np.float64) / hd), 1.0
    rot = hd
    theta = sizes["rope_theta"]
    e = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def c(turns):
        return rot * math.log(sizes["original_max_position_embeddings"]
                              / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(c(sizes["beta_fast"])), 0)
    high = min(math.ceil(c(sizes["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    r = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    return e * (1 - r) + e / sizes["rope_factor"] * r, \
        sizes["attention_factor"]


def _rotary(x, freqs, factor):
    """x [S, heads, hd]: dim i and i + rot/2 of the first ``rot = 2
    len(freqs)`` turned by pos * f_i, cos and sin times ``factor``."""
    S, rot = x.shape[0], 2 * len(freqs)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None]
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., rot:]], axis=-1)


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=False):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, every position's negative log
    likelihood of the next token [b, S] and which positions are scored.
    Differentiable in ``params``; ``remat`` keeps only each layer's, each
    expert's and each block of queries' inputs for the gradient (the same
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
    KV, hd = sizes["num_kv_heads"], sizes["head_dim"]
    window = sizes["sliding_window"]
    interval = sizes["full_attention_interval"]
    E, top_k = sizes["num_experts"], sizes["top_k"]
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    # ------------------------------------------------------------ attention
    def attention(q, k, v, seg, full):
        """One sequence: q [S, H, hd], k, v [S, KV, hd], seg [S]."""
        H = q.shape[1]
        # query head n reads KV head n // (H / KV)
        kT = jnp.repeat(k, H // KV, axis=1).transpose(1, 2, 0)
        vT = jnp.repeat(v, H // KV, axis=1).transpose(1, 0, 2)

        @keep
        def some_queries(args):
            qb, pos, seg_q = args
            scores = mm(qb.transpose(1, 0, 2), kT) / jnp.sqrt(float(hd))
            behind = pos[:, None] - jnp.arange(S)[None, :]     # i - j
            seen = (behind >= 0) & (seg_q[:, None] == seg[None, :])
            if not full:
                seen &= behind < window
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, vT).transpose(1, 0, 2)            # [qb, H, hd]

        out = jax.lax.map(some_queries, (
            q.reshape(-1, q_block, H, hd),
            jnp.arange(S).reshape(-1, q_block), seg.reshape(-1, q_block)))
        return out.reshape(S, H, hd)

    def attention_layer(x, p, full):
        H = sizes["num_heads"]
        freqs, factor = inverse_frequencies(sizes, full)
        h = _norm(x, p["attn_norm"], eps)
        q = mm(h, f32(p["wq"])).reshape(b, S, H, hd)
        k = mm(h, f32(p["wk"])).reshape(b, S, KV, hd)
        v = mm(h, f32(p["wv"])).reshape(b, S, KV, hd)

        def one_sequence(args):
            q, k, v, seg = args
            return attention(_rotary(q, freqs, factor),
                             _rotary(k, freqs, factor), v, seg, full)

        # (the sequences side by side: where they lie on several chips
        # each chip attends over its own)
        attn = jax.vmap(lambda *a: one_sequence(a))(q, k, v, segments)
        return x + mm(attn.reshape(T, H * hd), f32(p["wo"]))

    # ---------------------------------------------------------- feed-forward
    def swiglu(m, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(m, f32(w_gate))) * mm(m, f32(w_up)),
                  f32(w_down))

    @functools.partial(keep, static_argnums=2) if remat else keep
    def expert_layer(x, p, full):
        x = attention_layer(x, p, full)
        m = _norm(x, p["mlp_norm"], eps)
        moe = p["moe"]
        probs = jax.nn.softmax(mm(m, f32(moe["router"])), axis=-1)  # [T, E]
        _, chosen = jax.lax.top_k(probs, top_k)
        sent = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(1)  # [T, E]
        picked = probs * sent
        weights = picked / picked.sum(-1, keepdims=True)

        @keep
        def one_expert(out, expert):
            w_gate, w_up, w_down, weight = expert           # 0: not chosen
            return out + weight[:, None] * swiglu(
                m, w_gate, w_up, w_down), None

        # every token through every expert, one expert at a time
        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m), (
            moe["w_gate"], moe["w_in"], moe["w_out"], weights.T))
        return x + out, E * jnp.sum(sent.mean(0) * probs.mean(0))

    # ------------------------------------------------------------- the head
    head = f32(params["lm_head"])

    def token_nll(x, norm_w, targets):
        x = _norm(x, norm_w, eps)

        @keep
        def some_tokens(args):
            xb, target = args                     # [b, block, D], [b, block]
            logits = mm(xb, head)
            return jax.scipy.special.logsumexp(logits, axis=-1) \
                - jnp.take_along_axis(logits, target[..., None],
                                      axis=-1)[..., 0]

        # a block of every sequence's positions at a time
        s_block = _fit(S, block)
        nll = jax.lax.map(some_tokens, (
            x.reshape(b, -1, s_block, x.shape[-1]).swapaxes(0, 1),
            targets.reshape(b, -1, s_block).swapaxes(0, 1)))
        return nll.swapaxes(0, 1).reshape(b, S)

    # ------------------------------------------------------------ the stack
    x = f32(params["wte"][ids.reshape(T)])
    balance = 0.0
    periods = sizes["num_layers"] // interval
    for layer in range(sizes["num_layers"]):
        # a period's last layer is the full one
        period, i = divmod(layer, interval)
        full = i == interval - 1
        if period < periods:
            stack = params["blocks"]["full" if full else "sliding"]
            at = (period, 0 if full else i)
        else:           # the sliding layers after the last whole period
            stack, at = params["tail"], i
        x, bal = expert_layer(x, jax.tree.map(lambda a: a[at], stack), full)
        balance = balance + bal
    # position t is scored against token t+1 where both are of one
    # document; a sequence's last position has no next token
    scored = (segments == jnp.roll(segments, -1, axis=1)) \
        & (jnp.arange(S) < S - 1)[None, :]
    nll = token_nll(x, params["final_norm"], jnp.roll(ids, -1, axis=1))
    if per_token:
        return nll, scored
    scored = scored.astype(jnp.float32)
    return jnp.sum(nll * scored) / jnp.maximum(scored.sum(), 1.0) \
        + sizes["aux_loss_coef"] * balance


SIZES = ("num_layers", "full_attention_interval", "num_heads",
         "num_kv_heads", "head_dim", "sliding_window", "sliding_rope_theta",
         "rope_theta", "rope_factor", "original_max_position_embeddings",
         "beta_fast", "beta_slow", "attention_factor", "num_experts",
         "top_k", "norm_eps", "aux_loss_coef")


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
