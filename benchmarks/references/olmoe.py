"""Plain reference for OLMoE (Muennighoff et al. 2024, arXiv:2409.02060;
huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct): forward pass and loss in
``jax.numpy`` and float32 — no kernel, no sort, no capacity, no remat, no
mixed precision.  Gradients are ``jax.grad`` of :func:`micro_batch_loss`.

Per layer, on the residual stream x [tokens, D]:

    n = RMSNorm(x)
    q = rope(RMSNorm_q(W_q n)),  k = rope(RMSNorm_k(W_k n))
        each norm over the whole projection, before the heads are split,
        with its own learned scale; rotate-half pairing (dim i with
        i + hd/2), base ``rope_theta``, positions 0..S-1 over the packed
        sequence (not reset per document)
    h = x + W_o causal-attention(q, k, W_v n)     inside a document
    m = RMSNorm(h)
    p = softmax(W_r m)                            float32, over all experts
    y = h + sum_{e in top-k(p)} p_e W2_e(silu(W1_e m) * W3_e m)
        the chosen gates are NOT renormalised; nothing is dropped

then the final RMSNorm and an untied head.  Loss of a micro-batch:

    cross-entropy over the positions whose next token is in the same
        document
    + aux_loss_coef      * sum_layers E * sum_e f_e * P_e
    + router_z_loss_coef * sum_layers mean_t logsumexp(W_r m)^2

P_e = mean over the micro-batch's tokens of p_e; f_e = (token, choice)
pairs sent to e / tokens, all k choices counted (Hugging Face
``load_balancing_loss_func``; sum_e f_e = k).  The loss of a step is the
mean over its micro-batches.

It runs on the engine's own parameter tree, beside the engine's state, so
nothing large is ever whole in float32: attention takes one sequence and
one block of queries at a time, the experts run one at a time (its three
matrices cast as they are used) over blocks of tokens, with every token
through every expert and the gate 0 where the expert was not chosen — so
no [tokens, E, F] tensor exists — and the head takes a block of tokens at
a time.  f_e, P_e and the z-loss are statistics of the whole micro-batch:
they are taken over all its tokens before the product.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation), which is what
computing in a lower precision than the configuration states does.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (2 layers at the published
#: widths, 8 micro-batches of 4,096 packed tokens; PERF.md section 2, PR
#: 28).  The engine computes in bfloat16 with float32 softmax, router and
#: loss: over 25 seeds it moved the loss by at most 5.3e-4, and the
#: reference with bf16 products (``matmul_dtype``) by at most 5.7e-4 over
#: four — the engine's own arithmetic, so that is no control.  The control
#: is the nearest precision below: fp8 e4m3 products moved it by 1.9e-2 to
#: 5.3e-2.  The tolerance is 3.5 times the largest bf16 reading and a
#: ninth of the smallest fp8 one; a departure left out (QK-norm, the
#: gates' normalisation, either router loss, the document mask) is 1e-3
#: to 1e-1 even at toy size (tests/test_olmoe.py).
LOSS_ATOL = 2e-3

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, hd], positions 0..S-1, dim i paired with i + hd/2."""
    S, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd // 2) / (hd // 2))
    angles = jnp.arange(S)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(fn, x, block):
    """``fn`` over ``x`` [n, ...] in blocks of ``block`` rows, one at a
    time; results joined back to [n, ...]."""
    out = jax.lax.map(fn, x.reshape((-1, block) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block.  Differentiable in ``params``; ``remat`` keeps only
    each layer's and each expert's inputs for the gradient (the same
    arithmetic: what ``jax.grad`` at the published widths needs to fit
    one chip, scripts/olmoe_grad_check.py)."""
    keep = jax.checkpoint if remat else (lambda fn: fn)
    f32 = lambda a: a.astype(jnp.float32)
    if matmul_dtype is None:
        mm = jnp.matmul
    else:
        mm = lambda a, b: jnp.matmul(f32(a.astype(matmul_dtype)),
                                     f32(b.astype(matmul_dtype)))
    b, S = ids.shape
    T = b * S
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    E, top_k, eps = sizes["num_experts"], sizes["top_k"], sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    def attention(q, k, v, seg):
        """One sequence: q [S, H, hd], k and v [S, KV, hd], seg [S]."""
        k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
        kT, vT = k.transpose(1, 2, 0), v.transpose(1, 0, 2)   # per head

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
        return out.reshape(S, H * hd)

    @keep
    def layer(x, p):
        n = _rms_norm(x, p["attn_norm"], eps)
        q, k, v = (mm(n, f32(p[w])) for w in ("wq", "wk", "wv"))
        q = _rms_norm(q, p["q_norm"], eps)
        k = _rms_norm(k, p["k_norm"], eps)

        def one_sequence(args):
            qs, ks, vs, seg = args
            return attention(_rope(qs.reshape(S, H, hd), theta),
                             _rope(ks.reshape(S, KV, hd), theta),
                             vs.reshape(S, KV, hd), seg)

        attn = jax.lax.map(one_sequence, (
            q.reshape(b, S, -1), k.reshape(b, S, -1), v.reshape(b, S, -1),
            segments)).reshape(T, H * hd)
        h = x + mm(attn, f32(p["wo"]))

        m = _rms_norm(h, p["mlp_norm"], eps)
        logits = mm(m, f32(p["moe"]["router"]))               # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(probs, top_k)
        sent = jax.nn.one_hot(chosen, E, dtype=jnp.float32).sum(1)  # [T, E]
        gates = probs * sent                      # not renormalised

        @keep
        def one_expert(out, args):
            w_gate, w_in, w_out, gate = args      # gate [T]: 0 = not chosen
            w_gate, w_in, w_out = f32(w_gate), f32(w_in), f32(w_out)

            def some_tokens(mb):
                return mm(jax.nn.silu(mm(mb, w_gate)) * mm(mb, w_in), w_out)

            return out + gate[:, None] * _blocks(some_tokens, m, block), None

        moe = p["moe"]
        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
            moe["w_gate"], moe["w_in"], moe["w_out"], gates.T))
        balance = E * jnp.sum(sent.mean(0) * probs.mean(0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
        return h + out, (balance, z)

    x = f32(params["wte"][ids.reshape(T)])
    x, (balance, z) = jax.lax.scan(layer, x, params["blocks"])
    x = _rms_norm(x, params["final_norm"], eps)
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
    scored = scored.astype(jnp.float32)
    ce = jnp.sum(nll * scored) / jnp.maximum(scored.sum(), 1.0)
    return ce + sizes["aux_loss_coef"] * balance.sum() \
        + sizes["router_z_loss_coef"] * z.sum()


def step_loss(params, batch, sizes, chunk, put=None, matmul_dtype=None):
    """The loss ``engine.train_batch`` reports for ``batch`` (leaves
    [gas, B, S]) at ``params``: the mean over the gas micro-batches.  A
    micro-batch goes through whole, because the router's statistics are
    its own; ``chunk`` (sequences, as the driver counts) bounds the block
    of tokens that the experts and the head take at a time, at
    ``chunk`` sequences or ``TOKEN_BLOCK`` tokens, whichever is less.
    ``put`` places a host array on the devices (the engine's batch
    sharding)."""
    put = put or (lambda x: x)
    ids = np.asarray(batch["input_ids"])
    seg = batch.get("segment_ids")
    keys = ("num_heads", "num_kv_heads", "head_dim", "num_experts", "top_k",
            "rms_norm_eps", "rope_theta", "aux_loss_coef",
            "router_z_loss_coef")
    fn = jax.jit(functools.partial(
        micro_batch_loss, sizes={k: sizes[k] for k in keys},
        block=min(chunk * ids.shape[-1], TOKEN_BLOCK),
        matmul_dtype=matmul_dtype))
    with jax.default_matmul_precision("highest"):
        return float(np.mean([
            float(fn(params, put(ids[g]),
                     None if seg is None else put(np.asarray(seg)[g])))
            for g in range(ids.shape[0])]))
