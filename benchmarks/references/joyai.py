"""Plain reference for JoyAI-LLM-Flash (huggingface.co/jdopensource/JoyAI-
LLM-Flash, ``model_type: joyai_llm_flash``; its config is key for key the
DeepSeek-V3 layout, whose equations are published: DeepSeek-V2,
arXiv:2405.04434 section 2.1 for the attention; DeepSeek-V3,
arXiv:2412.19437 sections 2.1-2.2 for the router and the prediction
module): forward pass and loss in ``jax.numpy`` and float32 — no kernel, no
scan over layers, no sort, no plan, no mixed precision.  Gradients are
``jax.grad`` of :func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  No bias anywhere.

Latent attention, every layer (H heads; ``nope``, ``rot``, ``vd`` =
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``):

    c_q = N(h W_dq)                    [q_nope | q_rope] = c_q W_uq  per head
    [c_kv | k_r] = h W_dkv             c_kv <- N(c_kv)
    [k_nope | v] = c_kv W_ukv  per head;  k_r is ONE key for all heads
    q_rope, k_r <- rotary, pairs (2i, 2i+1), angle pos * theta^(-2i/rot),
        pos the position along the sequence (not reset at a document)
    q = [q_nope | q_rope]   k = [k_nope | k_r]      nope + rot wide
    P = causal softmax of q k^T / sqrt(nope + rot) inside a document
    out = concat_heads(P v) W_o

written as the plain masked ``[queries, S]`` softmax, a block of one
sequence's queries at a time against all of the sequence's keys.

Layer 0: ``x <- x + MLA(N(x))``, ``x <- x + W_down(silu(W_gate h) * W_up
h)``, ``h = N(x)``.  Layers 1..: the same attention, then experts: ``s =
sigmoid(h W_r)`` over all ``num_experts``; the ``top_k`` largest of ``s +
e_score_correction_bias`` are chosen; their weights are ``s`` (without the
bias) over the chosen ones' sum, times ``routed_scaling_factor``; ``MoE(h)
= sum_{e chosen, held} w_e SwiGLU_e(h) + SwiGLU_shared(h)``.  **The sum
runs over the experts held here only** (``expert_offset`` .. ``+
experts_held``; the parameter tree holds just those), the shared expert
whole: one chip's share of an expert-parallel layer, the partial result
going on to the next layer, as in the program.  Final ``N``, untied head.

Multi-token prediction (``num_mtp_layers`` 1): with ``x_t`` the last main
layer's output BEFORE the final norm, ``h'_t = [N_h(x_t) ; N_e(E[id_{t+1}])]
W_eh`` (the hidden state's half first), one more block of the layers-1..
kind, its own final ``N``, the SAME head, scored against ``id_{t+2}``.  ``E``
and the head are the main model's own leaves.

Loss of a micro-batch: ``L_main + mtp_loss_weight * L_mtp +
aux_loss_coef * sum_{expert layers, the module's too} num_experts * sum_e
f_e * P_e`` over ALL experts (f_e = (token, choice) pairs sent to e /
tokens; P_e = mean over the micro-batch's tokens of ``s_e / sum_e'
s_e'``).  ``L_main`` is the mean cross-entropy over the positions t whose
next token is in the same document; ``L_mtp`` over those where t, t+1 and
t+2 are.  The loss of a step is the mean over its micro-batches.

It runs on the engine's own parameter tree (``dense``, ``blocks`` stacked
over the expert layers, ``mtp``), a block of tokens at a time through the
held experts (one expert at a time, the weight 0 where it was not chosen),
the shared one and the head.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (five layers and the
#: prediction module at the published widths, 2 micro-batches of 2 x 8,192
#: packed tokens; PERF.md section 2, PR 38).  The engine (bfloat16 products,
#: float32 norms, router, softmax and loss) moved the loss by at most
#: 9.13e-4 over 17 runs and 17 seeds (-9.1e-4 ... +7.0e-4, sigma 4.6e-4);
#: the reference with every product's operands rounded to bf16, the engine's
#: own arithmetic, by 4.6e-5 to 4.3e-4 over 5 seeds (inside).  Rounded to
#: fp8 e4m3, the nearest precision below, the reference's mean loss read
#: 3.4e-3 to 4.6e-2 from the float32 one over the same 5 seeds (outside,
#: every seed).  2.5e-3 is 2.7 times the engine's largest reading (5.4 of
#: its sigmas: the accepted cells' 1e-3 would refuse one run in thirty) and
#: 0.74 of the control's smallest.
LOSS_ATOL = 2.5e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 38): the engine read 1.15e-2 to 1.57e-2 over 19 runs and 19
#: seeds (the reference rounded to bf16: 1.18e-2 to 1.31e-2 over 5 seeds);
#: the reference rounded to fp8 e4m3, the nearest precision below, 0.486 to
#: 0.629 over the same 5 seeds (outside, every seed).  0.1 is 6.4 times the
#: engine's largest reading and 0.21 of the control's smallest.  The
#: prediction module's per-token losses (:func:`mtp_token_losses`; the
#: driver does not read them, scripts/reference_control.py does) sit inside
#: the same limit by the same two readings: the program 1.14e-2 to 1.30e-2,
#: the bf16 reference 0.96e-2 to 1.04e-2, the fp8 one 0.349 to 0.486.
TOKEN_NLL_RMS_ATOL = 0.1

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rotary(x, theta):
    """x [S, heads, rot]: pairs (2i, 2i+1) turned by pos * theta^(-2i/rot)."""
    S, _, rot = x.shape
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, per_token=None):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block; ``per_token``: instead, ``"main"`` every position's
    negative log likelihood of the next token [b, S] and which positions
    are scored, ``"mtp"`` the same of the prediction module's (of token
    t+2).  Differentiable in ``params``; ``remat`` keeps only each layer's,
    each expert's and each block of queries' inputs for the gradient (the
    same arithmetic: what ``jax.grad`` at the published widths needs to fit
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
    eps = sizes["norm_eps"]
    H, rkv = sizes["num_heads"], sizes["kv_lora_rank"]
    nope, rot, vd = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                     sizes["v_head_dim"])
    theta = sizes["rope_theta"]
    E, top_k = sizes["num_experts"], sizes["top_k"]
    held = sizes.get("experts_held") or E
    offset = sizes.get("expert_offset", 0)
    block = _fit(T, block)
    q_block = _fit(S, QUERY_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    # ------------------------------------------------------------ attention
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

    def latent_attention(x, p):
        h = _norm(x, p["attn_norm"], eps)
        c_q = _norm(mm(h, f32(p["w_dq"])), p["q_norm"], eps)
        q = mm(c_q, f32(p["w_uq"])).reshape(b, S, H, nope + rot)
        down = mm(h, f32(p["w_dkv"])).reshape(b, S, rkv + rot)
        c_kv = _norm(down[..., :rkv], p["kv_norm"], eps)
        kv = mm(c_kv, f32(p["w_ukv"])).reshape(b, S, H, nope + vd)

        def one_sequence(args):
            q, kv, k_r, seg = args
            q = jnp.concatenate(
                [q[..., :nope], _rotary(q[..., nope:], theta)], axis=-1)
            k_r = _rotary(k_r[:, None, :], theta)             # [S, 1, rot]
            k = jnp.concatenate(
                [kv[..., :nope], jnp.repeat(k_r, H, axis=1)], axis=-1)
            return attention(q, k, kv[..., nope:], seg)

        attn = jax.lax.map(one_sequence,
                           (q, kv, down[..., rkv:], segments))
        return x + mm(attn.reshape(T, H * vd), f32(p["w_o"]))

    # ---------------------------------------------------------- feed-forward
    def swiglu(m, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(m, f32(w_gate))) * mm(m, f32(w_up)),
                  f32(w_down))

    @keep
    def dense_layer(x, p):
        x = latent_attention(x, p)
        m = _norm(x, p["mlp_norm"], eps)
        return x + jax.lax.map(
            lambda mb: swiglu(mb, p["w_gate"], p["w_up"], p["w_down"]),
            m.reshape(-1, block, m.shape[-1])).reshape(x.shape)

    @keep
    def expert_layer(x, p):
        x = latent_attention(x, p)
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

    # ------------------------------------------------------------- the head
    head = f32(params["lm_head"])

    def token_nll(x, norm_w, targets):
        x = _norm(x, norm_w, eps)

        def some_tokens(args):
            xb, target = args
            logits = mm(xb, head)
            return jax.scipy.special.logsumexp(logits, axis=-1) \
                - jnp.take_along_axis(logits, target[:, None], axis=-1)[:, 0]

        return jax.lax.map(some_tokens, (
            x.reshape(-1, block, x.shape[-1]),
            targets.reshape(-1, block))).reshape(b, S)

    def mean_over(nll, scored):
        scored = scored.astype(jnp.float32)
        return jnp.sum(nll * scored) / jnp.maximum(scored.sum(), 1.0)

    # ------------------------------------------------------- the main model
    wte = params["wte"]
    x = dense_layer(f32(wte[ids.reshape(T)]), params["dense"])
    balance = 0.0
    for i in range(sizes["num_layers"] - 1):
        x, bal = expert_layer(
            x, jax.tree.map(lambda a: a[i], params["blocks"]))
        balance = balance + bal
    # position t is scored against token t+1 where both are of one
    # document; a sequence's last position has no next token
    same_1 = segments == jnp.roll(segments, -1, axis=1)
    scored = same_1 & (jnp.arange(S) < S - 1)[None, :]
    if per_token == "main":
        return token_nll(x, params["final_norm"],
                         jnp.roll(ids, -1, axis=1)), scored
    loss = 0.0 if per_token else mean_over(
        token_nll(x, params["final_norm"], jnp.roll(ids, -1, axis=1)),
        scored)

    # ------------------------------------------------- the prediction module
    if sizes.get("num_mtp_layers", 0):
        mtp = params["mtp"]
        nxt = f32(wte[jnp.roll(ids, -1, axis=1).reshape(T)])
        joined = jnp.concatenate([_norm(x, mtp["norm_h"], eps),
                                  _norm(nxt, mtp["norm_e"], eps)], axis=-1)
        h, bal = expert_layer(mm(joined, f32(mtp["w_eh"])), mtp["block"])
        balance = balance + bal
        # position t against token t+2 where t, t+1 and t+2 are of one
        # document
        scored_2 = same_1 & (segments == jnp.roll(segments, -2, axis=1)) \
            & (jnp.arange(S) < S - 2)[None, :]
        nll_2 = token_nll(h, mtp["final_norm"], jnp.roll(ids, -2, axis=1))
        if per_token == "mtp":
            return nll_2, scored_2
        loss = loss + sizes["mtp_loss_weight"] * mean_over(nll_2, scored_2)
    elif per_token == "mtp":
        raise ValueError("joyai reference: num_mtp_layers is 0")
    return loss + sizes["aux_loss_coef"] * balance


SIZES = ("num_layers", "num_heads", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rope_theta", "num_experts",
         "top_k", "routed_scaling_factor", "expert_offset", "experts_held",
         "norm_eps", "aux_loss_coef", "num_mtp_layers", "mtp_loss_weight")


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


def _per_token(which, params, micro_batch, sizes, chunk, matmul_dtype):
    ids = jnp.asarray(micro_batch["input_ids"])
    seg = micro_batch.get("segment_ids")
    fn = _jitted(sizes, chunk, ids.shape[-1], matmul_dtype=matmul_dtype,
                 per_token=which)
    with jax.default_matmul_precision("highest"):
        nll, scored = fn(params, ids, None if seg is None
                         else jnp.asarray(seg))
    return np.asarray(nll), np.asarray(scored)


def token_losses(params, micro_batch, sizes, chunk, matmul_dtype=None):
    """Every position's negative log likelihood of its next token from the
    main head for one micro-batch (leaves [b, S]) at ``params``, float32
    [b, S], and the positions that are scored, bool [b, S]: what the mean
    of :func:`step_loss` averages away.  ``chunk`` as there."""
    return _per_token("main", params, micro_batch, sizes, chunk,
                      matmul_dtype)


def mtp_token_losses(params, micro_batch, sizes, chunk, matmul_dtype=None):
    """The same of the prediction module: position t's negative log
    likelihood of token t+2, and where t, t+1 and t+2 are of one
    document."""
    return _per_token("mtp", params, micro_batch, sizes, chunk, matmul_dtype)
