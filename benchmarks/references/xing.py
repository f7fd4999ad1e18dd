"""Plain reference for Xing4.0-29B-A4B (huggingface.co/XingChen-AGI/
Xing4.0-29B-A4B, ``model_type: xing4_0``): forward pass and loss in
``jax.numpy`` and float32 — no kernel, no scan over layers, no sort, no
plan, no mixed precision, and nothing imported from the program.  Its
config is the DeepSeek-V3 layout (latent attention: DeepSeek-V2,
arXiv:2405.04434 section 2.1; the sigmoid router with a selection bias and
the prediction module: DeepSeek-V3, arXiv:2412.19437 sections 2.1-2.2; all
three as references/joyai.py states them, written out again here) under a
residual of ``n = hc_mult`` streams mixed by manifold-constrained
hyper-connections (mHC: Xie et al., DeepSeek-AI, arXiv:2512.24880 section
4; hyper-connections: Zhu et al., arXiv:2409.19606), with YaRN rotary
frequencies.  Gradients are ``jax.grad`` of :func:`micro_batch_loss`.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  No bias in any sublayer.

**The residual.**  Per token ``X`` [n, D].  Entry: ``X[i] = E[x_t]`` for
every i.  Each sublayer ``F`` (a layer's attention; its MLP or experts) has
its own ``Phi`` [n D, 2 n + n^2], ``alpha`` [3] and ``b_pre``, ``b_post``
[n], ``b_res`` [n, n]:

    r = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)     (no learnable weight)
    [p | q | R] = r Phi
    H_pre  = sigmoid(alpha_0 p + b_pre)
    H_post = 2 sigmoid(alpha_1 q + b_post)
    M      = exp(clamp(alpha_2 mat(R) + b_res, hc_clamp_min, hc_clamp_max))
    hc_sinkhorn_iters times:  M[i, j] <- M[i, j] / (sum_i' M[i', j] + hc_eps)
                              M[i, j] <- M[i, j] / (sum_j' M[i, j'] + hc_eps)
    H_res  = M
    h      = sum_i H_pre[i] X[i]
    y      = F(N(h))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

(``mat(R)[i, j] = R[i n + j]``).  Exit: ``x = sum_i X[i]``, then the final
``N`` and the untied head.

Latent attention (H heads; ``nope``, ``rot``, ``vd`` = ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``):

    c_q = N(h W_dq)                    [q_nope | q_rope] = c_q W_uq  per head
    [c_kv | k_r] = h W_dkv             c_kv <- N(c_kv)
    [k_nope | v] = c_kv W_ukv  per head;  k_r is ONE key for all heads
    q_rope, k_r <- rotary, pairs (2i, 2i+1), angle pos * f_i, pos the
        position along the sequence (not reset at a document); f_i are
        YaRN's: theta^(-2i/rot) where dimension i turns more than
        ``beta_fast`` times in ``original_max_position_embeddings``, that
        over ``rope_factor`` where it turns fewer than ``beta_slow`` times,
        a linear ramp between; cos and sin times m(mscale) /
        m(mscale_all_dim), m(s) = 0.1 s ln(rope_factor) + 1
    P = causal softmax of q k^T m(mscale_all_dim)^2 / sqrt(nope + rot)
        inside a document
    out = concat_heads(P v) W_o

Leading layers (``num_dense_layers``): attention, then ``W_down(silu(W_gate
h) * W_up h)``.  Then expert layers: ``s = sigmoid(h W_r)``; the ``top_k``
largest of ``s + e_score_correction_bias`` are chosen; their weights are
``s`` over the chosen ones' sum, times ``routed_scaling_factor``; ``MoE(h) =
sum_{e chosen, held} w_e SwiGLU_e(h) + SwiGLU_shared(h)``, **the sum over
the experts held here only** (``expert_offset`` .. ``+ experts_held``).

Multi-token prediction (``num_mtp_layers`` 1): with ``x_t`` the main
stack's exit sum BEFORE the final norm, ``h'_t = [N_h(x_t) ;
N_e(E[id_{t+1}])] W_eh``; ``X[i] = h'_t`` for every i, one more expert
layer on the streams, their sum, a final ``N`` of its own, the SAME head,
scored against ``id_{t+2}``.

Loss of a micro-batch: ``L_main + mtp_loss_weight * L_mtp + aux_loss_coef
* sum_{expert layers, the module's too} num_experts * sum_e f_e * P_e`` as
references/joyai.py.  The loss of a step is the mean over its
micro-batches.

``matmul_dtype`` is for the control only: every matrix product's operands
(``r Phi`` among them) are rounded to that type first (float32
accumulation).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Set from
#: readings on the chip at the cell's own size (five layers and the
#: prediction module at the published widths, 4 micro-batches of 2 x 4,096
#: packed tokens; PERF.md section 2, PR 56).  The engine (bfloat16 products
#: and stream, float32 norms, coefficients, router, softmax and loss) moved
#: the loss by at most 1.03e-3 over 21 runs at 21 seeds and 12 draws of the
#: weights (-9.5e-4 ... +1.03e-3; all but three under 6e-4); the
#: reference with every product's operands rounded to bf16, the engine's own
#: arithmetic, by 3.2e-4 to 6.6e-4 over 3 seeds (inside).  Rounded to fp8
#: e4m3, the nearest precision below, the reference's mean loss read 1.06e-2
#: to 3.13e-2 from the float32 one over the same 3 seeds (outside, every
#: seed).  3e-3 is 2.9 times the engine's largest reading and 0.28 of the
#: control's smallest.
LOSS_ATOL = 3e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's per-token loss and this reference's,
#: allowed in nats (drivers/train_steps_counted.py, at the parameters a run
#: ends with).  From two readings on the chip at the cell's size (PERF.md
#: section 2, PR 56): the program read 3.9e-2 to 4.4e-2 at three draws of
#: fresh weights (scripts/reference_control.py; the reference rounded to
#: bf16: 3.1e-2 to 4.0e-2) and 3.1e-2 to 4.1e-2 at the end of 20 runs (the
#: driver's own reading, at step 12 or 13); the reference rounded to fp8
#: e4m3, the nearest precision below, 0.442 to 0.832 over the same 3 seeds
#: (outside, every seed).  0.13 is 2.9 times the program's largest reading
#: and 0.29 of the control's smallest.  The prediction module's per-token losses
#: (:func:`mtp_token_losses`; the driver does not read them,
#: scripts/reference_control.py does) sit inside the same limit by the same
#: two readings: the program 3.2e-2 to 3.8e-2, the bf16 reference 3.0e-2 to
#: 3.1e-2, the fp8 one 0.336 to 0.624.
TOKEN_NLL_RMS_ATOL = 0.13

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # tokens through an expert, or the head, at a time


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _yarn_m(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_frequencies(sizes):
    """[rot / 2] float64: the rotary frequencies of YaRN (Peng et al.,
    arXiv:2309.00071, "NTK-by-parts")."""
    rot, theta = sizes["qk_rope_head_dim"], sizes["rope_theta"]
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def dimension_turning(turns):
        return rot * math.log(sizes["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dimension_turning(sizes["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(sizes["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return plain / sizes["rope_factor"] * ramp + plain * (1.0 - ramp)


def _rotary(x, frequencies, scale):
    """x [S, heads, rot]: pairs (2i, 2i+1) turned by pos * f_i, cos and sin
    times ``scale``."""
    S = x.shape[0]
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(frequencies, jnp.float32)[None]
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
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
    frequencies = _yarn_frequencies(sizes)
    on_tables = _yarn_m(sizes["rope_factor"], sizes["mscale"]) \
        / _yarn_m(sizes["rope_factor"], sizes["mscale_all_dim"])
    softmax_scale = _yarn_m(sizes["rope_factor"], sizes["mscale_all_dim"]) \
        ** 2 / math.sqrt(nope + rot)
    n = sizes["hc_mult"]
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
            scores = mm(qb.transpose(1, 0, 2), kT) * softmax_scale
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
        """The branch alone: ``MLA(N(x))``."""
        h = _norm(x, p["attn_norm"], eps)
        c_q = _norm(mm(h, f32(p["w_dq"])), p["q_norm"], eps)
        q = mm(c_q, f32(p["w_uq"])).reshape(b, S, H, nope + rot)
        down = mm(h, f32(p["w_dkv"])).reshape(b, S, rkv + rot)
        c_kv = _norm(down[..., :rkv], p["kv_norm"], eps)
        kv = mm(c_kv, f32(p["w_ukv"])).reshape(b, S, H, nope + vd)

        def one_sequence(args):
            q, kv, k_r, seg = args
            q = jnp.concatenate(
                [q[..., :nope],
                 _rotary(q[..., nope:], frequencies, on_tables)], axis=-1)
            k_r = _rotary(k_r[:, None, :], frequencies, on_tables)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.repeat(k_r, H, axis=1)], axis=-1)
            return attention(q, k, kv[..., nope:], seg)

        attn = jax.lax.map(one_sequence,
                           (q, kv, down[..., rkv:], segments))
        return mm(attn.reshape(T, H * vd), f32(p["w_o"]))

    # ------------------------------------------------- the n-stream residual
    def hyper_connected(X, hc, sublayer):
        """X [T, n, D] -> X' [T, n, D]; ``sublayer(h) -> (y, more)``."""
        flat = X.reshape(T, -1)
        r = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                 + eps)
        z = mm(r, f32(hc["phi"]))                             # [T, 2n + n^2]
        alpha = f32(hc["alpha"])
        H_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + f32(hc["b_pre"]))
        H_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n]
                                      + f32(hc["b_post"]))
        M = jnp.exp(jnp.clip(
            alpha[2] * z[:, 2 * n:].reshape(T, n, n) + f32(hc["b_res"]),
            sizes["hc_clamp_min"], sizes["hc_clamp_max"]))
        for _ in range(sizes["hc_sinkhorn_iters"]):
            # a column over its sum (down the rows i), then a row over its
            M = M / (M.sum(axis=1, keepdims=True) + sizes["hc_eps"])
            M = M / (M.sum(axis=2, keepdims=True) + sizes["hc_eps"])
        h = jnp.sum(H_pre[:, :, None] * X, axis=1)            # [T, D]
        y, more = sublayer(h)
        mixed = jnp.sum(M[:, :, :, None] * X[:, None, :, :], axis=2)
        return mixed + H_post[:, :, None] * y[:, None, :], more

    def replicate(x):
        return jnp.broadcast_to(x[:, None, :], (T, n, x.shape[-1]))

    # ---------------------------------------------------------- feed-forward
    def swiglu(m, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(m, f32(w_gate))) * mm(m, f32(w_up)),
                  f32(w_down))

    def attention_sublayer(X, p):
        return hyper_connected(
            X, p["hc_attn"], lambda h: (latent_attention(h, p), None))[0]

    def dense_mlp(x, p):
        m = _norm(x, p["mlp_norm"], eps)
        return jax.lax.map(
            lambda mb: swiglu(mb, p["w_gate"], p["w_up"], p["w_down"]),
            m.reshape(-1, block, m.shape[-1])).reshape(x.shape)

    def experts(x, p):
        """-> (the held experts' part and the shared expert's, the
        balance term)."""
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
        return out, E * jnp.sum(sent.mean(0) * share.mean(0))

    @keep
    def dense_layer(X, p):
        X = attention_sublayer(X, p)
        return hyper_connected(X, p["hc_mlp"],
                               lambda h: (dense_mlp(h, p), None))[0]

    @keep
    def expert_layer(X, p):
        X = attention_sublayer(X, p)
        return hyper_connected(X, p["hc_mlp"], lambda h: experts(h, p))

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
    X = replicate(f32(wte[ids.reshape(T)]))
    dense = sizes["num_dense_layers"]
    for i in range(dense):
        X = dense_layer(X, jax.tree.map(lambda a: a[i], params["dense"]))
    balance = 0.0
    for i in range(sizes["num_layers"] - dense):
        X, bal = expert_layer(
            X, jax.tree.map(lambda a: a[i], params["blocks"]))
        balance = balance + bal
    x = X.sum(axis=1)
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
        H, bal = expert_layer(replicate(mm(joined, f32(mtp["w_eh"]))),
                              mtp["block"])
        h = H.sum(axis=1)
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
        raise ValueError("xing reference: num_mtp_layers is 0")
    return loss + sizes["aux_loss_coef"] * balance


SIZES = ("num_layers", "num_dense_layers", "num_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
         "rope_factor", "original_max_position_embeddings", "beta_fast",
         "beta_slow", "mscale", "mscale_all_dim", "hc_mult",
         "hc_sinkhorn_iters", "hc_eps", "hc_clamp_min", "hc_clamp_max",
         "num_experts", "top_k", "routed_scaling_factor", "expert_offset",
         "experts_held", "norm_eps", "aux_loss_coef", "num_mtp_layers",
         "mtp_loss_weight")


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
