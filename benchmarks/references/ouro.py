"""Plain reference for Ouro (huggingface.co/ByteDance/Ouro-2.6B,
``model_type: ouro``; Zhu et al. 2025, arXiv:2510.25741): forward pass and
loss in ``jax.numpy`` and float32 — a Python loop over the passes and,
inside it, over the layers, the same layer's weights read again in every
pass (the layer itself is one compiled function, run ``T L`` times); no
kernel, no scan over layers, no remat of its own choosing, no mixed
precision, dense masks.  Gradients are ``jax.grad`` of
:func:`micro_batch_loss`.

``N(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``.  No bias anywhere
but the gate's.  With T = ``total_ut_steps``, L = ``num_layers``, H =
``num_heads`` = ``num_kv_heads`` heads of hd = ``head_dim``::

    x^0 = E[ids]
    for t = 1..T:
        y = x^{t-1}
        for l = 1..L:                                   W_l, g_l: pass t's
            a = N(y; g1_l)                              are pass 1's
            q = a W_q, k = a W_k, v = a W_v             [S, H, hd]
            rotary on all hd dimensions, dim i with i + hd/2, angle pos *
              rope_theta^(-2i/hd), pos the position along the sequence
              (not reset at a document)
            P = softmax of q k^T / sqrt(hd) over the keys j <= i of i's
                document
            y = y + N(concat_heads(P v) W_o; g2_l)
            u = N(y; g3_l)
            y = y + N((silu(u W_gate) * (u W_up)) W_down; g4_l)
        x^t = N(y; g_f)
        nll^t_i = -log softmax(x^t_i W_head)[id_{i+1}]
        lam^t_i = sigmoid(x^t_i . w_g + b_g)
    p^t_i = lam^t_i prod_{j<t} (1 - lam^j_i)   (t < T)
    p^T_i = prod_{j<T} (1 - lam^j_i)
    o_i   = sum_t p^t_i nll^t_i - beta * H(p_i),   H(p) = -sum_t p^t log p^t

Loss of a micro-batch: the mean of ``o_i`` over the positions i whose next
token is in the same document; of a step: the mean over its micro-batches.

The catalog's keys give the widths, T, ``rope_theta``, the epsilon and the
untied head; the four norms a layer, the final norm after EVERY pass, the
gate's form, the objective and ``beta`` are the configuration's ``assumed``
(the public modelling code and the paper's first-stage objective as
remembered: no network here).  Departures from that description: none.  It
runs on the engine's own parameter tree (``blocks`` stacked ``[L, ...]``),
attention takes a block of a sequence's queries at a time against all of
the sequence's keys and the head a block of positions: blocks that make it
fit, the same arithmetic.

:func:`token_losses` is what drivers/train_steps_counted.py holds the
program's own forward pass (``model.apply``) to, position by position: the
LAST pass's ``nll^T`` — the published forward's logits where no token
leaves early (``early_exit_threshold`` 1), through all ``T L`` layer
applications; the objective ``o_i`` itself, which mixes the four passes
under the gates, is :func:`token_objectives` (tests/test_ouro.py) and its
mean :func:`step_loss`'s, which the driver holds the first step's loss to.

``matmul_dtype`` is for the control only: every matrix product's operands
are rounded to that type first (float32 accumulation).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  Between its
#: two readings on the chip at the cell's own size (PERF.md section 2, PR
#: 70): the engine moved it by at most 5.79e-4 over 14 runs at 14 seeds
#: (-5.79e-4 ... +3.45e-4, twelve of them under 3e-4); the control
#: (scripts/reference_control.py, this file with every product's operands
#: rounded) read 7.6e-6 and 3.0e-5 in bf16 - inside - and 3.26e-3 and
#: 1.63e-2 in fp8 e4m3: outside in both seeds.  2.6 x above the first, 2.2
#: x below the second (the Phi-4 and Kimi-Linear cells' limit, at the same
#: traffic).
LOSS_ATOL = 1.5e-3

#: root of the mean squared difference, over a micro-batch's scored
#: positions, between the program's last-pass per-token loss and this
#: reference's, allowed in nats (drivers/train_steps_counted.py, at the
#: parameters a run ends with).  Between its two readings on the chip
#: (PERF.md section 2, PR 70): the engine 6.9e-3 ... 1.05e-2 over 14 runs
#: (the bf16 control 1.62e-2 and 1.73e-2 at fresh parameters), the fp8 e4m3
#: control 0.715 and 0.792: 3.8 x above the first, 18 x below the second.
TOKEN_NLL_RMS_ATOL = 0.04

QUERY_BLOCK = 512       # queries of one sequence scored at a time
TOKEN_BLOCK = 1024      # positions through the head at a time


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rotary(x, theta):
    """x [S, heads, hd]: dim i and i + hd/2 turned by pos * theta^(-2i/hd)."""
    S, hd = x.shape[0], x.shape[-1]
    freqs = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _one_at_a_time(x):
    """Called as it stands (no ``jit`` around the whole), the loop would
    enqueue its ``T L`` layer calls at once, every call's output allocated
    when it is enqueued: 48 float32 states in use on the chip (4.2 GiB at
    the cell's size, counted into ``peak_hbm_gib``).  Wait for each."""
    return x if isinstance(x, jax.core.Tracer) else jax.block_until_ready(x)


def exit_distribution(z):
    """``p`` [T, ...] from the gates' logits ``z`` [T, ...]: ``lam =
    sigmoid(z)``; ``p^t = lam^t prod_{j<t} (1 - lam^j)``; the last pass
    takes what is left (its own gate decides nothing)."""
    lam = jax.nn.sigmoid(z)
    left, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def micro_batch_loss(params, ids, segments, sizes, block=TOKEN_BLOCK,
                     matmul_dtype=None, remat=False, output="loss"):
    """The loss of one micro-batch: ``ids`` [b, S] token ids, ``segments``
    [b, S] document numbers or None, ``sizes`` the configuration's
    ``model`` block.  ``output``: ``"loss"``; ``"last_nll"``: (the last
    pass's negative log likelihood of every position's next token [b, S],
    which positions are scored); ``"objectives"``: (``o_i`` [b, S], the
    same); ``"exits"``: (every pass's logits [T, b, S, V], ``p`` [T, b,
    S]).  Differentiable in ``params``; ``remat`` keeps only each layer
    application's and each block of queries' inputs for the gradient (the
    same arithmetic: what ``jax.grad`` at the published widths needs to
    fit one chip, scripts/olmoe_grad_check.py)."""
    keep = jax.checkpoint if remat else (lambda fn: fn)
    f32 = lambda a: a.astype(jnp.float32)
    if matmul_dtype is None:
        mm = jnp.matmul
    else:
        mm = lambda a, b: jnp.matmul(f32(a.astype(matmul_dtype)),
                                     f32(b.astype(matmul_dtype)))
    b, S = ids.shape
    T, L = sizes["total_ut_steps"], sizes["num_layers"]
    eps = sizes["norm_eps"]
    H, hd = sizes["num_heads"], sizes["head_dim"]
    assert sizes["num_kv_heads"] == H, "the published model has no groups"
    q_block = _fit(S, QUERY_BLOCK)
    if segments is None:
        segments = jnp.zeros((b, S), jnp.int32)

    def attention(q, k, v, seg):
        """One sequence: q, k, v [S, H, hd], seg [S]."""
        kT, vT = k.transpose(1, 2, 0), v.transpose(1, 0, 2)

        @keep
        def some_queries(args):
            qb, pos, seg_q = args
            scores = mm(qb.transpose(1, 0, 2), kT) / jnp.sqrt(float(hd))
            seen = (pos[:, None] >= jnp.arange(S)[None, :]) \
                & (seg_q[:, None] == seg[None, :])
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   axis=-1)
            return mm(probs, vT).transpose(1, 0, 2)            # [qb, H, hd]

        out = jax.lax.map(some_queries, (
            q.reshape(-1, q_block, H, hd),
            jnp.arange(S).reshape(-1, q_block), seg.reshape(-1, q_block)))
        return out.reshape(S, H, hd)

    @jax.jit
    @keep
    def layer(y, blocks, at, segments):
        p = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
            w, at, keepdims=False), blocks)
        a = _norm(y, p["attn_norm"], eps)
        q = mm(a, f32(p["wq"])).reshape(b, S, H, hd)
        k = mm(a, f32(p["wk"])).reshape(b, S, H, hd)
        v = mm(a, f32(p["wv"])).reshape(b, S, H, hd)
        theta = sizes["rope_theta"]
        o = jax.vmap(lambda q, k, v, seg: attention(
            _rotary(q, theta), _rotary(k, theta), v, seg))(q, k, v, segments)
        y = y + _norm(mm(o.reshape(b, S, H * hd), f32(p["wo"])),
                      p["attn_out_norm"], eps)
        u = _norm(y, p["mlp_norm"], eps)
        down = mm(jax.nn.silu(mm(u, f32(p["w_gate"])))
                  * mm(u, f32(p["w_up"])), f32(p["w_down"]))
        return y + _norm(down, p["mlp_out_norm"], eps)

    s_block = _fit(S, block)
    targets = jnp.roll(ids, -1, axis=1)

    @jax.jit
    def token_nll(x, head, targets):
        @keep
        def some_tokens(args):
            xb, target = args                   # [b, block, D], [b, block]
            logits = mm(xb, f32(head))
            return jax.scipy.special.logsumexp(logits, axis=-1) \
                - jnp.take_along_axis(logits, target[..., None],
                                      axis=-1)[..., 0]

        nll = jax.lax.map(some_tokens, (
            x.reshape(b, -1, s_block, x.shape[-1]).swapaxes(0, 1),
            targets.reshape(b, -1, s_block).swapaxes(0, 1)))
        return nll.swapaxes(0, 1).reshape(b, S)

    @jax.jit
    def after_a_pass(y, final_norm, gate):
        x = _norm(y, final_norm, eps)
        return x, mm(x, f32(gate["w"])) + f32(gate["b"])

    head = params["lm_head"]
    x = f32(params["wte"][ids])
    nll, z, logits = [], [], []
    for _ in range(T):
        for at in range(L):
            # pass t reads the layers pass 1 read
            x = _one_at_a_time(layer(x, params["blocks"], at, segments))
        x, gate_logit = after_a_pass(x, params["final_norm"],
                                     params["exit_gate"])
        z.append(gate_logit)
        if output == "exits":
            logits.append(mm(x, f32(head)))
        elif output != "last_nll":
            nll.append(token_nll(x, head, targets))
    p = exit_distribution(jnp.stack(z))                         # [T, b, S]
    if output == "exits":
        return jnp.stack(logits), p
    # position t is scored against token t+1 where both are of one
    # document; a sequence's last position has no next token
    scored = (segments == jnp.roll(segments, -1, axis=1)) \
        & (jnp.arange(S) < S - 1)[None, :]
    if output == "last_nll":
        return token_nll(x, head, targets), scored
    entropy = -jnp.sum(jax.scipy.special.xlogy(p, p), axis=0)
    objectives = jnp.sum(p * jnp.stack(nll), axis=0) \
        - sizes["exit_entropy_beta"] * entropy
    if output == "objectives":
        return objectives, scored
    scored = scored.astype(jnp.float32)
    return jnp.sum(objectives * scored) / jnp.maximum(scored.sum(), 1.0)


SIZES = ("num_layers", "total_ut_steps", "num_heads", "num_kv_heads",
         "head_dim", "rope_theta", "norm_eps", "exit_entropy_beta")


def _plain(sizes, chunk, seq_len, **kwargs):
    """:func:`micro_batch_loss` at a cell's sizes, called as it stands: a
    Python loop that runs ONE compiled layer ``T L`` times (one program of
    ``T L`` layers written out takes the compiler minutes at 4 x 12)."""
    return functools.partial(
        micro_batch_loss, sizes={k: sizes[k] for k in SIZES},
        block=min(chunk * seq_len, TOKEN_BLOCK), **kwargs)


def step_loss(params, batch, sizes, chunk, put=None, matmul_dtype=None):
    """The loss ``engine.train_batch`` reports for ``batch`` (leaves
    [gas, B, S]) at ``params``: the mean over the gas micro-batches.
    ``chunk`` (sequences, as the driver counts) bounds the block of
    positions the head takes at a time, at ``chunk`` sequences or
    ``TOKEN_BLOCK`` tokens, whichever is less.  ``put`` places a host
    array on the devices (the engine's batch sharding)."""
    put = put or (lambda x: x)
    ids = np.asarray(batch["input_ids"])
    seg = batch.get("segment_ids")
    fn = _plain(sizes, chunk, ids.shape[-1], matmul_dtype=matmul_dtype)
    with jax.default_matmul_precision("highest"):
        return float(np.mean([
            float(fn(params, put(ids[g]),
                     None if seg is None else put(np.asarray(seg)[g])))
            for g in range(ids.shape[0])]))


def _per_token(output, params, micro_batch, sizes, chunk, matmul_dtype):
    ids = jnp.asarray(micro_batch["input_ids"])
    seg = micro_batch.get("segment_ids")
    fn = _plain(sizes, chunk, ids.shape[-1], matmul_dtype=matmul_dtype,
                output=output)
    with jax.default_matmul_precision("highest"):
        values, scored = fn(params, ids, None if seg is None
                            else jnp.asarray(seg))
    return np.asarray(values), np.asarray(scored)


def token_losses(params, micro_batch, sizes, chunk, matmul_dtype=None):
    """Every position's negative log likelihood of its next token under the
    LAST pass's head for one micro-batch (leaves [b, S]) at ``params``,
    float32 [b, S], and the positions that are scored, bool [b, S]: what
    ``model.apply``'s logits give, position by position.  ``chunk`` as
    :func:`step_loss`'s."""
    return _per_token("last_nll", params, micro_batch, sizes, chunk,
                      matmul_dtype)


def token_objectives(params, micro_batch, sizes, chunk, matmul_dtype=None):
    """Every position's ``o_i = sum_t p^t nll^t - beta H(p)`` and the
    positions scored: what the mean of :func:`step_loss` averages away."""
    return _per_token("objectives", params, micro_batch, sizes, chunk,
                      matmul_dtype)
