"""Linear attention by the gated delta rule (Yang, Kautz & Hatamizadeh
2024, "Gated Delta Networks", arXiv:2412.06464) and the short causal
convolution that feeds it — what a Gated-DeltaNet layer
(models/qwen3_next.py) computes between its projections.

Per value head, with a float32 state ``S`` [dk, dv], ``S_0 = 0``:

    S   <- exp(g_t) * S_{t-1}                        decay   (g_t <= 0)
    S_t  = S + k_t (x) beta_t (v_t - S^T k_t)        delta-rule write
    o_t  = S_t^T q_t

:func:`gated_delta_rule` computes it in the chunked form of the paper's
section 3.  Inside a chunk of ``C`` tokens everything that does not need
the incoming state is a batched matrix product over all chunks at once:
with ``G_i = sum_{j<=i} g_j`` and ``L[i, j] = beta_i (k_i . k_j)
exp(G_i - G_j)`` for ``i > j`` (the WY / UT transform),

    W = (I + L)^-1 (beta k exp(G)),   U = (I + L)^-1 (beta v)

with ``(I + L)^-1`` taken once, in float32.  Across chunks the state is
carried: ``v_new = U - W S``, ``o = (q exp(G)) S + (q k^T . decay) v_new``
and ``S' = exp(G_C) S + (k exp(G_C - G))^T v_new``.

**A decay a key channel** (Kimi Team 2025, "Kimi Linear", arXiv:2510.26692,
KDA: ``g_t`` a vector of ``dk`` entries, ``S <- Diag(exp(g_t)) S_{t-1}``).
``g`` of rank four asks for it; the shape decides, nothing else does.  The
same transform holds with the decay inside the contraction, ``A[i, j] =
sum_c a_ic k_jc exp(G_ic - G_jc)`` for ``a`` = ``k`` (``L = beta A``,
strictly lower) and ``a`` = ``q``; the factors on the rows carry over
(``W = T (beta k . exp(G))``, ``(q . exp(G)) S``, ``S' = Diag(exp(G_C)) S +
(k . exp(G_C - G))^T v_new``: every exponent <= 0).  ``A`` does not
factor into one product without ``exp(-G_j)``, which overflows, so it is
made at a second level (Yang et al. 2023, GLA, arXiv:2312.06635 section
4): sub-blocks of ``SUB_BLOCK`` positions; for a pair ``I > J`` both
sides are taken against ``G`` at ``I``'s first row — ``(a_i . exp(G_i -
G_ref)) . (k_j . exp(G_ref - G_j))``, both exponents <= 0, one product —
and the diagonal sub-blocks element by element, ``[SUB_BLOCK, SUB_BLOCK,
dk]``, a chunk at a time so that the array is never whole
(:func:`_chunked_xla_channel`).

One algorithm, two lowerings for either decay (:func:`_kernel_blocking`
chooses, by the rule of ``ops/pallas/vmem.lowering``: the shape of ``g``
and what the call can observe, nothing else).  On one TPU, for heads that
are multiples of 128 wide, the Mosaic kernels of
ops/pallas/gated_delta_rule.py (a decay a head: ``ds_gdr_*``) and of
ops/pallas/kda.py (a decay a channel, one value head a key head:
``ds_kda_*`` — the levels above the sub-blocks by halves, the sub-blocks'
diagonals in registers): the state stays in VMEM across a sequence's
chunks, the inverse is taken inside the kernel and the backward is written
by hand.  Elsewhere :func:`_chunked_xla` and :func:`_chunked_xla_channel`:
the inverse from one unit-lower-triangular solve (a channel: by halves), a
``lax.scan`` whose body is checkpointed, so that the backward pass
(autodiff through the scan) keeps one state per chunk and recomputes the
rest — the fallbacks and, beside :func:`gated_delta_rule_recurrent`, the
kernels' oracles.

**Packed documents.**  With ``segment_ids`` a token sees only its own
document: at a document's first token the state is zero, exactly as if
``g`` were minus infinity there.  The chunked form takes that as a mask,
not as a number: a decay factor between two positions is zero where
their documents differ (documents are contiguous, so the positions
between them then hold a boundary), and the factor from the incoming
state to a position is zero where that position's document is not the one
the last chunk ended in.  Boundaries may fall anywhere — inside a chunk,
at its edge, around a one-token document.  Where ``chunk`` does not divide
the sequence, the tail is padded with tokens that write nothing.

:func:`causal_conv` is the depthwise causal convolution (width 4 in both
hybrids; Nemotron-H's with a bias) with the same reset — a tap that would
reach into the previous document reads zero — and with the bias and the
``silu`` both models apply straight after it.  It too has two lowerings
(:func:`_conv_blocking`): on one TPU the Mosaic kernels of
ops/pallas/causal_conv.py (each row read once; taps, reset, bias and
``silu`` in float32 registers; the backward by hand), in the orientation
the caller names; elsewhere :func:`_causal_conv_xla`, shifted copies with
autodiff's backward.

Their parts of a step carry the ``jax.named_scope``s ``conv`` and
``delta_rule`` (telemetry/tracing.py ``SCOPE_CONV``, ``SCOPE_DELTA_RULE``),
written by the model, and each call leaves its shape and the lowering it
took (``path``, with the kernels' grid blocking) in the step's account
(``tracing.delta_rule_chunks``, ``tracing.conv_calls``).
"""
import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.telemetry.tracing import count_in_step

DEFAULT_CHUNK = 64
SUB_BLOCK = 8           # positions of a chunk's second level (decay a channel)
_HIGHEST = lax.Precision.HIGHEST      # the oracle's products


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _conv_blocking(interpret, S, C, K, dtype, positions, first):
    """(the blocking of ops/pallas/causal_conv.py's kernels, or None for
    the XLA form; interpret), by ``vmem.lowering``'s rule."""
    from deepspeed_tpu.ops.pallas import causal_conv as kernels, vmem
    return vmem.lowering(
        interpret, kernels.supported(S, C, K, positions, first),
        lambda: kernels.slab_width(S, C, jnp.dtype(dtype).itemsize,
                                   positions, first))


def causal_conv(x, w, segment_ids=None, bias=None, activation=None,
                positions="sublanes", *, first_channel=0, interpret=None):
    """Depthwise causal convolution.  ``x`` [B, S, Cx], ``w`` [K, C] (tap
    ``K-1`` multiplies the current token, tap 0 the one ``K-1`` back):
    ``y_t = act(bias + sum_j w[j] * x_{t-(K-1)+j})`` [B, S, C], a tap
    before the sequence's start or, with ``segment_ids`` [B, S], in another
    document reading 0; ``bias`` [C] or None, ``activation`` None or
    ``"silu"``.  ``x`` may be wider than ``w``: the convolution takes its
    channels ``first_channel`` to ``first_channel + C`` (a caller hands
    over the projection it would slice, and the kernels read the part by
    their blocks' index).

    ``positions``: which axis of the kernels' slabs holds positions —
    ``"sublanes"`` (slabs of ``x`` as it is, channels along lanes) or
    ``"lanes"`` (slabs of ``x`` with its last two axes swapped: for a
    caller whose array the compiler lays out positions-minor, and whose
    next kernel takes it so).  The result is the same.  ``interpret``: None
    chooses the lowering (:func:`_conv_blocking`), True runs the kernels in
    interpret mode, False the XLA form."""
    if activation not in (None, "silu"):
        raise ValueError(f"causal_conv: activation {activation!r}")
    B, S, _ = x.shape
    K, C = w.shape
    blocking, interpret = _conv_blocking(interpret, S, C, K, x.dtype,
                                         positions, first_channel)
    row = {"batch": B, "positions": S, "channels": C, "taps": K,
           "orientation": positions,
           "path": "xla" if blocking is None else "kernel"}
    if blocking is not None:
        from deepspeed_tpu.ops.pallas.causal_conv import causal_conv_kernels
        row.update(slab=blocking.slab, tile=blocking.tile)
        y = causal_conv_kernels(x, w, segment_ids, bias, activation,
                                blocking, first_channel, interpret)
    else:
        y = _causal_conv_xla(x[..., first_channel:first_channel + C], w,
                             segment_ids)
        if bias is not None:
            y = y + bias.astype(x.dtype)
        if activation is not None:
            y = jax.nn.silu(y)
    count_in_step(conv_calls={f"{B}x{S}x{C}x{K}x{positions}": row})
    return y


def _causal_conv_xla(x, w, segment_ids):
    """The taps as shifted copies of ``x``, multiplied and added in
    ``x``'s dtype: the fallback and the kernels' oracle."""
    K = w.shape[0]
    S = x.shape[1]
    w = w.astype(x.dtype)
    y = x * w[K - 1]
    for back in range(1, K):
        past = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :S]
        if segment_ids is not None:
            before = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                             constant_values=-1)[:, :S]
            past = jnp.where((before == segment_ids)[..., None], past, 0)
        y = y + past * w[K - 1 - back]
    return y


def _chunked(x, n, C, Hk):
    """[B, S, Hk * rep, ...] -> [n, B, Hk, rep, C, ...]"""
    B, _, H = x.shape[:3]
    x = x.reshape((B, n, C, Hk, H // Hk) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 2, 4), 1, 0)


def _kernel_blocking(interpret, n, C, rep, dk, dv, dt, by_channel=False):
    """(the grid blocking of the rule's kernels — ops/pallas/kda.py's for a
    decay a key channel, else ops/pallas/gated_delta_rule.py's — or None
    for the XLA chunked forms below; interpret), by ``vmem.lowering``'s
    rule."""
    from deepspeed_tpu.ops.pallas import gated_delta_rule as gdr, kda, vmem
    kernels = kda if by_channel else gdr
    return vmem.lowering(
        interpret, kernels.supported(dk, dv, C, rep),
        lambda: kernels.chunks_per_step(n, C, rep, dk, dv,
                                        jnp.dtype(dt).itemsize))


def gated_delta_rule(q, k, v, g, beta, segment_ids=None,
                     chunk: int = DEFAULT_CHUNK, interpret=None,
                     l2norm_scales=None):
    """The recurrence of the module docstring for every head at once.

    ``q``, ``k`` [B, S, Hk, dk] (already normalised and scaled as the
    layer wants them — or, with ``l2norm_scales`` = (q's, k's), as they
    are: the call then takes ``l2norm(q) * q's`` and ``l2norm(k) * k's``
    itself, which the kernels do on the tiles they hold), ``v`` [B, S,
    Hv, dv] with ``Hv`` a multiple of
    ``Hk`` (key head ``h`` serves value heads ``h*Hv/Hk ..``), ``g`` (log
    decay, <= 0) [B, S, Hv] — one a head — or [B, S, Hv, dk] — one a key
    channel — and ``beta`` (write strength) [B, S, Hv],
    ``segment_ids`` [B, S] int or None.  Returns ``o`` [B, S, Hv, dv] in
    ``v``'s dtype.
    Matrix products take their operands in ``v``'s dtype (the model's:
    bfloat16 in a bf16 step, float32 in a float32 one) and accumulate in
    float32; the decays, the inverse of ``I + L`` and the carried state are
    float32.  Differentiable in all five.  ``interpret``: None chooses
    the lowering (:func:`_kernel_blocking`), True runs the kernels in
    interpret mode (the XLA form for a shape they refuse), False the XLA
    form."""
    B, S, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    dt = v.dtype
    C = min(int(chunk), S)
    n = -(-S // C)
    pad = n * C - S
    f32 = lambda a: a.astype(jnp.float32)
    by_channel = g.ndim == 4
    blocking, interpret = _kernel_blocking(interpret, n, C, Hv // Hk, dk, dv,
                                           dt, by_channel)
    if l2norm_scales is not None and blocking is None:
        q, k = (l2norm(t) * s for t, s in zip((q, k), l2norm_scales))
    if l2norm_scales is None or blocking is None:
        q, k = q.astype(dt), k.astype(dt)
    g, beta = f32(g), f32(beta)
    seg = (jnp.zeros((B, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    if pad:
        # tokens that write nothing (beta 0), decay nothing (g 0) and
        # belong to the last document
        tail = lambda a: jnp.pad(a, ((0, 0), (0, pad))
                                 + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (tail(t) for t in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    row = {"chunks": n, "chunk_len": C, "batch": B, "heads": Hv,
           "dk": dk, "dv": dv, "decay": "channel" if by_channel else "head",
           "path": "xla" if blocking is None else "kernel"}
    if blocking is not None:
        from deepspeed_tpu.ops.pallas.gated_delta_rule import \
            gated_delta_rule_kernels
        from deepspeed_tpu.ops.pallas.kda import kda_kernels
        row.update(heads_per_step=blocking.heads,
                   chunks_per_step=blocking.chunks)
        kernels = kda_kernels if by_channel else gated_delta_rule_kernels
        o = kernels(q, k, v, g, beta, seg, blocking, l2norm_scales,
                    interpret)
    else:
        xla = _chunked_xla_channel if by_channel else _chunked_xla
        o = xla(q, k, v, g, beta, seg, n, C)
    count_in_step(delta_rule_calls={f"{B}x{n * C}x{Hv}x{dk}x{dv}": row})
    return o[:, :S]


def _chunked_xla(q, k, v, g, beta, seg, n, C):
    """The chunked form as XLA einsums around a ``lax.scan``: the fallback
    and, beside :func:`gated_delta_rule_recurrent`, the kernels' oracle.
    Arguments as :func:`gated_delta_rule` prepared them (``n`` chunks of
    ``C`` tokens); returns ``o`` [B, n * C, Hv, dv]."""
    B, _, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    dt = v.dtype
    f32 = lambda a: a.astype(jnp.float32)
    rep = Hv // Hk
    dot = lambda spec, a, b: jnp.einsum(
        spec, a, b, preferred_element_type=jnp.float32)

    # g = key head, r = the value heads it serves, i/j/c = positions
    qc, kc = (_chunked(t, n, C, Hk)[:, :, :, 0] for t in (q, k))  # nbgcd
    vc = _chunked(v, n, C, Hk)                               # [n,B,g,r,C,dv]
    gc, bc = (_chunked(t, n, C, Hk) for t in (g, beta))      # [n,B,g,r,C]
    sc = seg.reshape(B, n, C).transpose(1, 0, 2)             # [n, B, C]
    # the document the previous chunk ended in (chunk 0: no state yet)
    prev = jnp.concatenate([sc[:1, :, 0], sc[:-1, :, -1]], axis=0)  # [n, B]
    G = jnp.cumsum(gc, axis=-1)
    heads = lambda m: m[:, :, None, None]                    # over g and r
    same = heads(sc[..., :, None] == sc[..., None, :])       # [n,B,1,1,C,C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # decay from position j to position i >= j of one document, else 0;
    # the difference is taken only where it is <= 0
    decay = jnp.exp(jnp.where(same & lower,
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    from_state = jnp.where(heads(sc == prev[..., None]), jnp.exp(G), 0.0)
    to_end = jnp.exp(jnp.where(heads(sc == sc[..., -1:]),
                               G[..., -1:] - G, -jnp.inf))
    keep_state = from_state[..., -1]                         # [n,B,g,r]

    kk = dot("nbgid,nbgjd->nbgij", kc, kc)[:, :, :, None]    # per key head
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    unit_lower = jnp.where(strict, bc[..., None] * kk * decay, 0.0) \
        + jnp.eye(C)
    # T = (I + L)^-1 once, in float32; W and U are products with it
    T = lax.linalg.triangular_solve(
        unit_lower, jnp.broadcast_to(jnp.eye(C), unit_lower.shape),
        left_side=True, lower=True, unit_diagonal=True).astype(dt)
    W = dot("nbgrij,nbgrjd->nbgrid", T,
            ((bc * from_state)[..., None]
             * f32(kc)[:, :, :, None]).astype(dt)).astype(dt)
    U = dot("nbgrij,nbgrjd->nbgrid", T,
            (bc[..., None] * f32(vc)).astype(dt)).astype(dt)
    attn = (dot("nbgid,nbgjd->nbgij", qc, kc)[:, :, :, None]
            * decay).astype(dt)

    @jax.checkpoint
    def one_chunk(state, xs):
        W_c, U_c, attn_c, q_c, k_c, from_c, to_c, keep_c = xs
        held = state.astype(dt)                              # [B,g,r,dk,dv]
        v_new = f32(U_c) - dot("bgrck,bgrkv->bgrcv", W_c, held)
        o = from_c[..., None] * dot("bgck,bgrkv->bgrcv", q_c, held) \
            + dot("bgrij,bgrjv->bgriv", attn_c, v_new.astype(dt))
        state = state * keep_c[..., None, None] + dot(
            "bgck,bgrcv->bgrkv", k_c, (to_c[..., None] * v_new).astype(dt))
        return state, o.astype(dt)

    state0 = jnp.zeros((B, Hk, rep, dk, dv), jnp.float32)
    _, o = lax.scan(one_chunk, state0,
                    (W, U, attn, qc, kc, from_state, to_end, keep_state))
    o = jnp.moveaxis(o, 0, 1)                                # [B,n,g,r,C,dv]
    return jnp.moveaxis(o, 4, 2).reshape(B, n * C, Hv, dv)


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _unit_lower_inverse(L):
    """``(I + L)^-1`` for ``L`` [..., C, C] strictly lower triangular, in
    float32, by halves: with the inverses ``A'``, ``D'`` of two
    neighbouring diagonal blocks, ``[[A, 0], [B, D]]^-1 = [[A', 0], [-D' B
    A', D']]`` — ``log2 C`` levels of two batched products each, from the
    1 x 1 blocks up, as accurate as forward substitution.  (The triangular
    solve's lowering walks the diagonal blocks one after another, 22 ms a
    layer-call of 8,192 chunk-heads on a v5e; the finite series ``sum_k
    (-L)^k`` is fast and loses every digit to cancellation once
    neighbouring keys are alike: PERF.md section 6, PR 60.)"""
    C = L.shape[-1]
    P = 1 << (C - 1).bit_length()
    lead = L.shape[:-2]
    if P != C:      # beside an identity: the inverse's corner is the same
        L = jnp.pad(L, [(0, 0)] * len(lead) + [(0, P - C)] * 2)
    dot = lambda a, b: jnp.einsum("...ij,...jk->...ik", a, b,
                                  precision=_HIGHEST)
    inverse = jnp.ones(lead + (P, 1, 1), jnp.float32)    # the 1 x 1 blocks'
    s = 1
    while s < P:
        n = P // (2 * s)
        # of each pair of blocks, the one under the diagonal: rows of the
        # pair's second half, columns of its first
        below = L.reshape(lead + (n, 2, s, n, 2, s))[..., :, 1, :, :, 0, :]
        below = jnp.sum(jnp.where(jnp.eye(n, dtype=bool)[:, None, :, None],
                                  below, 0.0), axis=-2)  # [..., n, s, s]
        first, second = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        inverse = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([-dot(dot(second, below), first), second],
                            axis=-1)], axis=-2)          # [..., n, 2s, 2s]
        s *= 2
    return inverse[..., 0, :C, :C]


def _chunked_xla_channel(q, k, v, g, beta, seg, n, C):
    """:func:`_chunked_xla` for ``g`` [B, n * C, Hv, dk], a decay a key
    channel (the module docstring has the equations).  One ``lax.scan``
    over chunks carries the state, its body checkpointed: what needs no
    state — ``A``, the inverse, ``W``, ``U`` and the decayed rows — is
    made inside it, for the one chunk, so that the element-wise diagonals
    and everything else of a chunk's size live for one step (on a v5e the
    rule's value and gradient at 16,384 tokens x 32 heads read the faster
    the fewer chunks' local work a step batched: 198, 148 and 95 ms at 32,
    8 and 1 under one inverse — PERF.md section 6, PR 60)."""
    B, _, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    dt = v.dtype
    f32 = lambda a: a.astype(jnp.float32)
    rep = Hv // Hk
    sb = _fit(C, SUB_BLOCK)
    nb = C // sb
    dot = lambda spec, a, b: jnp.einsum(
        spec, a, b, preferred_element_type=jnp.float32)

    # g = key head, r = the value heads it serves, i/j = positions, c = a
    # key channel, I/J = sub-blocks; every array leads with its chunk
    qc, kc = (_chunked(t, n, C, Hk) for t in (q, k))         # [n,B,g,1,C,dk]
    vc = _chunked(v, n, C, Hk)                               # [n,B,g,r,C,dv]
    gc = _chunked(g, n, C, Hk)                               # [n,B,g,r,C,dk]
    bc = _chunked(beta, n, C, Hk)                            # [n,B,g,r,C]
    sc = seg.reshape(B, n, C).transpose(1, 0, 2)             # [n, B, C]
    # the document the previous chunk ended in (chunk 0: no state yet)
    prev = jnp.concatenate([sc[:1, :, 0], sc[:-1, :, -1]], axis=0)  # [n, B]
    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    earlier = jnp.arange(C)[None, :] < (jnp.arange(nb) * sb)[:, None]  # I, j
    within = jnp.tril(jnp.ones((sb, sb), bool))              # i >= j
    on_diagonal = jnp.eye(nb, dtype=bool)[:, None, :, None]  # I, i, J, j
    heads = lambda a: a[:, None, None]                       # over g and r
    blocks = lambda a: a.reshape(a.shape[:-2] + (nb, sb, a.shape[-1]))

    @jax.checkpoint
    def one_chunk(state, xs):
        q_c, k_c, v_c, g_c, b_c, s_c, prev_c = xs
        G = jnp.cumsum(g_c, axis=-2)                         # [B,g,r,C,dk]
        Gs = blocks(G)                                       # ...,I,i,c
        ref = Gs[..., :1, :]                                 # at I's first row
        # rows against their sub-block's first, keys against every later
        # sub-block's first: both exponents <= 0, zero where not earlier
        k_ref = (f32(k_c)[..., None, :, :] * jnp.exp(jnp.where(
            earlier[..., None], ref - G[..., None, :, :], -jnp.inf))
        ).astype(dt)                                         # ...,I,j,c
        rows = jnp.exp(Gs - ref)
        # the diagonal sub-blocks, element by element: k_j exp(G_i - G_j)
        k_in = f32(blocks(jnp.broadcast_to(k_c, G.shape)))[..., None, :, :] \
            * jnp.exp(jnp.where(within[..., None],
                                Gs[..., :, None, :] - Gs[..., None, :, :],
                                -jnp.inf))                   # ...,I,i,j,c
        # sum_c a_ic k_jc exp(G_ic - G_jc) for a = k and a = q at once (one
        # reduction reads the diagonal's exponentials): [2, .., C, C]
        a_s = blocks(jnp.stack([jnp.broadcast_to(f32(a), G.shape)
                                for a in (k_c, q_c)]))
        off = dot("abgrIic,bgrIjc->abgrIij", (a_s * rows).astype(dt), k_ref)
        diag = jnp.sum(a_s[..., None, :] * k_in, axis=-1)    # ...,I,i,j
        A = jnp.where(on_diagonal, diag[..., None, :],
                      off.reshape(off.shape[:-1] + (nb, sb)))
        A = A.reshape(A.shape[:-4] + (C, C))
        same = heads(s_c[:, :, None] == s_c[:, None, :])     # [B,1,1,C,C]
        kk = jnp.where(same & strict, A[0], 0.0)
        attn = jnp.where(same & lower, A[1], 0.0).astype(dt)
        # T = (I + L)^-1 once, in float32; W and U are products with it
        T = _unit_lower_inverse(b_c[..., None] * kk).astype(dt)
        from_state = jnp.where(heads(s_c == prev_c[:, None])[..., None],
                               jnp.exp(G), 0.0)              # [B,g,r,C,dk]
        to_end = jnp.exp(jnp.where(heads(s_c == s_c[:, -1:])[..., None],
                                   G[..., -1:, :] - G, -jnp.inf))
        W = dot("bgrij,bgrjc->bgric", T,
                (b_c[..., None] * from_state * f32(k_c)).astype(dt))
        U = dot("bgrij,bgrjd->bgrid", T,
                (b_c[..., None] * f32(v_c)).astype(dt))
        # ... and what does: the new values, the output, the state's step
        held = state.astype(dt)                              # [B,g,r,dk,dv]
        v_new = (U - dot("bgrck,bgrkv->bgrcv", W.astype(dt), held)
                 ).astype(dt)
        o = dot("bgrck,bgrkv->bgrcv", (from_state * f32(q_c)).astype(dt),
                held) + dot("bgrij,bgrjv->bgriv", attn, v_new)
        state = state * from_state[..., -1, :, None] + dot(
            "bgrck,bgrcv->bgrkv", (to_end * f32(k_c)).astype(dt), v_new)
        return state, o.astype(dt)

    state0 = jnp.zeros((B, Hk, rep, dk, dv), jnp.float32)
    _, o = lax.scan(one_chunk, state0, (qc, kc, vc, gc, bc, sc, prev))
    o = jnp.moveaxis(o, 0, 1)                                # [B,n,g,r,C,dv]
    return jnp.moveaxis(o, 4, 2).reshape(B, n * C, Hv, dv)


def gated_delta_rule_recurrent(q, k, v, g, beta, segment_ids=None):
    """The same by the literal per-token recurrence (a ``lax.scan`` over
    tokens): the oracle the chunked form is tested against, and what a
    decode step would run.  Same arguments (``g`` of either rank) and
    result."""
    B, S, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    rep = Hv // Hk
    f32 = lambda a: a.astype(jnp.float32)
    q, k = (jnp.repeat(f32(t), rep, axis=2) for t in (q, k))
    seg = (jnp.zeros((B, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    first = jnp.concatenate(
        [jnp.ones((B, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t, first_t = xs
        if g_t.ndim == 2:       # [B, Hv]: one decay for a head's channels
            g_t = g_t[..., None]
        keep = jnp.where(first_t[:, None, None], 0.0, jnp.exp(g_t))
        state = state * keep[..., None]                 # [B, Hv, dk | 1, 1]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - read))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    by_token = lambda a: jnp.moveaxis(f32(a), 1, 0)
    _, o = lax.scan(token, jnp.zeros((B, Hv, dk, dv), jnp.float32),
                    (by_token(q), by_token(k), by_token(v), by_token(g),
                     by_token(beta), jnp.moveaxis(first, 1, 0)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)
