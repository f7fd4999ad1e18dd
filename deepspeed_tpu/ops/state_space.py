"""The state-space scan of Mamba-2 (Dao & Gu 2024, "Transformers are SSMs",
arXiv:2405.21060: the SSD layer) — what a Mamba-2 mixer
(models/nemotron_h.py) computes between its convolution and its gate.

Per head, with a float32 state ``H`` [P, N] (head dim x state size),
``H_0 = 0``, one scalar ``A < 0`` a head and a step ``dt_t > 0`` a token:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t
    y_t = H_t C_t + D x_t

``B`` and ``C`` [N] are shared by the heads of a group (head ``h`` reads
group ``h // (heads / groups)``).  :func:`ssd_scan` computes it in the
chunked form of the paper's section 6.  With ``G_i`` the running sum of
``dt A`` inside a chunk of ``C`` tokens, everything that does not need the
incoming state is a batched matrix product over all chunks at once:

    Y_intra = (L . C B^T) (dt x)      L[i, j] = exp(G_i - G_j), i >= j
    S_c     = sum_j exp(G_last - G_j) dt_j x_j (x) B_j      the chunk's state

``C B^T`` once per group for its heads, ``L`` per head and in float32.
Across chunks a ``lax.scan`` carries ``H`` in float32, ``H_c = exp(G_last)
H_{c-1} + S_c``, and ``Y_inter = exp(G_i) C_i H_{c-1}``.  Matrix products
take their operands in ``x``'s dtype (the model's: bfloat16 in a bf16
step) and accumulate in float32; the decays and the state are float32.
The backward pass is autodiff's.

**Packed documents.**  With ``segment_ids`` the state is zero at a
document's first token, exactly as if ``dt A`` were minus infinity there,
taken as a mask and not as a number (ops/linear_attention.py has the same
rule): ``L[i, j]`` is zero where the documents of ``i`` and ``j`` differ,
a position writes into its chunk's state only where its document is the
chunk's last, and reads the incoming state only where its document is the
one the previous chunk ended in.  Boundaries may fall anywhere — inside a
chunk, at its edge, around a one-token document.  Where ``chunk`` does not
divide the sequence the tail is padded with tokens of step 0.

:func:`ssd_recurrent` is the literal per-token recurrence: the oracle the
chunked form is tested against, and what a decode step would run.

The model writes the ``jax.named_scope`` ``scan`` around the call
(telemetry/tracing.py ``SCOPE_SCAN``); each call leaves its chunk count,
chunk length, heads and groups in the step's account
and the lowering it took (``tracing.ssd_chunks``).

One algorithm, two lowerings (:func:`_kernel_blocking` chooses, by the
rule of ``ops/pallas/vmem.lowering``).  On one TPU, for a state size of
whole lane tiles, a chunk of one (128) and heads of whole sublane tiles,
the Mosaic kernels of ops/pallas/state_space.py: a group's state stays
in VMEM across a sequence's chunks, a head's decay matrix is built in
registers and never written to HBM, the backward is written by hand.
Elsewhere :func:`_chunked_xla`, einsums around a ``lax.scan`` with
autodiff's backward — the fallback and, beside :func:`ssd_recurrent`, the
kernels' oracle.

:func:`lightning_attention` (models/minicpm_sala.py) is the same recurrence
with nothing selective left in it — a step of 1, a constant decay a head,
``B = k``, ``C = q``, one group a head — and calls :func:`ssd_scan`: the
two families share one function and, on a TPU, one pair of kernels.
"""
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.telemetry.tracing import count_in_step

DEFAULT_CHUNK = 128
_HIGHEST = lax.Precision.HIGHEST      # the oracle's products


def _chunked(t, n, C, G):
    """[B, n * C, G * r, ...] -> [n, B, G, r, C, ...]"""
    B, _, H = t.shape[:3]
    t = t.reshape((B, n, C, G, H // G) + t.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(t, 2, 4), 1, 0)


def _kernel_blocking(interpret, n, C, r, P, N, dtype):
    """(the grid blocking of ops/pallas/state_space.py's kernels, or None
    for the XLA chunked form below; interpret), by ``vmem.lowering``'s
    rule."""
    from deepspeed_tpu.ops.pallas import state_space as kernels, vmem
    return vmem.lowering(
        interpret, kernels.supported(P, N, r, C),
        lambda: kernels.chunks_per_step(n, C, r, P, N,
                                        jnp.dtype(dtype).itemsize))


def _chunk_alone_refused(C, r, P, N) -> dict:
    """``{"why": "chunk 256"}`` where the kernels refuse a call's shapes for
    its chunk alone — they would take the same call at their own chunk — so
    that the step's account says why a published chunk size runs as XLA;
    else nothing."""
    from deepspeed_tpu.ops.pallas import state_space as kernels
    alone = not kernels.supported(P, N, r, C) \
        and kernels.supported(P, N, r, kernels.LANES)
    return {"why": f"chunk {C}"} if alone else {}


def ssd_scan(x, dt, A, B, C, D=None, segment_ids=None,
             chunk: int = DEFAULT_CHUNK, interpret=None):
    """The recurrence of the module docstring for every head at once.

    ``x`` [b, S, H, P]; ``dt`` [b, S, H] (already softplus'd, > 0); ``A``
    [H] (< 0); ``B``, ``C`` [b, S, G, N] with ``H`` a multiple of ``G``;
    ``D`` [H] or None (no skip term); ``segment_ids`` [b, S] int or None.
    Returns ``y`` [b, S, H, P] in ``x``'s dtype.  Differentiable in ``x``,
    ``dt``, ``A``, ``B``, ``C`` and ``D``.  ``interpret``: None chooses the
    lowering (:func:`_kernel_blocking`), True runs the kernels in
    interpret mode, False the XLA form."""
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    dtype = x.dtype
    Cn = min(int(chunk), S)
    n = -(-S // Cn)
    pad = n * Cn - S
    f32 = lambda t: t.astype(jnp.float32)
    blocking, interpret = _kernel_blocking(interpret, n, Cn, H // G, P, N,
                                           dtype)
    dt, A = f32(dt), f32(A)
    seg = (jnp.zeros((b, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    if pad:
        # tokens of step 0: they decay nothing and write nothing, and
        # belong to the last document
        tail = lambda t: jnp.pad(t, ((0, 0), (0, pad))
                                 + ((0, 0),) * (t.ndim - 2))
        x, dt, B, C = (tail(t) for t in (x, dt, B, C))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    B, C = B.astype(dtype), C.astype(dtype)
    row = {"chunks": n, "chunk_len": Cn, "batch": b, "heads": H, "groups": G,
           "head_dim": P, "state": N,
           "path": "xla" if blocking is None else "kernel",
           **_chunk_alone_refused(Cn, H // G, P, N)}
    if blocking is not None:
        from deepspeed_tpu.ops.pallas.state_space import ssd_kernels
        row.update(heads_per_step=blocking.heads,
                   chunks_per_step=blocking.chunks)
        y = ssd_kernels(x, dt, A, B, C, D, seg, blocking, interpret)
    else:
        y = _chunked_xla(x, dt, A, B, C, D, seg, n, Cn)
    count_in_step(ssd_calls={f"{b}x{n * Cn}x{H}x{P}x{N}": row})
    return y[:, :S]


def _chunked_xla(x, dt, A, B, C, D, seg, n, Cn):
    """The chunked form as XLA einsums around a ``lax.scan``, the backward
    autodiff's: the fallback and, beside :func:`ssd_recurrent`, the
    kernels' oracle.  Arguments as :func:`ssd_scan` prepared them (``n``
    chunks of ``Cn`` tokens, float32 ``dt`` and ``A``, ``B`` and ``C`` in
    ``x``'s dtype); returns ``y`` [b, n * Cn, H, P] in ``x``'s dtype."""
    b, _, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    dtype = x.dtype
    f32 = lambda t: t.astype(jnp.float32)
    dot = lambda spec, u, v: jnp.einsum(
        spec, u, v, preferred_element_type=jnp.float32)

    # g = group, r = the heads it serves, i/j = positions, p, s = the
    # state's two dims
    xc = _chunked(x, n, Cn, G)                               # [n,b,g,r,C,P]
    dtc = _chunked(dt, n, Cn, G)                             # [n,b,g,r,C]
    Bc, Cc = (_chunked(t, n, Cn, G)[:, :, :, 0]
              for t in (B, C))                               # [n,b,g,C,N]
    sc = seg.reshape(b, n, Cn).transpose(1, 0, 2)            # [n, b, C]
    # the document the previous chunk ended in (chunk 0: no state yet)
    prev = jnp.concatenate([sc[:1, :, 0], sc[:-1, :, -1]], axis=0)  # [n, b]
    Gc = jnp.cumsum(dtc * A.reshape(G, H // G, 1), axis=-1)
    heads = lambda m: m[:, :, None, None]                    # over g and r
    same = heads(sc[..., :, None] == sc[..., None, :])       # [n,b,1,1,C,C]
    lower = jnp.tril(jnp.ones((Cn, Cn), bool))
    # decay from position j to position i >= j of one document, else 0;
    # the difference is taken only where it is <= 0
    decay = jnp.exp(jnp.where(same & lower,
                              Gc[..., :, None] - Gc[..., None, :], -jnp.inf))
    from_state = jnp.where(heads(sc == prev[..., None]), jnp.exp(Gc), 0.0)
    to_end = jnp.exp(jnp.where(heads(sc == sc[..., -1:]),
                               Gc[..., -1:] - Gc, -jnp.inf))
    keep_state = from_state[..., -1]                         # [n,b,g,r]

    cb = dot("nbgis,nbgjs->nbgij", Cc, Bc)[:, :, :, None]    # per group
    xdt = (dtc[..., None] * f32(xc)).astype(dtype)
    y = dot("nbgrij,nbgrjp->nbgrip", (decay * cb).astype(dtype), xdt)
    states = dot("nbgrjp,nbgjs->nbgrps",
                 ((to_end * dtc)[..., None] * f32(xc)).astype(dtype), Bc)

    def one_chunk(state, xs):
        s_c, keep_c = xs
        return state * keep_c[..., None, None] + s_c, state

    _, incoming = lax.scan(
        one_chunk, jnp.zeros((b, G, H // G, P, N), jnp.float32),
        (states, keep_state))                                # [n,b,g,r,P,N]
    y = y + from_state[..., None] * dot(
        "nbgis,nbgrps->nbgrip", Cc, incoming.astype(dtype))
    y = jnp.moveaxis(y, 0, 1)                                # [b,n,g,r,C,P]
    y = jnp.moveaxis(y, 4, 2).reshape(b, n * Cn, H, P)
    if D is not None:
        y = y + f32(D)[:, None] * f32(x)
    return y.astype(dtype)


def ssd_recurrent(x, dt, A, B, C, D=None, segment_ids=None):
    """The same by the literal per-token recurrence (a ``lax.scan`` over
    tokens), in float32.  Same arguments and result."""
    b, S, H, P = x.shape
    G = B.shape[2]
    f32 = lambda t: t.astype(jnp.float32)
    Bh, Ch = (jnp.repeat(f32(t), H // G, axis=2) for t in (B, C))
    seg = (jnp.zeros((b, S), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    first = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    A = f32(A)

    def token(state, xs):
        x_t, dt_t, B_t, C_t, first_t = xs
        keep = jnp.where(first_t[:, None], 0.0, jnp.exp(dt_t * A))  # [b, H]
        state = state * keep[..., None, None] \
            + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :]
        return state, jnp.einsum("bhps,bhs->bhp", state, C_t,
                                 precision=_HIGHEST)

    by_token = lambda t: jnp.moveaxis(f32(t), 1, 0)
    _, y = lax.scan(token, jnp.zeros((b, H, P, Bh.shape[-1]), jnp.float32),
                    (by_token(x), by_token(dt), by_token(Bh), by_token(Ch),
                     jnp.moveaxis(first, 1, 0)))
    y = jnp.moveaxis(y, 0, 1)
    if D is not None:
        y = y + f32(D)[:, None] * f32(x)
    return y.astype(x.dtype)


def lightning_slopes(heads: int):
    """The decay rates of Lightning attention, one a head: ``s_h = 2^(-8 h
    / heads)``, h = 1..heads (ALiBi's slopes, as Lightning Attention-2,
    arXiv:2401.04658, builds them); a head's state decays by ``exp(-s_h)``
    a token."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                   / heads)


def lightning_attention(q, k, v, slopes, segment_ids=None,
                        chunk: int = DEFAULT_CHUNK, interpret=None):
    """Lightning attention (Qin et al. 2024, arXiv:2401.04658): linear
    attention with a constant decay a head and no gate.  Per head, with a
    float32 state ``S`` [dk, dv], zero at a document's first token:

        S_t = exp(-slopes[h]) S_{t-1} + k_t^T v_t        o_t = q_t S_t

    ``q``, ``k`` [b, S, H, dk] (any scale already in ``q``), ``v`` [b, S,
    H, dv], ``slopes`` [H] > 0 -> ``o`` [b, S, H, dv] in ``v``'s dtype.
    This IS the recurrence at the top of this file with a step of 1, ``A =
    -slopes``, ``B = k``, ``C = q`` and one group a head, so it runs
    through :func:`ssd_scan` — its Mosaic kernels on one TPU, the XLA form
    elsewhere — and adds nothing of its own: the step's account holds the
    call as the row of ``tracing.ssd_chunks()`` whose ``groups`` equal its
    ``heads`` (``chunks`` is the count of Lightning chunks).  The slopes
    are constants: no gradient flows to them."""
    b, S, H, _ = q.shape
    return ssd_scan(v, jnp.ones((b, S, H), jnp.float32),
                    -lax.stop_gradient(slopes.astype(jnp.float32)), k, q,
                    None, segment_ids, chunk=chunk, interpret=interpret)
