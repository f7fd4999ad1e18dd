"""Manifold-constrained hyper-connections (mHC: Xie et al., DeepSeek-AI,
arXiv:2512.24880 section 4; hyper-connections: Zhu et al.,
arXiv:2409.19606) — the residual path of a model whose residual is ``n``
streams a token (models/xing.py).

Per token the residual is ``X`` [n, C], **held as one row of n C**, the
streams side by side along the last axis: the stream of a batch is ``[B, S,
n C]``, whose last two axes tile as any activation's do (an axis of n = 4
between them would be padded to a whole sublane tile), a stream is a
lane-aligned slice of it, and the projection below is a plain matmul on it
as it lies.  A sublayer ``F`` (an attention, a
feed-forward) has its own ``Phi`` [n C, 2 n + n^2], three scalars
``alpha = (pre, post, res)`` and biases ``b_pre``, ``b_post`` [n], ``b_res``
[n, n].  In float32:

    r = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)      (no learnable weight)
    [p | q | R] = r Phi                               (n | n | n^2 columns)
    H_pre  = sigmoid(alpha_pre p + b_pre)             [n]
    H_post = 2 sigmoid(alpha_post q + b_post)         [n]
    M_0    = exp(clamp(alpha_res mat(R) + b_res, lo, hi))
    M     <- T_r(T_c(M)), ``sweeps`` times            (columns over their
             sums, then rows over theirs, each sum + sinkhorn_eps)
    H_res  = M_sweeps                                 [n, n], doubly
                                                      stochastic to the
                                                      iteration's accuracy
    h      = sum_i H_pre[i] X[i]                      (:func:`hc_read`)
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(h)  (:func:`hc_write`)

The stream comes and goes in its own dtype (bfloat16 in training); every
coefficient, every sum and the Sinkhorn sweeps are float32, and what is
written is rounded once.  ``r Phi`` is computed as ``(X Phi) / rms``: the
products of a bfloat16 stream with bfloat16 weights are exact in float32,
so ``r`` is never rounded to the stream's dtype.

All of it is XLA: the read and the write are one pass each over the
stream, forward and (differentiated by hand) backward — their share of the
memory floor on the chip is in PERF.md (``hc.stream_roofline``) — and the
sweeps run on ``[n, n, tokens]`` arrays, tokens along the lanes, with
autodiff's gradients through them.
Scopes ``hc/coeff``, ``hc/read`` and ``hc/write`` lie inside the scope of
the sublayer that calls; each sublayer's call of :func:`hc_coefficients`
leaves a row in the step's account (``tracing.hc_calls``).
"""
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.tracing import (SCOPE_HC, SCOPE_HC_COEFF,
                                             SCOPE_HC_READ, SCOPE_HC_WRITE,
                                             count_in_step)


@dataclass(frozen=True)
class HyperConnection:
    """The sizes and constants of one model's hyper-connections."""
    streams: int = 4                #: n (``hc_mult``)
    sweeps: int = 20                #: ``hc_sinkhorn_iters``
    sinkhorn_eps: float = 1e-6      #: ``hc_eps``, added to every sum
    clamp_min: float = -30.0        #: ``mhc_h_res_clamp_min``
    clamp_max: float = 30.0         #: ``mhc_h_res_clamp_max``
    norm_eps: float = 1e-6          #: the flattened RMSNorm's

    @property
    def columns(self) -> int:
        return 2 * self.streams + self.streams ** 2


#: ``b_res`` starts as this times the identity: ``H_res`` within ``(n - 1)
#: exp(-4.5)`` = 0.033 of it at n = 4 (a larger diagonal starts nearer and
#: lets less gradient through the saturated entries)
RES_DIAGONAL = 4.5


def init_hc_params(hc: HyperConnection, d_model: int, key, lead=(),
                   alpha: float = 0.01):
    """One sublayer's leaves (``lead``: stacked layers).  The start is
    close to the pre-norm residual ``X + F(N(X))``: ``phi`` normal of std
    0.02 and ``alpha`` small, so the biases decide; ``b_pre`` = logit(1/n)
    (the read is the streams' mean, which is any one of them while they
    are equal), ``b_post`` 0 (``H_post`` 1) and ``b_res`` ``RES_DIAGONAL``
    times the identity."""
    n = hc.streams
    return {
        "phi": jax.random.normal(key, lead + (n * d_model, hc.columns),
                                 jnp.float32) * 0.02,
        "alpha": jnp.full(lead + (3,), alpha, jnp.float32),
        "b_pre": jnp.full(lead + (n,), -jnp.log(n - 1.0) if n > 1 else 30.0,
                          jnp.float32),
        "b_post": jnp.zeros(lead + (n,), jnp.float32),
        "b_res": jnp.broadcast_to(RES_DIAGONAL * jnp.eye(n, dtype=jnp.float32),
                                  lead + (n, n)),
    }


def sinkhorn(m, sweeps: int, eps: float):
    """``m`` [n, n, ...] positive -> ``sweeps`` times columns over their
    sums (axis 0 runs down a column), then rows over theirs."""
    for _ in range(sweeps):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def hc_coefficients(x, p, hc: HyperConnection, at: str = "",
                    calls: int = 1):
    """``x`` [..., n C] (the stream) and one sublayer's leaves ``p`` ->
    ``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` float32.
    ``at`` names the call site for the step's account and ``calls`` says how
    often a forward pass runs it (the length of the layer loop it is
    traced in)."""
    n = hc.streams
    lead, width = x.shape[:-1], x.shape[-1] // n
    assert x.shape[-1] == n * width, (x.shape, n)
    tokens = math.prod(lead)
    count_in_step(hc_calls={f"{tokens}x{n}x{width}@{at}": {
        "site": at, "tokens": tokens, "streams": n, "width": width,
        "calls_per_pass": calls}})
    with jax.named_scope(SCOPE_HC), jax.named_scope(SCOPE_HC_COEFF):
        flat = x.reshape(-1, n * width)
        f32 = flat.astype(jnp.float32)
        inv_rms = jax.lax.rsqrt(jnp.mean(f32 * f32, axis=-1) + hc.norm_eps)
        proj = jnp.einsum(
            "tk,kj->jt", flat, p["phi"].astype(flat.dtype),
            precision=None if flat.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) * inv_rms    # [2n + n^2, T]
        alpha = p["alpha"].astype(jnp.float32)
        b = lambda name: p[name].astype(jnp.float32)[..., None]
        pre = jax.nn.sigmoid(alpha[0] * proj[:n] + b("b_pre"))
        post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[n:2 * n] + b("b_post"))
        res = jnp.exp(jnp.clip(
            alpha[2] * proj[2 * n:].reshape(n, n, -1) + b("b_res"),
            hc.clamp_min, hc.clamp_max))
        res = sinkhorn(res, hc.sweeps, hc.sinkhorn_eps)
        return (pre.T.reshape(lead + (n,)), post.T.reshape(lead + (n,)),
                jnp.moveaxis(res, -1, 0).reshape(lead + (n, n)))


def _streams(x, n):
    """The n streams of x [..., n C], float32 [..., C] each."""
    width = x.shape[-1] // n
    return [x[..., j * width:(j + 1) * width].astype(jnp.float32)
            for j in range(n)]


def _coefficient(h, *index):
    """One coefficient a token, laid along the width: [..., 1]."""
    return h[(Ellipsis,) + index + (None,)]


# The read and the write are written stream by stream (n is small and
# static) and differentiated by hand: each direction is then elementwise
# work on [..., C] slices that XLA fuses into one pass, where the transpose
# of n slices is n zero-padded copies of the stream and a sum.
@jax.custom_vjp
def _read(x, h_pre):
    n = h_pre.shape[-1]
    return sum(_coefficient(h_pre, i) * xi
               for i, xi in enumerate(_streams(x, n))).astype(x.dtype)


def _read_fwd(x, h_pre):
    return _read(x, h_pre), (x, h_pre)


def _read_bwd(saved, dh):
    x, h_pre = saved
    n = h_pre.shape[-1]
    dh = dh.astype(jnp.float32)
    dx = jnp.concatenate([(_coefficient(h_pre, i) * dh).astype(x.dtype)
                          for i in range(n)], axis=-1)
    dpre = jnp.stack([jnp.sum(xi * dh, axis=-1) for xi in _streams(x, n)],
                     axis=-1)
    return dx, dpre


_read.defvjp(_read_fwd, _read_bwd)


@jax.custom_vjp
def _write(x, y, h_post, h_res):
    n = h_post.shape[-1]
    xs, yf = _streams(x, n), y.astype(jnp.float32)
    return jnp.concatenate([
        (sum(_coefficient(h_res, i, j) * xs[j] for j in range(n))
         + _coefficient(h_post, i) * yf).astype(x.dtype)
        for i in range(n)], axis=-1)


def _write_fwd(x, y, h_post, h_res):
    return _write(x, y, h_post, h_res), (x, y, h_post, h_res)


def _write_bwd(saved, g):
    x, y, h_post, h_res = saved
    n = h_post.shape[-1]
    gs, xs, yf = _streams(g, n), _streams(x, n), y.astype(jnp.float32)
    dx = jnp.concatenate([
        sum(_coefficient(h_res, i, j) * gs[i] for i in range(n))
        .astype(x.dtype) for j in range(n)], axis=-1)
    dy = sum(_coefficient(h_post, i) * gs[i] for i in range(n)) \
        .astype(y.dtype)
    dpost = jnp.stack([jnp.sum(gi * yf, axis=-1) for gi in gs], axis=-1)
    dres = jnp.stack([jnp.stack([jnp.sum(gi * xj, axis=-1) for xj in xs],
                                axis=-1) for gi in gs], axis=-2)
    return dx, dy, dpost, dres


_write.defvjp(_write_fwd, _write_bwd)


def hc_read(x, h_pre):
    """``sum_i H_pre[i] X[i]``: x [..., n C], h_pre [..., n] -> [..., C] in
    x's dtype."""
    with jax.named_scope(SCOPE_HC), jax.named_scope(SCOPE_HC_READ):
        return _read(x, h_pre)


def hc_write(x, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: x [..., n C], y
    [..., C], h_post [..., n], h_res [..., n, n] -> [..., n C] in x's
    dtype."""
    with jax.named_scope(SCOPE_HC), jax.named_scope(SCOPE_HC_WRITE):
        return _write(x, y, h_post, h_res)


def replicate(x, n: int):
    """Entry: every stream starts as ``x`` [..., C] -> [..., n C]."""
    with jax.named_scope(SCOPE_HC):
        return jnp.concatenate([x] * n, axis=-1)


def exit_sum(x, n: int):
    """Exit: ``sum_i X[i]``, x [..., n C] -> [..., C], summed in float32."""
    with jax.named_scope(SCOPE_HC):
        return sum(_streams(x, n)).astype(x.dtype)
