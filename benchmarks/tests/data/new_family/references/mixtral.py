"""Plain reference for the Mixtral family (``models/mixtral.py``) with
dropless dispatch: forward pass and loss in ``jax.numpy`` and float32 — no
kernel, no sort, no capacity.  A test's fixture (tests/data/), not a
supported configuration: it shows what a new family brings.

Pre-RMSNorm blocks; rotary q and k (split-half pairing); grouped-query
causal attention; a float32 softmax over the experts, the ``top_k``
largest renormalised to sum to one, and the token's output the
gate-weighted sum of those experts' SwiGLU — computed here through every
expert with the gate zero elsewhere; untied head.  Loss, as the model
defines it: the mean next-token cross-entropy over every position of the
micro-batch, plus ``AUX_LOSS_COEF`` times the sum over layers of
``moe/sharded_moe.py::topk_routing``'s load-balance term, E * sum_e
(mean gate of e) * (share of tokens whose first choice is e); then the
mean over the micro-batches of a step.  That term is a mean over the
micro-batch's tokens, so a micro-batch goes through whole: ``chunk`` is
not used.
"""
import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats: the GPT-2
#: reference's.  The rehearsal of tests/test_new_family.py (bfloat16 on
#: the CPU, one seed) reads 4.4e-5; a supported configuration sets its
#: limit from a dozen seeds and a control on the chip (PERF.md section 2)
LOSS_ATOL = 2e-3
AUX_LOSS_COEF = 0.01            # MixtralConfig.aux_loss_coef's default


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    angles = jnp.arange(S)[:, None] * theta ** (-jnp.arange(half) / half)
    cos, sin = (f(angles)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _loss(params, tokens, sizes):
    """tokens [B, S] -> the model's loss on that micro-batch."""
    B, S = tokens.shape
    H, KV, hd = sizes["num_heads"], sizes["num_kv_heads"], sizes["head_dim"]
    E, k, eps = sizes["num_experts"], sizes["top_k"], sizes["rms_norm_eps"]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def block(x, layer):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rope((h @ p["wq"]).reshape(B, S, H, hd), sizes["rope_theta"])
        kk = _rope((h @ p["wk"]).reshape(B, S, KV, hd), sizes["rope_theta"])
        v = (h @ p["wv"]).reshape(B, S, KV, hd)
        kk, v = (jnp.repeat(t, H // KV, axis=2) for t in (kk, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        x = x + jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(
            B, S, H * hd) @ p["wo"]
        h = _rms_norm(x, p["mlp_norm"], eps).reshape(B * S, -1)
        gates = jax.nn.softmax(h @ p["moe"]["router"], axis=-1)     # [T, E]
        top, idx = jax.lax.top_k(gates, k)
        chosen = jax.nn.one_hot(idx, E).sum(1)                      # [T, E]
        weight = gates * chosen / top.sum(-1, keepdims=True)
        every = jnp.einsum(
            "tef,efd->ted",
            jax.nn.silu(jnp.einsum("td,edf->tef", h, p["moe"]["w_gate"]))
            * jnp.einsum("td,edf->tef", h, p["moe"]["w_in"]),
            p["moe"]["w_out"])
        first = jax.nn.one_hot(idx[:, 0], E).mean(0)
        aux = E * (gates.mean(0) * first).sum()
        return x + (weight[..., None] * every).sum(1).reshape(x.shape), aux

    x, aux = jax.lax.scan(block, params["wte"].astype(jnp.float32)[tokens],
                          params["blocks"])
    x = _rms_norm(x, params["final_norm"].astype(jnp.float32), eps)
    logp = jax.nn.log_softmax(
        x[:, :-1] @ params["lm_head"].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return nll.mean() + AUX_LOSS_COEF * aux.sum()


def step_loss(params, batch, sizes, chunk, put=None):
    """The loss ``engine.train_batch`` reports for ``batch`` (leaves
    [gas, B, S]) at ``params``; ``put`` places a micro-batch on the
    devices."""
    put = put or (lambda x: x)
    fn = jax.jit(lambda p, t: _loss(p, t, sizes))
    with jax.default_matmul_precision("highest"):
        return float(np.mean([float(fn(params, put(micro)))
                              for micro in np.asarray(batch["input_ids"])]))
