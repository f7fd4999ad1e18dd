"""Plain reference for the GPT-2 family: forward pass and mean next-token
cross-entropy in ``jax.numpy`` and float32, nothing else — no kernel, no
remat, no mixed precision.  It follows Radford et al. 2019 / Brown et al.
2020 as ``models/gpt2.py`` does: learned positions, pre-LayerNorm blocks,
fused QKV with bias, tanh-GELU MLP of 4*d_model, tied output head.

It runs on the engine's own parameter tree (whatever dtype and sharding
that has): the stacked layers are scanned and one layer at a time is cast
to float32, so no second full copy of the model exists; sequences go
through in chunks, so the [chunk, S, V] logits bound its memory.

Loss, as the engine defines it: per micro-batch the mean over scored
positions (position t is scored against token t+1; with ``segment_ids`` a
document's last token is not scored against the next document's first,
and attention stays inside a document), then the mean over the gas
micro-batches of a step.
"""
import jax
import jax.numpy as jnp
import numpy as np

#: |engine first-step loss - reference loss| allowed, in nats.  The engine
#: computes in bfloat16 (8 significant bits) with float32 softmax and
#: loss; at the published widths on the chip that moved the loss by at
#: most 2.2e-4 (760M, both traffic mixes, 2.7B on four chips; PERF.md,
#: PR 23).  The tolerance is about ten times that: a compute type with
#: fewer significant bits (fp8 e4m3 has 4: 16x the rounding error), a
#: dropped bias or a wrong mask lands outside; bf16 stays well inside.
LOSS_ATOL = 2e-3


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _forward_sums(params, tokens, segments, num_heads, eps):
    """tokens [b, S] -> (sum of scored losses, number scored)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    b, S = tokens.shape
    wte = params["wte"].astype(jnp.float32)
    x = wte[tokens] + params["wpe"].astype(jnp.float32)[:S]
    D = x.shape[-1]
    hd = D // num_heads
    mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
    if segments is not None:
        mask = mask & (segments[:, None, :, None] == segments[:, None, None, :])

    def block(x, layer):
        p = f32(layer)
        h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
        q, k, v = jnp.split(h @ p["qkv_w"] + p["qkv_b"], 3, axis=-1)
        q, k, v = (t.reshape(b, S, num_heads, hd) for t in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, S, D)
        x = x + attn @ p["proj_w"] + p["proj_b"]
        h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
        h = jax.nn.gelu(h @ p["mlp_in_w"] + p["mlp_in_b"], approximate=True)
        return x + h @ p["mlp_out_w"] + p["mlp_out_b"], None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _layer_norm(x, params["lnf_scale"].astype(jnp.float32),
                    params["lnf_bias"].astype(jnp.float32), eps)
    logp = jax.nn.log_softmax(x[:, :-1] @ wte.T, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    scored = jnp.ones_like(nll) if segments is None else \
        (segments[:, 1:] == segments[:, :-1]).astype(jnp.float32)
    return (nll * scored).sum(), scored.sum()


def step_loss(params, batch, model_config, chunk, put=None):
    """The loss ``engine.train_batch`` reports for ``batch`` (leaves
    [gas, B, S]) at ``params``.  ``chunk`` sequences at a time; ``put``
    places a host chunk on the devices (the engine's batch sharding)."""
    put = put or (lambda x: x)
    fn = jax.jit(_forward_sums, static_argnums=(3, 4))
    ids = np.asarray(batch["input_ids"])
    seg = batch.get("segment_ids")
    micro_means = []
    with jax.default_matmul_precision("highest"):
        for g in range(ids.shape[0]):
            total = count = 0.0
            for i in range(0, ids.shape[1], chunk):
                s, c = fn(params, put(ids[g, i:i + chunk]),
                          None if seg is None
                          else put(np.asarray(seg)[g, i:i + chunk]),
                          model_config["num_heads"],
                          model_config["layer_norm_eps"])
                total, count = total + float(s), count + float(c)
            micro_means.append(total / count)
    return float(np.mean(micro_means))
