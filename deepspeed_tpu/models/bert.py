"""BERT family (encoder + masked-LM head), TPU-native.

Capability parity target: the reference's flagship kernel benchmark is
BERT-Large pretraining (docs/_posts/2020-05-28-fastest-bert-training.md:36,
csrc/transformer/ fused encoder kernels + the bert-pretraining tutorial).
Same design as models/gpt2.py: pure params pytree, one ``lax.scan`` over a
stacked layer dimension, Megatron-pattern TP specs, bf16-ready, remat
policies; post-LN residuals and learned position/type embeddings per the
BERT paper.  The MLM objective trains on ``labels`` (-100 = unmasked,
ignored) — the reference tutorial's NSP head is deliberately dropped
(RoBERTa-era practice; parity is the pretraining throughput path).
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import Model, maybe_stream, scan_blocks, resolve_size
from deepspeed_tpu.ops.attention import bidirectional_attention


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_seq_len: int = 512
    type_vocab_size: int = 2
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    layer_norm_eps: float = 1e-12
    gelu_approximate: bool = True   # False = erf gelu (HF BERT default)
    dtype: str = "float32"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    @property
    def d_mlp(self) -> int:
        return 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


BERT_SIZES = {
    "base": dict(num_layers=12, num_heads=12, d_model=768),
    "large": dict(num_layers=24, num_heads=16, d_model=1024),
}


def init_params(config: BertConfig, rng) -> dict:
    D, V, S, L, M = (config.d_model, config.vocab_size, config.max_seq_len,
                     config.num_layers, config.d_mlp)
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)

    def stack(key, shape):
        return norm(key, (L,) + shape) * std

    return {
        "wte": norm(next(k), (V, D)) * std,
        "wpe": norm(next(k), (S, D)) * std,
        "wtype": norm(next(k), (config.type_vocab_size, D)) * std,
        "emb_ln_scale": jnp.ones((D,)), "emb_ln_bias": jnp.zeros((D,)),
        "blocks": {
            "qkv_w": stack(next(k), (D, 3 * D)),
            "qkv_b": jnp.zeros((L, 3 * D)),
            "proj_w": stack(next(k), (D, D)),
            "proj_b": jnp.zeros((L, D)),
            "ln1_scale": jnp.ones((L, D)), "ln1_bias": jnp.zeros((L, D)),
            "mlp_in_w": stack(next(k), (D, M)),
            "mlp_in_b": jnp.zeros((L, M)),
            "mlp_out_w": stack(next(k), (M, D)),
            "mlp_out_b": jnp.zeros((L, D)),
            "ln2_scale": jnp.ones((L, D)), "ln2_bias": jnp.zeros((L, D)),
        },
        # MLM head: transform + LN + decoder tied to wte + output bias
        "mlm_dense_w": norm(next(k), (D, D)) * std,
        "mlm_dense_b": jnp.zeros((D,)),
        "mlm_ln_scale": jnp.ones((D,)), "mlm_ln_bias": jnp.zeros((D,)),
        "mlm_bias": jnp.zeros((V,)),
    }


def logical_specs(config: BertConfig) -> dict:
    """Megatron-pattern TP over the ``model`` axis (column-parallel QKV /
    MLP-in, row-parallel proj / MLP-out)."""
    return {
        "wte": P("model", None),
        "wpe": P(), "wtype": P(),
        "emb_ln_scale": P(), "emb_ln_bias": P(),
        "blocks": {
            "qkv_w": P(None, None, "model"),
            "qkv_b": P(None, "model"),
            "proj_w": P(None, "model", None),
            "proj_b": P(),
            "ln1_scale": P(), "ln1_bias": P(),
            "mlp_in_w": P(None, None, "model"),
            "mlp_in_b": P(None, "model"),
            "mlp_out_w": P(None, "model", None),
            "mlp_out_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
        },
        "mlm_dense_w": P(), "mlm_dense_b": P(),
        "mlm_ln_scale": P(), "mlm_ln_bias": P(),
        "mlm_bias": P("model"),
    }


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _block(x, layer, pad_mask, config: BertConfig):
    """Post-LN encoder block: x [B, S, D]."""
    B, S, D = x.shape
    H, hd = config.num_heads, config.head_dim
    qkv = x @ layer["qkv_w"].astype(x.dtype) + layer["qkv_b"].astype(x.dtype)
    q, kk, v = jnp.split(qkv, 3, axis=-1)
    attn = bidirectional_attention(
        q.reshape(B, S, H, hd), kk.reshape(B, S, H, hd),
        v.reshape(B, S, H, hd), pad_mask=pad_mask,
        impl=config.attention_impl)
    attn = attn.reshape(B, S, D)
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    x = _layer_norm(
        x + attn @ layer["proj_w"].astype(x.dtype)
        + layer["proj_b"].astype(x.dtype),
        layer["ln1_scale"], layer["ln1_bias"], config.layer_norm_eps)
    h = x @ layer["mlp_in_w"].astype(x.dtype) + layer["mlp_in_b"].astype(x.dtype)
    h = jax.nn.gelu(h, approximate=config.gelu_approximate)
    return _layer_norm(
        x + h @ layer["mlp_out_w"].astype(x.dtype)
        + layer["mlp_out_b"].astype(x.dtype),
        layer["ln2_scale"], layer["ln2_bias"], config.layer_norm_eps)


def forward(params, batch, config: BertConfig, rng=None):
    """input_ids [B, S] (+ optional attention_mask / token_type_ids)
    -> MLM logits [B, S, V]."""
    tokens = batch["input_ids"]
    B, S = tokens.shape
    dtype = jnp.dtype(config.dtype)
    pad_mask = batch.get("attention_mask")
    types = batch.get("token_type_ids")
    x = (params["wte"].astype(dtype)[tokens]
         + params["wpe"].astype(dtype)[:S]
         + (params["wtype"].astype(dtype)[types] if types is not None
            else params["wtype"].astype(dtype)[0]))
    x = _layer_norm(x, params["emb_ln_scale"], params["emb_ln_bias"],
                    config.layer_norm_eps)

    def block_fn(x, layer):
        return _block(x, maybe_stream(layer), pad_mask, config)
    if config.remat:
        from deepspeed_tpu.models.model import remat_policy
        block_fn = jax.checkpoint(block_fn,
                                  policy=remat_policy(config.remat_policy))
    # LTD token-gather would misalign the closed-over pad_mask rows
    x = scan_blocks(block_fn, x, params["blocks"], rng, batch,
                    config.num_layers, allow_ltd=pad_mask is None)
    return head(params, x, config)


def head(params, x, config: BertConfig):
    dtype = jnp.dtype(config.dtype)
    h = x @ params["mlm_dense_w"].astype(dtype) + params["mlm_dense_b"].astype(dtype)
    h = jax.nn.gelu(h, approximate=config.gelu_approximate)
    h = _layer_norm(h, params["mlm_ln_scale"], params["mlm_ln_bias"],
                    config.layer_norm_eps)
    return (h @ params["wte"].astype(dtype).T
            + params["mlm_bias"].astype(dtype))


def mlm_loss(apply_fn):
    """Masked-LM objective: mean cross-entropy over positions with
    ``labels != -100`` (falls back to all positions without labels —
    matches the causal models' smoke-test usage)."""
    import optax

    def loss_fn(params, batch, rng=None):
        logits = apply_fn(params, batch, rng)
        labels = batch.get("labels")
        if labels is None:
            labels, m = batch["input_ids"], None
        else:
            m = (labels != -100)
            labels = jnp.where(m, labels, 0)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels)
        if m is None:
            return losses.mean()
        m = m.astype(jnp.float32)
        return (losses * m).sum() / jnp.maximum(m.sum(), 1.0)

    return loss_fn


def count_params(config: BertConfig) -> int:
    D, V, S, L, M = (config.d_model, config.vocab_size, config.max_seq_len,
                     config.num_layers, config.d_mlp)
    per_layer = 3 * D * D + 3 * D + D * D + D + 2 * D * M + M + D + 4 * D
    head_p = D * D + D + 2 * D + V
    return (V * D + S * D + config.type_vocab_size * D + 2 * D
            + L * per_layer + head_p)


def bert_model(size: str = "base", **overrides) -> Model:
    cfg_kwargs = resolve_size(BERT_SIZES, size, "bert")
    cfg_kwargs.update(overrides)
    config = BertConfig(**cfg_kwargs)
    n_params = count_params(config)
    apply_fn = lambda p, b, rng=None: forward(p, b, config, rng)
    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=apply_fn,
        loss_fn=mlm_loss(apply_fn),
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * n_params,
        meta={"name": f"bert-{size}", "n_params": n_params,
              "supports_random_ltd": True, "supports_pld": True},
    )
