"""BLOOM-style decoder: ALiBi positional attention (no position
embeddings), embedding LayerNorm, biased GELU MLP, tied head.

Reference capability: the bloom kernel-injection container
(deepspeed/module_inject/containers/bloom.py); converted checkpoints run
every engine feature natively.
"""
from dataclasses import dataclass
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import Model, qdot, resolve_size
from deepspeed_tpu.models.neox import _ln


@dataclass(frozen=True)
class BloomConfig:
    vocab_size: int = 250880
    max_seq_len: int = 2048
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 64
    layer_norm_eps: float = 1e-5
    dtype: str = "float32"
    remat: bool = False
    remat_policy: str = "nothing"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def d_mlp(self) -> int:
        return 4 * self.d_model


BLOOM_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
                 d_model=32),
    "560m": dict(vocab_size=250880, max_seq_len=2048, num_layers=24,
                 num_heads=16, d_model=1024),
}


def alibi_slopes(num_heads: int) -> np.ndarray:
    """ALiBi per-head slopes (Press et al.; matches HF's
    build_alibi_tensor)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    closest = 2 ** int(np.floor(np.log2(num_heads)))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: num_heads - closest]
    return np.concatenate([base, extra])


def init_params(config: BloomConfig, rng) -> dict:
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    k = iter(jax.random.split(rng, 8))
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    return {
        "wte": norm(next(k), (V, D)) * std,
        "emb_ln_scale": jnp.ones((D,)), "emb_ln_bias": jnp.zeros((D,)),
        "blocks": {
            "ln1_scale": jnp.ones((L, D)), "ln1_bias": jnp.zeros((L, D)),
            "ln2_scale": jnp.ones((L, D)), "ln2_bias": jnp.zeros((L, D)),
            "qkv_w": norm(next(k), (L, D, 3 * D)) * std,
            "qkv_b": jnp.zeros((L, 3 * D)),
            "dense_w": norm(next(k), (L, D, D)) * std / (2 * L) ** 0.5,
            "dense_b": jnp.zeros((L, D)),
            "mlp_in_w": norm(next(k), (L, D, M)) * std,
            "mlp_in_b": jnp.zeros((L, M)),
            "mlp_out_w": norm(next(k), (L, M, D)) * std / (2 * L) ** 0.5,
            "mlp_out_b": jnp.zeros((L, D)),
        },
        "lnf_scale": jnp.ones((D,)), "lnf_bias": jnp.zeros((D,)),
    }


def logical_specs(config: BloomConfig) -> dict:
    return {
        "wte": P("model", None),
        "emb_ln_scale": P(), "emb_ln_bias": P(),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "qkv_w": P(None, None, "model"), "qkv_b": P(None, "model"),
            "dense_w": P(None, "model", None), "dense_b": P(),
            "mlp_in_w": P(None, None, "model"), "mlp_in_b": P(None, "model"),
            "mlp_out_w": P(None, "model", None), "mlp_out_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
    }


def _alibi_attention(q, k, v, slopes, segment_ids=None):
    """Causal attention with the ALiBi additive bias
    ``slopes[h] * key_position`` (row-shift-invariant form HF uses);
    ``segment_ids`` restricts attention within packed segments."""
    B, S, H, hd = q.shape
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    bias = slopes[None, :, None, None] * jnp.arange(S)[None, None, None, :]
    scores = scores + bias
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))[None, None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None]
                       == segment_ids[:, None, None, :])
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_qkv(x, layer, config: BloomConfig, positions=None):
    """LN1 + fused QKV (head-major [q|k|v] packing); no positional
    transform — ALiBi biases scores, not projections."""
    B, S, D = x.shape
    H, hd = config.num_heads, config.head_dim
    dt = x.dtype
    h = _ln(x, layer["ln1_scale"], layer["ln1_bias"], config.layer_norm_eps)
    qkv = qdot(h, layer["qkv_w"]) + layer["qkv_b"].astype(dt)
    return jnp.split(qkv.reshape(B, S, H, 3 * hd), 3, axis=-1)


def _block_finish(x, attn_flat, layer, config: BloomConfig):
    dt = x.dtype
    x = x + (qdot(attn_flat, layer["dense_w"])
             + layer["dense_b"].astype(dt))
    h = _ln(x, layer["ln2_scale"], layer["ln2_bias"], config.layer_norm_eps)
    m = jax.nn.gelu(qdot(h, layer["mlp_in_w"])
                    + layer["mlp_in_b"].astype(dt), approximate=True)
    return x + qdot(m, layer["mlp_out_w"]) + layer["mlp_out_b"].astype(dt)


def _block(x, layer, config: BloomConfig, slopes, rng=None,
           segment_ids=None):
    B, S, D = x.shape
    q, kk, v = _block_qkv(x, layer, config)
    attn = _alibi_attention(q, kk, v, slopes, segment_ids)
    return _block_finish(x, attn.reshape(B, S, D), layer, config)


def forward(params, batch, config: BloomConfig, rng=None):
    tokens = batch["input_ids"]
    dtype = jnp.dtype(config.dtype)
    slopes = jnp.asarray(alibi_slopes(config.num_heads), jnp.float32)
    x = params["wte"].astype(dtype)[tokens]
    x = _ln(x, params["emb_ln_scale"], params["emb_ln_bias"],
            config.layer_norm_eps)

    seg = batch.get("segment_ids") if isinstance(batch, dict) else None

    def block_fn(x, layer):
        from deepspeed_tpu.models.model import maybe_stream
        return _block(x, maybe_stream(layer), config, slopes, rng, seg)
    if config.remat:
        from deepspeed_tpu.models.model import remat_policy
        block_fn = jax.checkpoint(
            block_fn, policy=remat_policy(config.remat_policy))
    from deepspeed_tpu.models.model import scan_blocks
    x = scan_blocks(block_fn, x, params["blocks"], rng, batch,
                    config.num_layers, allow_ltd=seg is None)
    x = _ln(x, params["lnf_scale"], params["lnf_bias"],
            config.layer_norm_eps)
    # tied head (BLOOM always ties lm_head to the word embeddings)
    return x @ params["wte"].astype(dtype).T


def count_params(config: BloomConfig) -> int:
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    per_layer = 4 * D + 3 * D * D + 3 * D + D * D + D + D * M + M + M * D + D
    return V * D + 2 * D + L * per_layer + 2 * D


def _serving_fns(config: BloomConfig):
    """KV-cache serving through the shared scaffold (models/serving.py):
    BLOOM contributes its fused-QKV projection, the post-LN finish, and
    the ALiBi bias — biased causal attention at prefill, the decode
    kernel's ``alibi_slopes`` form per token (reference capability:
    containers/bloom.py + the ds_softmax_context ALiBi path)."""
    from deepspeed_tpu.models import serving

    slopes = jnp.asarray(alibi_slopes(config.num_heads), jnp.float32)
    dt = jnp.dtype(config.dtype)

    def embed_fn(params, tokens):
        x = params["wte"].astype(dt)[tokens]
        return _ln(x, params["emb_ln_scale"], params["emb_ln_bias"],
                   config.layer_norm_eps)

    def qkv_fn(x, layer, positions):
        return _block_qkv(x, layer, config, positions)

    def finish_fn(x, attn_flat, layer):
        return _block_finish(x, attn_flat, layer, config)

    def head_fn(params, x):
        x = _ln(x, params["lnf_scale"], params["lnf_bias"],
                config.layer_norm_eps)
        return x @ params["wte"].astype(dt).T

    # fused per-layer megakernel wiring (ISSUE 12): head-major fused QKV
    # + ALiBi decode attention + GELU MLP in one Pallas call
    from deepspeed_tpu.ops.pallas.fused_decode import FusedLayerSpec
    fused_spec = FusedLayerSpec(
        num_heads=config.num_heads, num_kv_heads=config.num_heads,
        head_dim=config.head_dim, d_model=config.d_model,
        norm="ln", eps=config.layer_norm_eps, qkv="headmajor",
        qkv_bias=True, out_bias=True, mlp="gelu_tanh", mlp_bias=True,
        alibi=True)

    def fused_weights(layer):
        return {"n1_s": layer["ln1_scale"], "n1_b": layer["ln1_bias"],
                "wqkv": layer["qkv_w"], "bqkv": layer["qkv_b"],
                "wo": layer["dense_w"], "bo": layer["dense_b"],
                "n2_s": layer["ln2_scale"], "n2_b": layer["ln2_bias"],
                "w_in": layer["mlp_in_w"], "b_in": layer["mlp_in_b"],
                "w_out": layer["mlp_out_w"], "b_out": layer["mlp_out_b"]}

    def init_cache_fn(bs, max_len, dtype=None):
        return serving.init_cache(config.num_layers, config.num_heads,
                                  config.head_dim, bs, max_len, dtype,
                                  config.dtype)

    def prefill_fn(p, b, c):
        return serving.prefill(
            p, b, c, embed_fn=embed_fn, qkv_fn=qkv_fn, finish_fn=finish_fn,
            head_fn=head_fn, num_heads=config.num_heads,
            num_kv_heads=config.num_heads, attention_impl="xla",
            attn_fn=lambda q, k, v: _alibi_attention(q, k, v, slopes))

    def decode_fn(p, t, c, l):
        return serving.decode_step(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads, alibi_slopes=slopes,
            fused_spec=fused_spec, fused_weights_fn=fused_weights)

    def verify_fn(p, t, c, l):
        return serving.verify_window(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads, alibi_slopes=slopes,
            fused_spec=fused_spec, fused_weights_fn=fused_weights)

    return init_cache_fn, prefill_fn, decode_fn, verify_fn


def bloom_model(size: str = "tiny", **overrides) -> Model:
    cfg_kwargs = resolve_size(BLOOM_SIZES, size, "bloom")
    cfg_kwargs.update(overrides)
    config = BloomConfig(**cfg_kwargs)
    n_params = count_params(config)
    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=lambda p, b, rng=None: forward(p, b, config, rng),
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * n_params,
        meta={"name": f"bloom-{size}", "n_params": n_params,
              "supports_random_ltd": True, "supports_pld": True},
        **dict(zip(("init_cache_fn", "prefill_fn", "decode_fn",
                    "verify_fn"),
                   _serving_fns(config))),
    )
