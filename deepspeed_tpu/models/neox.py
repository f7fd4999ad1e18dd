"""GPT-NeoX-style decoder (Pythia / NeoX-20B family): LayerNorm with
biases, fused-QKV attention with PARTIAL rotary embeddings
(``rotary_pct`` of each head rotates, the rest passes through), biased
GELU MLP, and the parallel attention+MLP residual
(``use_parallel_residual``).

Reference capability: the gptneox kernel-injection container
(deepspeed/module_inject/containers/gptneox.py); here the architecture is
a native model so every engine feature (ZeRO, TP specs, offload,
compression) applies unchanged after ``neox_from_hf`` conversion.
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import Model, qdot, resolve_size
from deepspeed_tpu.models.llama import rope
from deepspeed_tpu.ops.attention import causal_attention


@dataclass(frozen=True)
class NeoXConfig:
    vocab_size: int = 50432
    max_seq_len: int = 2048
    num_layers: int = 6
    num_heads: int = 8
    d_model: int = 512
    rotary_pct: float = 0.25
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    #: HF GPT-NeoX default hidden_act="gelu" is the EXACT erf GELU;
    #: gelu_new/gelu_fast variants map to the tanh approximation
    gelu_approximate: bool = False
    #: GPT-J variants (module_inject/containers/gptj.py capability): the
    #: rotate-every-two rotary pairing and the biased untied lm_head.
    #: GPT-J's single shared block LayerNorm converts as ln2 := ln1.
    rotary_interleaved: bool = False
    head_bias: bool = False
    dtype: str = "float32"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def d_mlp(self) -> int:
        return 4 * self.d_model

    @property
    def rotary_ndims(self) -> int:
        return int(self.head_dim * self.rotary_pct)


NEOX_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
                 d_model=32),
    "pythia-160m": dict(vocab_size=50304, max_seq_len=2048, num_layers=12,
                        num_heads=12, d_model=768),
    "20b": dict(vocab_size=50432, max_seq_len=2048, num_layers=44,
                num_heads=64, d_model=6144, rotary_pct=0.25),
}


def init_params(config: NeoXConfig, rng) -> dict:
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    k = iter(jax.random.split(rng, 10))
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    return {
        "wte": norm(next(k), (V, D)) * std,
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
        "embed_out": norm(next(k), (D, V)) * std,
        **({"embed_out_b": jnp.zeros((V,))} if config.head_bias else {}),
    }


def logical_specs(config: NeoXConfig) -> dict:
    head = {"embed_out": P(None, "model")}
    if config.head_bias:
        head["embed_out_b"] = P("model")
    return {
        "wte": P("model", None),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "qkv_w": P(None, None, "model"), "qkv_b": P(None, "model"),
            "dense_w": P(None, "model", None), "dense_b": P(),
            "mlp_in_w": P(None, None, "model"), "mlp_in_b": P(None, "model"),
            "mlp_out_w": P(None, "model", None), "mlp_out_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
        **head,
    }


def _ln(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _partial_rope(x, config: NeoXConfig, positions=None):
    """Rotate the first ``rotary_ndims`` of each head, pass the rest."""
    rot = config.rotary_ndims
    il = config.rotary_interleaved
    if rot >= x.shape[-1]:
        return rope(x, config.rope_theta, positions, interleaved=il)
    xr = rope(x[..., :rot], config.rope_theta, positions, interleaved=il)
    return jnp.concatenate([xr, x[..., rot:]], axis=-1)


def _block_qkv(x, layer, config: NeoXConfig, positions=None):
    """LN1 + fused QKV (head-major [q|k|v] packing) + partial rotary."""
    B, S, D = x.shape
    H, hd = config.num_heads, config.head_dim
    dt = x.dtype
    h1 = _ln(x, layer["ln1_scale"], layer["ln1_bias"],
             config.layer_norm_eps)
    qkv = qdot(h1, layer["qkv_w"]) + layer["qkv_b"].astype(dt)
    q, kk, v = jnp.split(qkv.reshape(B, S, H, 3 * hd), 3, axis=-1)
    q = _partial_rope(q, config, positions)
    kk = _partial_rope(kk, config, positions)
    return q, kk, v


def _block_finish(x, attn_flat, layer, config: NeoXConfig):
    """Output projection + MLP with the parallel/serial residual form."""
    dt = x.dtype
    attn_out = (qdot(attn_flat, layer["dense_w"])
                + layer["dense_b"].astype(dt))
    h2_in = x if config.use_parallel_residual else x + attn_out
    h2 = _ln(h2_in, layer["ln2_scale"], layer["ln2_bias"],
             config.layer_norm_eps)
    m = jax.nn.gelu(qdot(h2, layer["mlp_in_w"])
                    + layer["mlp_in_b"].astype(dt),
                    approximate=config.gelu_approximate)
    mlp_out = qdot(m, layer["mlp_out_w"]) + layer["mlp_out_b"].astype(dt)
    if config.use_parallel_residual:
        return x + attn_out + mlp_out       # gpt-j style parallel residual
    return h2_in + mlp_out


def _block(x, layer, config: NeoXConfig, rng=None, segment_ids=None):
    B, S, D = x.shape
    q, kk, v = _block_qkv(x, layer, config)
    attn = causal_attention(q, kk, v, impl=config.attention_impl,
                            segment_ids=segment_ids)
    return _block_finish(x, attn.reshape(B, S, D), layer, config)


def forward(params, batch, config: NeoXConfig, rng=None):
    tokens = batch["input_ids"]
    dtype = jnp.dtype(config.dtype)
    x = params["wte"].astype(dtype)[tokens]
    seg = batch.get("segment_ids") if isinstance(batch, dict) else None

    def block_fn(x, layer):
        from deepspeed_tpu.models.model import maybe_stream
        return _block(x, maybe_stream(layer), config, rng, seg)
    if config.remat:
        from deepspeed_tpu.models.model import remat_policy
        block_fn = jax.checkpoint(
            block_fn, policy=remat_policy(config.remat_policy))
    from deepspeed_tpu.models.model import scan_blocks
    x = scan_blocks(block_fn, x, params["blocks"], rng, batch,
                    config.num_layers, allow_ltd=seg is None)
    x = _ln(x, params["lnf_scale"], params["lnf_bias"],
            config.layer_norm_eps)
    logits = x @ params["embed_out"].astype(dtype)
    if config.head_bias:
        logits = logits + params["embed_out_b"].astype(dtype)
    return logits


def count_params(config: NeoXConfig) -> int:
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    per_layer = 4 * D + 3 * D * D + 3 * D + D * D + D + D * M + M + M * D + D
    return (V * D + L * per_layer + 2 * D + D * V
            + (V if config.head_bias else 0))


def _serving_fns(config: NeoXConfig):
    """KV-cache serving via the shared rotary scaffold (models/serving.py):
    NeoX contributes its fused-QKV partial-rotary projection and the
    parallel-residual finish."""
    from deepspeed_tpu.models import serving

    def embed_fn(params, tokens):
        return params["wte"].astype(jnp.dtype(config.dtype))[tokens]

    def qkv_fn(x, layer, positions):
        return _block_qkv(x, layer, config, positions)

    def finish_fn(x, attn_flat, layer):
        return _block_finish(x, attn_flat, layer, config)

    def head_fn(params, x):
        x = _ln(x, params["lnf_scale"], params["lnf_bias"],
                config.layer_norm_eps)
        logits = x @ params["embed_out"].astype(jnp.dtype(config.dtype))
        if config.head_bias:
            logits = logits + params["embed_out_b"].astype(
                jnp.dtype(config.dtype))
        return logits

    # fused per-layer megakernel wiring (ISSUE 12): head-major fused QKV
    # + partial rotary + parallel/serial residual in one Pallas call.
    # GPT-J-converted checkpoints (rotary_interleaved) keep the unfused
    # path — the spec reports itself unsupported
    from deepspeed_tpu.ops.pallas.fused_decode import FusedLayerSpec
    fused_spec = FusedLayerSpec(
        num_heads=config.num_heads, num_kv_heads=config.num_heads,
        head_dim=config.head_dim, d_model=config.d_model,
        norm="ln", eps=config.layer_norm_eps, qkv="headmajor",
        qkv_bias=True, out_bias=True,
        mlp="gelu_tanh" if config.gelu_approximate else "gelu_exact",
        mlp_bias=True,
        residual="parallel" if config.use_parallel_residual else "serial",
        rotary_dims=config.rotary_ndims, rope_theta=config.rope_theta,
        rotary_interleaved=config.rotary_interleaved)

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
            num_kv_heads=config.num_heads,
            attention_impl=config.attention_impl)

    def decode_fn(p, t, c, l):
        return serving.decode_step(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads,
            fused_spec=fused_spec, fused_weights_fn=fused_weights)

    def verify_fn(p, t, c, l):
        return serving.verify_window(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads,
            fused_spec=fused_spec, fused_weights_fn=fused_weights)

    return init_cache_fn, prefill_fn, decode_fn, verify_fn


def neox_model(size: str = "tiny", **overrides) -> Model:
    cfg_kwargs = resolve_size(NEOX_SIZES, size, "neox")
    cfg_kwargs.update(overrides)
    config = NeoXConfig(**cfg_kwargs)
    n_params = count_params(config)
    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=lambda p, b, rng=None: forward(p, b, config, rng),
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * n_params,
        meta={"name": f"neox-{size}", "n_params": n_params,
              "supports_random_ltd": True, "supports_pld": True,
              "sparse_grad_params": {"wte": "input_ids"}},
        **dict(zip(("init_cache_fn", "prefill_fn", "decode_fn",
                    "verify_fn"),
                   _serving_fns(config))),
    )
