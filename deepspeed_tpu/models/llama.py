"""Llama-2 / Llama-3-style decoder family, TPU-native: RMSNorm, rotary position
embeddings, grouped-query attention, SwiGLU MLP; scan-over-layers with stacked
params, Megatron-pattern TP specs.

Covers the BASELINE.md configs "Llama-2 13B ZeRO-3 + offload" and "Llama-2 7B
PP×ZeRO-1".  Architecture follows the public Llama papers; capability parity
target is the reference's HF-Llama support (module_inject/containers/llama.py).
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import Model, qdot, resolve_size
from deepspeed_tpu.ops.attention import causal_attention


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32          # < num_heads → grouped-query attention
    d_model: int = 4096
    d_mlp: int = 11008
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    #: InternLM variant (module_inject/containers/internlm.py capability):
    #: biased q/k/v/o projections on the otherwise-llama block
    attn_bias: bool = False
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


LLAMA_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=2, num_heads=4,
                 num_kv_heads=2, d_model=32, d_mlp=64),
    "7b": dict(num_layers=32, num_heads=32, num_kv_heads=32, d_model=4096,
               d_mlp=11008),
    "13b": dict(num_layers=40, num_heads=40, num_kv_heads=40, d_model=5120,
                d_mlp=13824),
    "70b": dict(num_layers=80, num_heads=64, num_kv_heads=8, d_model=8192,
                d_mlp=28672),
}


def init_params(config: LlamaConfig, rng) -> dict:
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    k = iter(jax.random.split(rng, 12))
    std = 0.02
    res_std = std / (2 * L) ** 0.5
    norm = partial(jax.random.normal, dtype=jnp.float32)
    blocks = {
        "attn_norm": jnp.ones((L, D)),
        "wq": norm(next(k), (L, D, H * hd)) * std,
        "wk": norm(next(k), (L, D, KV * hd)) * std,
        "wv": norm(next(k), (L, D, KV * hd)) * std,
        "wo": norm(next(k), (L, H * hd, D)) * res_std,
        "mlp_norm": jnp.ones((L, D)),
        "w_gate": norm(next(k), (L, D, M)) * std,
        "w_up": norm(next(k), (L, D, M)) * std,
        "w_down": norm(next(k), (L, M, D)) * res_std,
    }
    if config.attn_bias:
        blocks.update({"wq_b": jnp.zeros((L, H * hd)),
                       "wk_b": jnp.zeros((L, KV * hd)),
                       "wv_b": jnp.zeros((L, KV * hd)),
                       "wo_b": jnp.zeros((L, D))})
    return {
        "wte": norm(next(k), (V, D)) * std,
        "blocks": blocks,
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }


def numpy_init_params(config: LlamaConfig, seed: int = 0) -> dict:
    """Host-side init mirroring ``init_params``'s distributions with numpy
    (the offload tier's fast init — see models/gpt2.py numpy_init_params)."""
    import numpy as np
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    rng = np.random.default_rng(seed)
    std = 0.02
    res_std = std / (2 * L) ** 0.5

    def norm(shape, scale):
        return rng.standard_normal(shape, dtype=np.float32) * scale

    blocks = {
        "attn_norm": np.ones((L, D), np.float32),
        "wq": norm((L, D, H * hd), std),
        "wk": norm((L, D, KV * hd), std),
        "wv": norm((L, D, KV * hd), std),
        "wo": norm((L, H * hd, D), res_std),
        "mlp_norm": np.ones((L, D), np.float32),
        "w_gate": norm((L, D, M), std),
        "w_up": norm((L, D, M), std),
        "w_down": norm((L, M, D), res_std),
    }
    if config.attn_bias:
        blocks.update({"wq_b": np.zeros((L, H * hd), np.float32),
                       "wk_b": np.zeros((L, KV * hd), np.float32),
                       "wv_b": np.zeros((L, KV * hd), np.float32),
                       "wo_b": np.zeros((L, D), np.float32)})
    return {
        "wte": norm((V, D), std),
        "blocks": blocks,
        "final_norm": np.ones((D,), np.float32),
        "lm_head": norm((D, V), std),
    }


def logical_specs(config: LlamaConfig) -> dict:
    blocks = {
        "attn_norm": P(),
        "wq": P(None, None, "model"),
        "wk": P(None, None, "model"),
        "wv": P(None, None, "model"),
        "wo": P(None, "model", None),
        "mlp_norm": P(),
        "w_gate": P(None, None, "model"),
        "w_up": P(None, None, "model"),
        "w_down": P(None, "model", None),
    }
    if config.attn_bias:
        blocks.update({"wq_b": P(None, "model"), "wk_b": P(None, "model"),
                       "wv_b": P(None, "model"), "wo_b": P()})
    return {
        "wte": P("model", None),
        "blocks": blocks,
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope(x, theta: float, positions=None, interleaved: bool = False,
         first: int = 0):
    """Rotary embeddings on [B, S, H, hd].  ``interleaved=False`` pairs
    dim i with i+hd/2 (llama/NeoX split-half convention);
    ``interleaved=True`` pairs dims (2i, 2i+1) (the GPT-J rotate_every_two
    convention — same frequencies, different lane pairing, so converted
    checkpoints must match their family's layout).  ``positions``: [S]
    (shared across batch) or [B, S] (per-row, decode).  ``first``
    (interleaved only): lanes ``first..hd`` turn, at the frequencies of a
    head ``hd - first`` wide, and the lanes before them pass through —
    latent attention's position-free part, rotated in place."""
    if interleaved:
        return _rope_interleaved(x, theta, positions, first)
    assert not first, "rope: first= is the interleaved layout's"
    B, S, H, hd = x.shape
    if positions is None:
        positions = jnp.arange(S)
    freqs = theta ** (-jnp.arange(0, hd // 2) / (hd // 2))
    if positions.ndim == 1:
        angles = positions[:, None] * freqs[None, :]     # [S, hd/2]
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    else:
        angles = positions[:, :, None] * freqs[None, None, :]   # [B, S, hd/2]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def _rope_interleaved(x, theta, positions, first):
    """``x C + swap(x) Sn`` over whole heads: ``swap(x)[2i] = -x[2i+1]``,
    ``swap(x)[2i+1] = x[2i]``.  Term for term ``x1 cos - x2 sin`` and ``x1
    sin + x2 cos`` in float32, rounded once, with no stride along the head
    (``x[..., 0::2]`` is a gather by index before XLA sees it, and its
    transpose a scatter-add)."""
    S, hd = x.shape[1], x.shape[-1]
    assert 0 <= first < hd and (hd - first) % 2 == 0, (first, hd)
    if positions is None:
        positions = jnp.arange(S)
    c, s = interleaved_tables(positions, theta, hd - first, first)
    return _turn_pairs(x, c, s, first, False)


def interleaved_tables(positions, theta, rot, first=0, inv_freq=None):
    """``(C, Sn)`` float32 ``[(B,) S, 1, first + rot]`` of the interleaved
    rotary: ``C`` is 1 on the ``first`` lanes that pass and ``cos`` (each
    frequency twice) on the ``rot`` that turn, ``Sn`` 0 and ``sin``.
    ``inv_freq`` [rot / 2]: the frequencies, where they are not
    ``theta``'s own (a scaled context)."""
    freqs = theta ** (-jnp.arange(0, rot // 2) / (rot // 2)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None] * freqs                # [(B,) S, rot/2]
    lanes = [(0, 0)] * (angles.ndim - 1) + [(first, 0)]
    c = jnp.pad(jnp.repeat(jnp.cos(angles), 2, axis=-1), lanes,
                constant_values=1.0)
    s = jnp.pad(jnp.repeat(jnp.sin(angles), 2, axis=-1), lanes)
    return jnp.expand_dims(c, -2), jnp.expand_dims(s, -2)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _turn_pairs(x, c, s, first, back):
    """``x c + swap(x) s``; ``back``: with ``swap``'s transpose, the
    rotation by the opposite angle.  ``swap`` is a product with a signed
    permutation: one non-zero a column, so exact in ``x``'s own dtype on
    the MXU, and the multiply-add is the product's epilogue — one pass
    over ``x``.  (A -0.0 on a lane that passes comes back +0.0.)"""
    hd = x.shape[-1]
    turn = np.zeros((hd, hd), np.float32)
    even = np.arange(first, hd, 2)
    turn[even + 1, even], turn[even, even + 1] = -1.0, 1.0
    swapped = jnp.einsum(
        "bshd,de->bshe", x, jnp.asarray(turn.T if back else turn, x.dtype),
        # bfloat16 operands are whole as they are; float32 ones must not
        # be cut to bfloat16 on the way into the MXU
        precision=None if x.dtype == jnp.bfloat16 else lax.Precision.HIGHEST,
        preferred_element_type=x.dtype)
    return (x.astype(jnp.float32) * c
            + swapped.astype(jnp.float32) * s).astype(x.dtype)


def _turn_pairs_fwd(x, c, s, first, back):
    return _turn_pairs(x, c, s, first, back), (c, s)


def _turn_pairs_bwd(first, back, tables, g):
    # swap meets the cotangent in its own dtype, as it met x
    c, s = tables
    return _turn_pairs(g, c, s, first, not back), None, None


_turn_pairs.defvjp(_turn_pairs_fwd, _turn_pairs_bwd)


def _block_qkv(x, layer, config: LlamaConfig, positions=None, lora=None):
    """RMSNorm + QKV + rotary; x [B, S, D] -> q [B,S,H,hd], k/v [B,S,KV,hd]
    (kv heads NOT repeated — the caller decides, so caches stay compact).
    ``lora(name, h)`` adds per-row adapter deltas on the projection
    outputs BEFORE rope — rope is a position-dependent linear map on the
    projected vectors, so this is where the offline merge lands too
    (ISSUE 20)."""
    from deepspeed_tpu.models.serving import lora_add
    B, S, D = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    h = _rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    dt = h.dtype
    q = lora_add(qdot(h, layer["wq"]), lora, "wq", h)
    kk = lora_add(qdot(h, layer["wk"]), lora, "wk", h)
    v = lora_add(qdot(h, layer["wv"]), lora, "wv", h)
    if config.attn_bias:
        q = q + layer["wq_b"].astype(dt)
        kk = kk + layer["wk_b"].astype(dt)
        v = v + layer["wv_b"].astype(dt)
    q = q.reshape(B, S, H, hd)
    kk = kk.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    q = rope(q, config.rope_theta, positions)
    kk = rope(kk, config.rope_theta, positions)
    return q, kk, v


def _block_finish(x, attn, layer, config: LlamaConfig, lora=None):
    from deepspeed_tpu.models.serving import lora_add
    dt = x.dtype
    attn_out = lora_add(qdot(attn, layer["wo"]), lora, "wo", attn)
    if config.attn_bias:
        attn_out = attn_out + layer["wo_b"].astype(dt)
    x = x + attn_out
    h = _rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
    gated = jax.nn.silu(lora_add(qdot(h, layer["w_gate"]), lora,
                                 "w_gate", h)) \
        * lora_add(qdot(h, layer["w_up"]), lora, "w_up", h)
    x = x + lora_add(qdot(gated, layer["w_down"]), lora, "w_down", gated)
    return x


def _block(x, layer, config: LlamaConfig, rng=None, segment_ids=None):
    B, S, D = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    q, kk, v = _block_qkv(x, layer, config)
    # kv heads stay compact: the attention dispatch attends GQA natively
    # (from-scratch flash kernel) or repeats in the fallback paths
    attn = causal_attention(q, kk, v, impl=config.attention_impl,
                            segment_ids=segment_ids)
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    return _block_finish(x, attn.reshape(B, S, H * hd), layer, config)


def forward(params, batch, config: LlamaConfig, rng=None):
    tokens = batch["input_ids"]
    dtype = jnp.dtype(config.dtype)
    x = params["wte"].astype(dtype)[tokens]
    # stream-inside-remat (see models/model.py maybe_stream): param-offload
    # transfers happen inside the remat boundary
    seg = batch.get("segment_ids") if isinstance(batch, dict) else None

    def block_fn(x, layer):
        from deepspeed_tpu.models.model import maybe_stream
        return _block(x, maybe_stream(layer), config, rng, seg)
    if config.remat:
        from deepspeed_tpu.models.model import remat_policy
        block_fn = jax.checkpoint(
            block_fn, policy=remat_policy(config.remat_policy))

    # layer scan with random-LTD + progressive-layer-drop hooks (see
    # models/model.py scan_blocks); packed batches skip LTD (a token
    # subset would misalign the closed-over segment ids)
    from deepspeed_tpu.models.model import scan_blocks
    x = scan_blocks(block_fn, x, params["blocks"], rng, batch,
                    config.num_layers, allow_ltd=seg is None)
    x = _rms_norm(x, params["final_norm"], config.rms_norm_eps)
    return x @ params["lm_head"].astype(dtype)


# --------------------------------------------------------------------- decode
def _serving_fns(config: LlamaConfig):
    """KV-cache serving via the shared rotary-GQA scaffold
    (models/serving.py) — llama contributes its QKV projection and dense
    SwiGLU finish."""
    from deepspeed_tpu.models import serving

    def embed_fn(params, tokens):
        return params["wte"].astype(jnp.dtype(config.dtype))[tokens]

    def qkv_fn(x, layer, positions, lora=None):
        return _block_qkv(x, layer, config, positions, lora=lora)

    def finish_fn(x, attn_flat, layer, lora=None):
        return _block_finish(x, attn_flat, layer, config, lora=lora)

    def head_fn(params, x):
        return head(params, x, config)

    # fused per-layer megakernel wiring (ISSUE 12): RMSNorm + split QKV
    # + full rotary + GQA decode attention + SwiGLU in one Pallas call
    from deepspeed_tpu.ops.pallas.fused_decode import FusedLayerSpec
    fused_spec = FusedLayerSpec(
        num_heads=config.num_heads, num_kv_heads=config.num_kv_heads,
        head_dim=config.head_dim, d_model=config.d_model,
        norm="rms", eps=config.rms_norm_eps, qkv="split",
        qkv_bias=config.attn_bias, out_bias=config.attn_bias,
        mlp="swiglu", mlp_bias=False, rotary_dims=config.head_dim,
        rope_theta=config.rope_theta)

    def fused_weights(layer):
        cw = {"n1_s": layer["attn_norm"], "wq": layer["wq"],
              "wk": layer["wk"], "wv": layer["wv"], "wo": layer["wo"],
              "n2_s": layer["mlp_norm"], "w_gate": layer["w_gate"],
              "w_up": layer["w_up"], "w_down": layer["w_down"]}
        if config.attn_bias:
            cw.update(bq=layer["wq_b"], bk=layer["wk_b"],
                      bv=layer["wv_b"], bo=layer["wo_b"])
        return cw

    def init_cache_fn(bs, max_len, dtype=None):
        return serving.init_cache(config.num_layers, config.num_kv_heads,
                                  config.head_dim, bs, max_len, dtype,
                                  config.dtype)

    def prefill_fn(p, b, c, lora=None):
        return serving.prefill(
            p, b, c, embed_fn=embed_fn, qkv_fn=qkv_fn, finish_fn=finish_fn,
            head_fn=head_fn, num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads,
            attention_impl=config.attention_impl, lora=lora)

    def decode_fn(p, t, c, l, lora=None):
        return serving.decode_step(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads,
            fused_spec=fused_spec, fused_weights_fn=fused_weights,
            lora=lora)

    def verify_fn(p, t, c, l, lora=None):
        return serving.verify_window(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads,
            fused_spec=fused_spec, fused_weights_fn=fused_weights,
            lora=lora)

    return init_cache_fn, prefill_fn, decode_fn, verify_fn


def count_params(config: LlamaConfig) -> int:
    D, V, L, M = (config.d_model, config.vocab_size, config.num_layers,
                  config.d_mlp)
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    per_layer = 2 * D + D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * M
    return V * D + L * per_layer + D + D * V


def embed(params, batch, config: LlamaConfig):
    dtype = jnp.dtype(config.dtype)
    return params["wte"].astype(dtype)[batch["input_ids"]]


def head(params, x, config: LlamaConfig):
    x = _rms_norm(x, params["final_norm"], config.rms_norm_eps)
    return qdot(x, params["lm_head"])


def llama_model(size: str = "7b", **overrides) -> Model:
    cfg_kwargs = resolve_size(LLAMA_SIZES, size, "llama")
    cfg_kwargs.update(overrides)
    config = LlamaConfig(**cfg_kwargs)
    n_params = count_params(config)
    return Model(
        config=config,
        init_fn=partial(init_params, config),
        numpy_init_fn=partial(numpy_init_params, config),
        apply_fn=lambda p, b, rng=None: forward(p, b, config, rng),
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * n_params,
        meta={"name": f"llama-{size}", "n_params": n_params,
              "supports_random_ltd": True, "supports_pld": True,
              "lora_serving": True,
              # wte grads come solely from input_ids lookups (untied
              # lm_head): eligible for the sparse_gradients exchange
              "sparse_grad_params": {"wte": "input_ids"}},
        embed_fn=lambda p, b: embed(p, b, config),
        block_fn=lambda lp, x: _block(x, lp, config),
        head_fn=lambda p, x: head(p, x, config),
        **dict(zip(("init_cache_fn", "prefill_fn", "decode_fn",
                    "verify_fn"),
                   _serving_fns(config))),
    )
