"""GPT-2 family, TPU-native: pure-functional params pytree, ``lax.scan`` over a
stacked layer dimension (one compiled layer body, MXU-friendly static shapes),
bf16-ready, with tensor-parallel logical specs on the Megatron pattern
(column-parallel QKV/MLP-in, row-parallel proj/MLP-out).

This is the framework's flagship dense LM for the BASELINE.md configs
(GPT-2 125M / 1.3B).  Capability parity target: the models DeepSpeed's examples
train via Megatron-DeepSpeed; architecture follows the public GPT-2 paper, not
the reference's code.
"""
from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import (Head, Model, maybe_stream, qdot,
                                        remat_policy, resolve_size)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_EMBED, SCOPE_HEAD_LOSS, SCOPE_MLP)


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    dtype: str = "float32"          # compute dtype; master params are fp32
    remat: bool = False             # activation checkpointing per layer
    remat_policy: str = "nothing"   # nothing | save_attn | dots | offload_attn
    attention_impl: str = "auto"    # auto | xla | flash (pallas)
    activation: str = "gelu"        # gelu (tanh approx) | gelu_exact (erf) | relu
    mlp_dim: int = 0                # 0 = the GPT-2 default 4*d_model

    @property
    def d_mlp(self) -> int:
        return self.mlp_dim or 4 * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


# presets matching the BASELINE.md configs
GPT2_SIZES = {
    "125m": dict(num_layers=12, num_heads=12, d_model=768),
    "350m": dict(num_layers=24, num_heads=16, d_model=1024),
    "760m": dict(num_layers=24, num_heads=16, d_model=1536),
    "1.3b": dict(num_layers=24, num_heads=32, d_model=2048),
    "2.7b": dict(num_layers=32, num_heads=32, d_model=2560),
    "6.7b": dict(num_layers=32, num_heads=32, d_model=4096),
    "13b": dict(num_layers=40, num_heads=40, d_model=5120),
}


def init_params(config: GPT2Config, rng) -> dict:
    D, V, S, L, M = (config.d_model, config.vocab_size, config.max_seq_len,
                     config.num_layers, config.d_mlp)
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    # residual-projection init scaled by depth (GPT-2 paper convention)
    res_std = std / (2 * L) ** 0.5
    norm = partial(jax.random.normal, dtype=jnp.float32)

    def stack_init(key, shape, scale):
        return norm(key, (L,) + shape) * scale

    params = {
        "wte": norm(next(k), (V, D)) * std,
        "wpe": norm(next(k), (S, D)) * std,
        "blocks": {
            "ln1_scale": jnp.ones((L, D)),
            "ln1_bias": jnp.zeros((L, D)),
            "qkv_w": stack_init(next(k), (D, 3 * D), std),
            "qkv_b": jnp.zeros((L, 3 * D)),
            "proj_w": stack_init(next(k), (D, D), res_std),
            "proj_b": jnp.zeros((L, D)),
            "ln2_scale": jnp.ones((L, D)),
            "ln2_bias": jnp.zeros((L, D)),
            "mlp_in_w": stack_init(next(k), (D, M), std),
            "mlp_in_b": jnp.zeros((L, M)),
            "mlp_out_w": stack_init(next(k), (M, D), res_std),
            "mlp_out_b": jnp.zeros((L, D)),
        },
        "lnf_scale": jnp.ones((D,)),
        "lnf_bias": jnp.zeros((D,)),
    }
    return params


def init_layer_slice(config: GPT2Config, rng, i) -> dict:
    """ONE layer's block params (no leading L), distributions matching
    ``init_params``.  Jittable with a traced layer index — the engine's
    offload tier generates layers on device and DMAs each slice to pinned
    host, so neither HBM nor the (slow, single-core) host RNG ever holds
    the full stacked tensors."""
    D, M, L = config.d_model, config.d_mlp, config.num_layers
    r = jax.random.fold_in(rng, i)
    k = iter(jax.random.split(r, 8))
    std = 0.02
    res_std = std / (2 * L) ** 0.5
    norm = partial(jax.random.normal, dtype=jnp.float32)
    return {
        "ln1_scale": jnp.ones((D,)), "ln1_bias": jnp.zeros((D,)),
        "qkv_w": norm(next(k), (D, 3 * D)) * std,
        "qkv_b": jnp.zeros((3 * D,)),
        "proj_w": norm(next(k), (D, D)) * res_std,
        "proj_b": jnp.zeros((D,)),
        "ln2_scale": jnp.ones((D,)), "ln2_bias": jnp.zeros((D,)),
        "mlp_in_w": norm(next(k), (D, M)) * std,
        "mlp_in_b": jnp.zeros((M,)),
        "mlp_out_w": norm(next(k), (M, D)) * res_std,
        "mlp_out_b": jnp.zeros((D,)),
    }


def init_nonblock(config: GPT2Config, rng) -> dict:
    """Everything outside the stacked blocks (small), same distributions."""
    D, V, S = config.d_model, config.vocab_size, config.max_seq_len
    k = iter(jax.random.split(rng, 4))
    std = 0.02
    norm = partial(jax.random.normal, dtype=jnp.float32)
    return {
        "wte": norm(next(k), (V, D)) * std,
        "wpe": norm(next(k), (S, D)) * std,
        "lnf_scale": jnp.ones((D,)), "lnf_bias": jnp.zeros((D,)),
    }


def numpy_init_params(config: GPT2Config, seed: int = 0) -> dict:
    """Host-side init mirroring ``init_params``'s distributions with
    numpy's PCG64 (~3.5x the single-core throughput of jax-cpu threefry).
    Used by the engine's ZeRO-Infinity tier, where params are *stored* in
    host memory and a multi-GB device init would exhaust HBM."""
    D, V, S, L, M = (config.d_model, config.vocab_size, config.max_seq_len,
                     config.num_layers, config.d_mlp)
    rng = np.random.default_rng(seed)
    std = 0.02
    res_std = std / (2 * L) ** 0.5

    def norm(shape, scale):
        return rng.standard_normal(shape, dtype=np.float32) * scale

    return {
        "wte": norm((V, D), std),
        "wpe": norm((S, D), std),
        "blocks": {
            "ln1_scale": np.ones((L, D), np.float32),
            "ln1_bias": np.zeros((L, D), np.float32),
            "qkv_w": norm((L, D, 3 * D), std),
            "qkv_b": np.zeros((L, 3 * D), np.float32),
            "proj_w": norm((L, D, D), res_std),
            "proj_b": np.zeros((L, D), np.float32),
            "ln2_scale": np.ones((L, D), np.float32),
            "ln2_bias": np.zeros((L, D), np.float32),
            "mlp_in_w": norm((L, D, M), std),
            "mlp_in_b": np.zeros((L, M), np.float32),
            "mlp_out_w": norm((L, M, D), res_std),
            "mlp_out_b": np.zeros((L, D), np.float32),
        },
        "lnf_scale": np.ones((D,), np.float32),
        "lnf_bias": np.zeros((D,), np.float32),
    }


def logical_specs(config: GPT2Config) -> dict:
    """Tensor-parallel layout over the ``model`` mesh axis (Megatron pattern:
    reference capability = client-mpu TP, engine.py:1095 + AutoTP
    module_inject/auto_tp.py:165)."""
    return {
        "wte": P("model", None),          # vocab-parallel embedding
        "wpe": P(),
        "blocks": {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_w": P(None, None, "model"),   # column parallel
            "qkv_b": P(None, "model"),
            "proj_w": P(None, "model", None),  # row parallel
            "proj_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "mlp_in_w": P(None, None, "model"),
            "mlp_in_b": P(None, "model"),
            "mlp_out_w": P(None, "model", None),
            "mlp_out_b": P(),
        },
        "lnf_scale": P(), "lnf_bias": P(),
    }


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _lora_add(y, lora, name, h):
    """Adapter delta on a projection output (see ``lora_add`` in
    models/serving.py)."""
    from deepspeed_tpu.models.serving import lora_add
    return lora_add(y, lora, name, h)


@jax.named_scope(SCOPE_ATTN)
def _block_qkv(x, layer, config: GPT2Config, lora=None):
    """LN1 + QKV projection; x [B, S, D] -> q/k/v [B, S, H, hd].
    ``lora(name, h)`` is the per-layer gather-LoRA callback (ISSUE 20)."""
    B, S, D = x.shape
    H, hd = config.num_heads, config.head_dim
    h = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"], config.layer_norm_eps)
    qkv = qdot(h, layer["qkv_w"]) + layer["qkv_b"].astype(h.dtype)
    qkv = _lora_add(qkv, lora, "qkv_w", h)
    q, kk, v = jnp.split(qkv, 3, axis=-1)
    return (q.reshape(B, S, H, hd), kk.reshape(B, S, H, hd),
            v.reshape(B, S, H, hd))


def _block_finish(x, attn, layer, config: GPT2Config, lora=None):
    """Post-attention half: proj + residual + MLP; x/attn [B, S, D]."""
    with jax.named_scope(SCOPE_ATTN):
        proj = qdot(attn, layer["proj_w"]) + layer["proj_b"].astype(x.dtype)
        x = x + _lora_add(proj, lora, "proj_w", attn)
    with jax.named_scope(SCOPE_MLP):
        return _block_mlp(x, layer, config, lora)


def _block_mlp(x, layer, config: GPT2Config, lora=None):
    h = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"], config.layer_norm_eps)
    h = _lora_add(qdot(h, layer["mlp_in_w"])
                  + layer["mlp_in_b"].astype(h.dtype),
                  lora, "mlp_in_w", h)
    if config.activation == "relu":
        h = jax.nn.relu(h)
    else:
        h = jax.nn.gelu(h, approximate=config.activation != "gelu_exact")
    x = x + _lora_add(qdot(h, layer["mlp_out_w"])
                      + layer["mlp_out_b"].astype(x.dtype),
                      lora, "mlp_out_w", h)
    return x


@jax.named_scope(SCOPE_BLOCK)
def _block(x, layer, config: GPT2Config, rng=None, segment_ids=None):
    """One transformer block; shapes [B, S, D]."""
    B, S, D = x.shape
    q, kk, v = _block_qkv(x, layer, config)
    with jax.named_scope(SCOPE_ATTN):
        attn = causal_attention(q, kk, v, impl=config.attention_impl,
                                segment_ids=segment_ids)
        attn = attn.reshape(B, S, D)
    # named residual: the save_attn remat policy keeps attention outputs and
    # recomputes the (cheap, MXU-bound) linear parts in the backward pass —
    # re-running the flash kernel is the expensive half of full remat
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    return _block_finish(x, attn, layer, config)


def head_inputs(params: dict, batch: dict, config: GPT2Config,
                rng=None) -> Head:
    """Token ids [B, S] -> the head's inputs: the normed last hidden state
    and the tied embedding on its own axis.  Layers run under ``lax.scan``
    so XLA compiles one block and (under ZeRO-3 shardings) gathers each
    layer's params just-in-time, overlapping the all-gather with the
    previous layer's compute — the reference's prefetch coordinator
    (partitioned_param_coordinator.py:256) collapses into XLA scheduling."""
    tokens = batch["input_ids"]
    B, S = tokens.shape
    dtype = jnp.dtype(config.dtype)
    with jax.named_scope(SCOPE_EMBED):
        x = (params["wte"].astype(dtype)[tokens]
             + params["wpe"].astype(dtype)[:S])

    # stream-inside-remat: with ZeRO-Infinity param offload the layer slice is
    # transferred host→device *inside* the remat boundary, so backward
    # re-streams it instead of keeping every layer's device copy alive
    seg = batch.get("segment_ids") if isinstance(batch, dict) else None

    def block_fn(x, layer):
        return _block(x, maybe_stream(layer), config, rng, seg)
    if config.remat:
        block_fn = jax.checkpoint(block_fn,
                                  policy=remat_policy(config.remat_policy))

    # layer scan with random-LTD + progressive-layer-drop hooks (see
    # models/model.py scan_blocks); packed batches skip LTD (a token
    # subset would misalign the closed-over segment ids)
    from deepspeed_tpu.models.model import scan_blocks
    x = scan_blocks(block_fn, x, params["blocks"], rng, batch,
                    config.num_layers, allow_ltd=seg is None)
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                        config.layer_norm_eps)
    return Head(x, params["wte"], tied=True)


def forward(params: dict, batch: dict, config: GPT2Config, rng=None):
    """Token ids [B, S] -> logits [B, S, V]."""
    return head_inputs(params, batch, config, rng).logits()


# --------------------------------------------------------------------- decode
# KV-cache serving path (reference capability: ds_softmax_context KV-cache
# attention, csrc/transformer/inference/csrc/pt_binding.cpp:434, plus the
# inference containers' cache management).  Caches are [L, B, S_max, H, hd];
# decode is a lax.scan over layers with a single-token decode-attention kernel.

def _fused_spec(config: GPT2Config, sm_scale=None):
    """Fused-megakernel layer spec (ISSUE 12): LN + fused QKV + decode
    attention + GELU MLP, serial residual.  ``sm_scale`` is the GPT-Neo
    unscaled-score hook (a static float, so it rides the spec); the
    ``min_pos_fn`` sliding-window hook keeps the unfused path."""
    from deepspeed_tpu.ops.pallas.fused_decode import FusedLayerSpec
    mlp = {"gelu": "gelu_tanh", "gelu_exact": "gelu_exact",
           "relu": "relu"}.get(config.activation, "gelu_tanh")
    return FusedLayerSpec(
        num_heads=config.num_heads, num_kv_heads=config.num_heads,
        head_dim=config.head_dim, d_model=config.d_model,
        norm="ln", eps=config.layer_norm_eps, qkv="fused", qkv_bias=True,
        out_bias=True, mlp=mlp, mlp_bias=True, sm_scale=sm_scale)


def _fused_weights(layer):
    return {"n1_s": layer["ln1_scale"], "n1_b": layer["ln1_bias"],
            "wqkv": layer["qkv_w"], "bqkv": layer["qkv_b"],
            "wo": layer["proj_w"], "bo": layer["proj_b"],
            "n2_s": layer["ln2_scale"], "n2_b": layer["ln2_bias"],
            "w_in": layer["mlp_in_w"], "b_in": layer["mlp_in_b"],
            "w_out": layer["mlp_out_w"], "b_out": layer["mlp_out_b"]}

def init_cache(config: GPT2Config, batch_size: int, max_len: int, dtype=None):
    """``dtype="int8"`` selects the quantized cache: int8 payload + one
    fp32 scale per cached head-vector — half the HBM bytes the
    bandwidth-bound decode kernel must stream."""
    L, H, hd = config.num_layers, config.num_heads, config.head_dim
    shape = (L, batch_size, max_len, H, hd)
    if str(dtype) == "int8":
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.ones(sshape, jnp.float32),
                "v_s": jnp.ones(sshape, jnp.float32)}
    dtype = jnp.dtype(dtype or config.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, batch, cache, config: GPT2Config, attn_fn=None,
            lora=None):
    """Run the causal forward over (right-padded) prompts, filling the cache.
    Returns (logits [B, S, V], cache).  ``attn_fn(q, k, v, layer_idx)``
    overrides the attention product (GPT-Neo's banded/unscaled form rides
    this hook).  ``lora`` (ISSUE 20): gather-LoRA batch — prompt KV
    depends on the adapter, so prefill applies it too; the layer-major
    stacks ride the scan as xs."""
    from deepspeed_tpu.models.serving import lora_layer_fn
    tokens = batch["input_ids"]
    B, S = tokens.shape
    dtype = jnp.dtype(config.dtype)
    x = params["wte"].astype(dtype)[tokens] + params["wpe"].astype(dtype)[:S]
    if attn_fn is None:
        attn_fn = lambda q, k, v, idx: causal_attention(
            q, k, v, impl=config.attention_impl)

    def body(carry, xs):
        if lora is None:
            layer, idx = xs
            lfn = None
        else:
            layer, idx, ls = xs
            lfn = lora_layer_fn(lora, ls)
        layer = maybe_stream(layer)      # dequant / host-stream per layer
        q, kk, v = _block_qkv(carry, layer, config, lora=lfn)
        attn = attn_fn(q, kk, v, idx)
        out = _block_finish(carry, attn.reshape(B, S, -1), layer, config,
                            lora=lfn)
        return out, (kk, v)

    idxs = jnp.arange(config.num_layers)
    xs = (params["blocks"], idxs) if lora is None \
        else (params["blocks"], idxs, lora["stacks"])
    x, (ks, vs) = lax.scan(body, x, xs)
    if "k_s" in cache:      # int8 cache: quantize the prefill block
        from deepspeed_tpu.ops.pallas.decode_attention import (
            quantize_prefill_into_cache)
        return (head(params, x, config),
                quantize_prefill_into_cache(cache, ks, vs))
    cache = {
        "k": lax.dynamic_update_slice(cache["k"], ks.astype(cache["k"].dtype),
                                      (0, 0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(cache["v"], vs.astype(cache["v"].dtype),
                                      (0, 0, 0, 0, 0)),
    }
    logits = head(params, x, config)
    return logits, cache


def decode_step(params, tokens, cache, lengths, config: GPT2Config,
                sm_scale=None, min_pos_fn=None, lora=None):
    """One decode step.  tokens [B] int32, lengths [B] = current cache fill
    per row (the new token's position).  Returns (logits [B, V], cache).

    Hooks for gpt2-family variants: ``sm_scale`` overrides the score
    scale (GPT-Neo's unscaled form passes 1.0); ``min_pos_fn(idx,
    lengths) -> [B]`` supplies a per-layer sliding-window floor for the
    decode kernel."""
    from deepspeed_tpu.models.serving import use_scan_decode, write_token
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, quantize_kv)
    B = tokens.shape[0]
    dtype = jnp.dtype(config.dtype)
    D = config.d_model
    x = (params["wte"].astype(dtype)[tokens] +
         params["wpe"].astype(dtype)[lengths])              # [B, D]

    quantized = "k_s" in cache      # int8 cache: quantize new K/V vectors

    from deepspeed_tpu.models import serving as _sv
    # per-row gather-LoRA keeps the unrolled composition (ISSUE 20):
    # neither the fused megakernel nor the scan form expresses the
    # per-layer stack slices
    fused = (min_pos_fn is None and lora is None
             and _sv.fused_decode_active(params["blocks"],
                                         _fused_spec(config, sm_scale)))
    if (use_scan_decode(params["blocks"], fused=fused)
            and sm_scale is None and min_pos_fn is None and lora is None):
        # large int8 models: scan serializes the per-layer dequant (the
        # unrolled loop lets XLA materialize every layer's bf16 weights
        # at once — see serving.quantized_layer_bytes).  The GPT-Neo
        # hooks (sm_scale/min_pos_fn) keep the unrolled form — those
        # variants don't reach this scale quantized.
        return _sv.decode_step_scan(
            params, x, cache, lengths,
            qkv_fn=lambda xx, layer, pos: _block_qkv(xx, layer, config),
            finish_fn=lambda xx, attn, layer: _block_finish(
                xx, attn, layer, config),
            head_fn=lambda p, xx: head(p, xx, config),
            num_heads=config.num_heads)
    if fused:
        # ONE Pallas call per layer (ISSUE 12)
        x, cache = _sv._fused_layer_pass(
            params, x[:, None, :], cache, lengths,
            spec=_fused_spec(config, sm_scale), weights_fn=_fused_weights)
        return head(params, x, config)[:, 0], cache

    # python-unrolled layer loop with in-place one-hot cache writes: 2.2x
    # faster than the round-4 lax.scan + scatter form (the scan
    # dynamic-sliced every layer's weights and double-buffered the cache;
    # TPU scatter alone cost ~0.6 ms/step — scripts/decode_profile.py).
    # int8 weights ride the fused-dequant qgemm path (keep_quantized):
    # no compute-dtype dequant exists for XLA to hoist across layers
    from deepspeed_tpu.models.serving import qgemm_active
    keep_q = qgemm_active(params["blocks"])
    kc, vc = cache["k"], cache["v"]
    ksc, vsc = (cache["k_s"], cache["v_s"]) if quantized else (None, None)
    for l in range(config.num_layers):
        layer = maybe_stream(jax.tree.map(lambda a: a[l], params["blocks"]),
                             keep_quantized=keep_q)
        lfn = _sv.lora_at_layer(lora, l)
        q, kk, v = _block_qkv(x[:, None, :], layer, config, lora=lfn)
        if quantized:
            kq, ks1 = quantize_kv(kk[:, 0])
            vq, vs1 = quantize_kv(v[:, 0])
            kc = write_token(kc, l, kq, lengths)
            vc = write_token(vc, l, vq, lengths)
            ksc = write_token(ksc, l, ks1, lengths)
            vsc = write_token(vsc, l, vs1, lengths)
        else:
            kc = write_token(kc, l, kk[:, 0], lengths)
            vc = write_token(vc, l, v[:, 0], lengths)
        attn = decode_attention(
            q[:, 0], kc[l], vc[l], lengths + 1, sm_scale=sm_scale,
            k_scale=ksc[l] if quantized else None,
            v_scale=vsc[l] if quantized else None,
            min_pos=(min_pos_fn(jnp.int32(l), lengths)
                     if min_pos_fn is not None else None))
        x = _block_finish(x, attn.reshape(B, D).astype(x.dtype),
                          layer, config, lora=lfn)
    logits = head(params, x[:, None, :], config)[:, 0]
    if quantized:
        return logits, {"k": kc, "v": vc, "k_s": ksc, "v_s": vsc}
    return logits, {"k": kc, "v": vc}


def verify_window(params, tokens, cache, lengths, config: GPT2Config,
                  sm_scale=None, min_pos_fn=None, lora=None):
    """Speculative-decoding verification (serving/spec): score a W-token
    window at positions ``lengths .. lengths+W-1`` with ONE weight pass
    per layer — the QKV/MLP/head projections run once over all W
    positions, and each position attends causally via the same
    ``decode_attention`` kernel ``decode_step`` uses, so position j's
    logits match a sequential decode chain's exactly.  Returns
    (logits [B, W, V], cache).  ``sm_scale``/``min_pos_fn`` are the
    GPT-Neo hooks (unscaled scores, per-layer sliding-window floor)."""
    from deepspeed_tpu.models.serving import qgemm_active, write_token
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, quantize_kv)
    B, W = tokens.shape
    dtype = jnp.dtype(config.dtype)
    positions = lengths[:, None] + jnp.arange(W)[None, :]   # [B, W]
    x = (params["wte"].astype(dtype)[tokens] +
         params["wpe"].astype(dtype)[positions])            # [B, W, D]
    from deepspeed_tpu.models import serving as _sv
    if min_pos_fn is None and lora is None and _sv.fused_decode_active(
            params["blocks"], _fused_spec(config, sm_scale)):
        # the whole window per layer in ONE Pallas call (ISSUE 12)
        x, cache = _sv._fused_layer_pass(
            params, x, cache, lengths,
            spec=_fused_spec(config, sm_scale), weights_fn=_fused_weights)
        return head(params, x, config), cache
    quantized = "k_s" in cache
    keep_q = qgemm_active(params["blocks"])
    kc, vc = cache["k"], cache["v"]
    ksc, vsc = (cache["k_s"], cache["v_s"]) if quantized else (None, None)
    for l in range(config.num_layers):
        layer = maybe_stream(jax.tree.map(lambda a: a[l], params["blocks"]),
                             keep_quantized=keep_q)
        lfn = _sv.lora_at_layer(lora, l)
        q, kk, v = _block_qkv(x, layer, config, lora=lfn)
        attn_cols = []
        for j in range(W):
            if quantized:
                kq, ks1 = quantize_kv(kk[:, j])
                vq, vs1 = quantize_kv(v[:, j])
                kc = write_token(kc, l, kq, lengths + j)
                vc = write_token(vc, l, vq, lengths + j)
                ksc = write_token(ksc, l, ks1, lengths + j)
                vsc = write_token(vsc, l, vs1, lengths + j)
            else:
                kc = write_token(kc, l, kk[:, j], lengths + j)
                vc = write_token(vc, l, v[:, j], lengths + j)
            attn_cols.append(decode_attention(
                q[:, j], kc[l], vc[l], lengths + j + 1, sm_scale=sm_scale,
                k_scale=ksc[l] if quantized else None,
                v_scale=vsc[l] if quantized else None,
                min_pos=(min_pos_fn(jnp.int32(l), lengths + j)
                         if min_pos_fn is not None else None)))
        attn = jnp.stack(attn_cols, axis=1)                 # [B, W, H, hd]
        x = _block_finish(x, attn.reshape(B, W, -1).astype(x.dtype),
                          layer, config, lora=lfn)
    logits = head(params, x, config)                        # [B, W, V]
    if quantized:
        return logits, {"k": kc, "v": vc, "k_s": ksc, "v_s": vsc}
    return logits, {"k": kc, "v": vc}


def count_params(config: GPT2Config) -> int:
    D, V, S, L, M = (config.d_model, config.vocab_size, config.max_seq_len,
                     config.num_layers, config.d_mlp)
    per_layer = 4 * D + 3 * D * D + 3 * D + D * D + D + 2 * D * M + M + D
    return V * D + S * D + L * per_layer + 2 * D


@jax.named_scope(SCOPE_EMBED)
def embed(params, batch, config: GPT2Config):
    tokens = batch["input_ids"]
    dtype = jnp.dtype(config.dtype)
    S = tokens.shape[1]
    return params["wte"].astype(dtype)[tokens] + params["wpe"].astype(dtype)[:S]


@jax.named_scope(SCOPE_HEAD_LOSS)
def head(params, x, config: GPT2Config):
    dtype = jnp.dtype(config.dtype)
    x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"],
                    config.layer_norm_eps)
    return x @ params["wte"].astype(dtype).T


def gpt2_model(size: str = "125m", **overrides) -> Model:
    cfg_kwargs = resolve_size(GPT2_SIZES, size, "gpt2")
    cfg_kwargs.update(overrides)
    config = GPT2Config(**cfg_kwargs)
    n_params = count_params(config)

    def loss(params, batch, rng=None):
        return head_inputs(params, batch, config, rng).token_loss(batch)

    # ``token_loss`` of ``apply_fn``'s logits, taken without them: the
    # stock loss that the NVMe tier's streamed head VJP mirrors
    loss.causal_lm_loss = True
    return Model(
        config=config,
        init_fn=partial(init_params, config),
        numpy_init_fn=partial(numpy_init_params, config),
        layer_init_fn=partial(init_layer_slice, config),
        nonblock_init_fn=partial(init_nonblock, config),
        apply_fn=lambda p, b, rng=None: forward(p, b, config, rng),
        loss_fn=loss,
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * n_params,
        meta={"name": f"gpt2-{size}", "n_params": n_params,
              "supports_random_ltd": True, "supports_pld": True,
              "lora_serving": True},
        embed_fn=lambda p, b: embed(p, b, config),
        block_fn=lambda lp, x: _block(x, lp, config),
        head_fn=lambda p, x: head(p, x, config),
        init_cache_fn=lambda bs, ml, dtype=None: init_cache(config, bs, ml, dtype),
        prefill_fn=lambda p, b, c, lora=None: prefill(p, b, c, config,
                                                      lora=lora),
        decode_fn=lambda p, t, c, l, lora=None: decode_step(
            p, t, c, l, config, lora=lora),
        verify_fn=lambda p, t, c, l, lora=None: verify_window(
            p, t, c, l, config, lora=lora),
    )
