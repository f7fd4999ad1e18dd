"""Mixtral-style MoE decoder: Llama blocks with top-k-routed expert SwiGLU
FFNs, expert-parallel over the ``expert`` mesh axis (BASELINE.md config 5:
Mixtral-8x7B EP + Ulysses SP).

OLMoE (Muennighoff et al. 2024, arXiv:2409.02060) is the same family with
three differences, each a field of :class:`MixtralConfig` and all set by
the size ``olmoe-1b-7b``: RMSNorm on q and k (``qk_norm``), the chosen
gates left un-normalised (``norm_topk_prob=False``), and the paper's two
router losses (``load_balance="all_choices"``, ``router_z_loss_coef``).
"""
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.model import Head, Model, qdot, resolve_size
from deepspeed_tpu.models.llama import _rms_norm, rope
from deepspeed_tpu.moe.layer import (STEP_LOAD, MoEConfig, layer_sums,
                                     moe_layer)
from deepspeed_tpu.moe.sharded_moe import topkgating
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTN, SCOPE_BLOCK, SCOPE_EMBED, SCOPE_HEAD_LOSS, SCOPE_MLP)


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    d_model: int = 4096
    d_ff: int = 14336
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    #: None (default) = drop-free at eval/serving (capacity >= all tokens
    #: on one expert, the HF serving semantic — keeps the cached decode,
    #: the prefill, and the no-cache oracle token-identical regardless of
    #: router skew).  Set a number to cap eval capacity (cheaper dispatch
    #: for long prefills, at the cost of potential drops).
    eval_capacity_factor: "float | None" = None
    #: expert dispatch formulation (moe/layer.py dispatch_mode): "auto"
    #: (default — einsum when training, megablocks-style grouped GEMM at
    #: eval/serving), "einsum", or "grouped".  Grouped serving consumes
    #: int8 expert stacks in place through the fused-dequant grouped
    #: kernel (ops/pallas/grouped_gemm.py) instead of the per-expert
    #: residual-dequant fallback (ISSUE 8).
    moe_dispatch: str = "auto"
    aux_loss_coef: float = 0.01
    #: form of the load-balance term that ``aux_loss_coef`` weighs
    #: (moe/sharded_moe.py LOAD_BALANCE_FORMS)
    load_balance: str = "first_choice"
    #: weight of mean_t(logsumexp(router logits)^2), summed over layers
    router_z_loss_coef: float = 0.0
    #: divide the top-k gate values by their sum (Mixtral); OLMoE does not
    norm_topk_prob: bool = True
    #: RMSNorm with a learned scale on the q and on the k projection, each
    #: over its whole width, before the heads are split and rotated (OLMoE)
    qk_norm: bool = False
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def moe(self) -> MoEConfig:
        eval_cf = (self.eval_capacity_factor
                   if self.eval_capacity_factor is not None
                   else self.num_experts / self.top_k)
        return MoEConfig(d_model=self.d_model, d_ff=self.d_ff,
                         num_experts=self.num_experts, top_k=self.top_k,
                         capacity_factor=self.capacity_factor,
                         eval_capacity_factor=eval_cf,
                         aux_loss_coef=self.aux_loss_coef,
                         z_loss_coef=self.router_z_loss_coef,
                         norm_topk_prob=self.norm_topk_prob,
                         load_balance=self.load_balance,
                         activation="silu_glu",
                         dispatch_mode=self.moe_dispatch)


MIXTRAL_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=2, num_heads=4,
                 num_kv_heads=2, d_model=32, d_ff=64, num_experts=4, top_k=2),
    # single-chip bench config (~0.8B total / ~0.3B active): full MoE
    # state (bf16 params + fp32 masters/moments) fits one 16 GB chip
    "1b-moe": dict(vocab_size=32000, max_seq_len=2048, num_layers=8,
                   num_heads=16, num_kv_heads=8, d_model=1024, d_ff=3584,
                   num_experts=8, top_k=2),
    "8x7b": dict(),
    # OLMoE-1B-7B (huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct
    # config.json): 64 experts of width 1024, 8 per token, MHA 16 x 128;
    # loss weights 0.01 / 0.001 are the paper's.  6.9B parameters at its
    # 16 layers; one chip trains num_layers=2 (benchmarks/configs)
    "olmoe-1b-7b": dict(vocab_size=50304, max_seq_len=4096, num_layers=16,
                        num_heads=16, num_kv_heads=16, d_model=2048,
                        d_ff=1024, num_experts=64, top_k=8, rope_theta=1e4,
                        rms_norm_eps=1e-5, qk_norm=True,
                        norm_topk_prob=False, load_balance="all_choices",
                        aux_loss_coef=0.01, router_z_loss_coef=0.001),
}


def init_params(config: MixtralConfig, rng) -> dict:
    D, V, L = config.d_model, config.vocab_size, config.num_layers
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    E, F = config.num_experts, config.d_ff
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    res_std = std / (2 * L) ** 0.5
    norm = partial(jax.random.normal, dtype=jnp.float32)
    qk_norm = {"q_norm": jnp.ones((L, H * hd)),
               "k_norm": jnp.ones((L, KV * hd))} if config.qk_norm else {}
    return {
        "wte": norm(next(k), (V, D)) * std,
        "blocks": {
            "attn_norm": jnp.ones((L, D)),
            "wq": norm(next(k), (L, D, H * hd)) * std,
            "wk": norm(next(k), (L, D, KV * hd)) * std,
            "wv": norm(next(k), (L, D, KV * hd)) * std,
            "wo": norm(next(k), (L, H * hd, D)) * res_std,
            **qk_norm,
            "mlp_norm": jnp.ones((L, D)),
            "moe": {
                "router": norm(next(k), (L, D, E)) * std,
                "w_gate": norm(next(k), (L, E, D, F)) * std,
                "w_in": norm(next(k), (L, E, D, F)) * std,
                "w_out": norm(next(k), (L, E, F, D)) * res_std,
            },
        },
        "final_norm": jnp.ones((D,)),
        "lm_head": norm(next(k), (D, V)) * std,
    }


def logical_specs(config: MixtralConfig) -> dict:
    return {
        "wte": P("model", None),
        "blocks": {
            "attn_norm": P(),
            "wq": P(None, None, "model"),
            "wk": P(None, None, "model"),
            "wv": P(None, None, "model"),
            "wo": P(None, "model", None),
            **({"q_norm": P(), "k_norm": P()} if config.qk_norm else {}),
            "mlp_norm": P(),
            "moe": {
                "router": P(),
                "w_gate": P(None, "expert", None, "model"),
                "w_in": P(None, "expert", None, "model"),
                "w_out": P(None, "expert", "model", None),
            },
        },
        "final_norm": P(),
        "lm_head": P(None, "model"),
    }


def _qkv(x, layer, config: MixtralConfig, positions=None):
    """RMSNorm + QKV + rotary; kv heads NOT repeated (compact caches)."""
    B, S, D = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    h = _rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    q, kk = qdot(h, layer["wq"]), qdot(h, layer["wk"])
    if config.qk_norm:
        q = _rms_norm(q, layer["q_norm"], config.rms_norm_eps)
        kk = _rms_norm(kk, layer["k_norm"], config.rms_norm_eps)
    q = rope(q.reshape(B, S, H, hd), config.rope_theta, positions)
    kk = rope(kk.reshape(B, S, KV, hd), config.rope_theta, positions)
    v = qdot(h, layer["wv"]).reshape(B, S, KV, hd)
    return q, kk, v


def _moe_finish(x, attn_flat, layer, config: MixtralConfig, train: bool,
                rng=None):
    """Attention output projection + residual + routed-expert FFN ->
    (x, (router loss, the layer's int32 sums: ``moe/layer.py
    layer_sums``))."""
    with jax.named_scope(SCOPE_ATTN):
        x = x + qdot(attn_flat, layer["wo"])
    with jax.named_scope(SCOPE_MLP):
        h = _rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
        moe_out, aux, stats = moe_layer(layer["moe"], h, config.moe,
                                        train=train, rng=rng,
                                        return_stats=True)
        return x + moe_out, (aux, layer_sums(stats))


@jax.named_scope(SCOPE_BLOCK)
def _block(carry, layer, config: MixtralConfig, train: bool, rng=None,
           segment_ids=None):
    x = carry
    B, S, D = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    with jax.named_scope(SCOPE_ATTN):
        q, kk, v = _qkv(x, layer, config)
        attn = causal_attention(q, kk, v, impl=config.attention_impl,
                                segment_ids=segment_ids)
    attn = jax.ad_checkpoint.checkpoint_name(attn, "attn_out")
    return _moe_finish(x, attn.reshape(B, S, H * hd), layer, config,
                       train, rng)


def head_with_aux(params, batch, config: MixtralConfig, train: bool = True,
                  rng=None):
    """-> (the head's inputs, router loss summed over layers, the layers'
    int32 sums added up: ``moe/layer.py layer_sums``)."""
    tokens = batch["input_ids"]
    dtype = jnp.dtype(config.dtype)
    with jax.named_scope(SCOPE_EMBED):
        x = params["wte"].astype(dtype)[tokens]
    seg = batch.get("segment_ids") if isinstance(batch, dict) else None
    # stream-inside-remat (see models/model.py maybe_stream)
    def block_fn(x, layer):
        from deepspeed_tpu.models.model import maybe_stream
        return _block(x, maybe_stream(layer), config, train=train, rng=rng,
                      segment_ids=seg)
    if config.remat:
        from deepspeed_tpu.models.model import remat_policy
        block_fn = jax.checkpoint(
            block_fn, policy=remat_policy(config.remat_policy))
    x, (aux, sums) = lax.scan(block_fn, x, params["blocks"])
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _rms_norm(x, params["final_norm"], config.rms_norm_eps)
    return Head(x, params["lm_head"]), jnp.sum(aux), jnp.sum(sums, 0)


# --------------------------------------------------------------------- decode
# MoE serving path (reference capability:
# ops/transformer/inference/moe_inference.py + inference/engine.py:230 EP
# groups): the shared rotary-GQA cache scaffold (models/serving.py) with
# the routed-expert FFN as the post-attention block — drop-free at eval by
# default, EP-sharded when the mesh has a wide expert axis.

def _serving_fns(config: MixtralConfig):
    from deepspeed_tpu.models import serving

    def embed_fn(params, tokens):
        return params["wte"].astype(jnp.dtype(config.dtype))[tokens]

    def qkv_fn(x, layer, positions):
        return _qkv(x, layer, config, positions)

    def finish_fn(x, attn_flat, layer):
        out, _ = _moe_finish(x, attn_flat, layer, config, train=False)
        return out

    def head_fn(params, x):
        x = _rms_norm(x, params["final_norm"], config.rms_norm_eps)
        return x @ params["lm_head"].astype(jnp.dtype(config.dtype))

    # fused per-layer megakernel wiring (ISSUE 12): the kernel fuses
    # RMSNorm + QKV + rotary + GQA decode attention + attn-out
    # (mlp="none"); the routed-expert FFN stays OUTSIDE as the
    # ``moe_tail_fn`` so it keeps riding the grouped-GEMM slot kernels
    # (ISSUE 8) — one megakernel launch + the expert dispatch per layer
    from deepspeed_tpu.ops.pallas.fused_decode import FusedLayerSpec
    fused_spec = FusedLayerSpec(
        num_heads=config.num_heads, num_kv_heads=config.num_kv_heads,
        head_dim=config.head_dim, d_model=config.d_model,
        norm="rms", eps=config.rms_norm_eps, qkv="split",
        qkv_bias=False, out_bias=False, mlp="none",
        rotary_dims=config.head_dim, rope_theta=config.rope_theta)

    if config.qk_norm:
        fused_spec = None       # the megakernel has no norm on q and k

    def fused_weights(layer):
        return {"n1_s": layer["attn_norm"], "wq": layer["wq"],
                "wk": layer["wk"], "wv": layer["wv"], "wo": layer["wo"]}

    def moe_tail(x, layer):
        h = _rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
        moe_out, _ = moe_layer(layer["moe"], h, config.moe, train=False)
        return x + moe_out

    def init_cache_fn(bs, max_len, dtype=None):
        return serving.init_cache(config.num_layers, config.num_kv_heads,
                                  config.head_dim, bs, max_len, dtype,
                                  config.dtype)

    def prefill_fn(p, b, c):
        return serving.prefill(
            p, b, c, embed_fn=embed_fn, qkv_fn=qkv_fn, finish_fn=finish_fn,
            head_fn=head_fn, num_heads=config.num_heads,
            num_kv_heads=config.num_kv_heads,
            attention_impl=config.attention_impl)

    def decode_fn(p, t, c, l):
        return serving.decode_step(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads,
            moe_grouped=serving.moe_dispatch_grouped(config.moe),
            fused_spec=fused_spec, fused_weights_fn=fused_weights,
            moe_tail_fn=moe_tail)

    def verify_fn(p, t, c, l):
        return serving.verify_window(
            p, t, c, l, embed_fn=embed_fn, qkv_fn=qkv_fn,
            finish_fn=finish_fn, head_fn=head_fn,
            num_heads=config.num_heads,
            moe_grouped=serving.moe_dispatch_grouped(config.moe),
            fused_spec=fused_spec, fused_weights_fn=fused_weights,
            moe_tail_fn=moe_tail)

    return init_cache_fn, prefill_fn, decode_fn, verify_fn


def count_params(config: MixtralConfig) -> int:
    import numpy as np
    shapes = jax.eval_shape(partial(init_params, config), jax.random.PRNGKey(0))
    return int(sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)))


def mixtral_model(size: str = "8x7b", **overrides) -> Model:
    cfg_kwargs = resolve_size(MIXTRAL_SIZES, size, "mixtral")
    cfg_kwargs.update(overrides)
    config = MixtralConfig(**cfg_kwargs)
    n_params = count_params(config)
    # active params per token ≈ dense part + top_k/num_experts of experts
    active = n_params - (1 - config.top_k / config.num_experts) * (
        3 * config.num_layers * config.num_experts * config.d_model * config.d_ff)

    def loss_with_load(params, batch, rng=None):
        head, aux, sums = head_with_aux(params, batch, config,
                                        train=True, rng=rng)
        # inside a document only, where the batch is packed; aux = the
        # weighted router losses summed over layers (moe/layer.py)
        return head.token_loss(batch) + aux, dict(zip(STEP_LOAD, sums[1:]))

    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=lambda p, b, rng=None: head_with_aux(
            p, b, config, train=False, rng=rng)[0].logits(),
        loss_fn=lambda p, b, rng=None: loss_with_load(p, b, rng)[0],
        # nothing is left out of this loss (no bound; an einsum's capacity
        # drops are the reference's semantics), so no ``step_counts``: what
        # leaves the step beside it is its load (``engine.step_load()``)
        loss_with_counts_fn=loss_with_load,
        logical_specs=logical_specs(config),
        flops_per_token=6.0 * active,
        meta={"name": f"mixtral-{size}", "n_params": n_params,
              "active_params": active},
        **dict(zip(("init_cache_fn", "prefill_fn", "decode_fn",
                    "verify_fn"),
                   _serving_fns(config))),
    )
