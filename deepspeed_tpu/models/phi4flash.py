"""Phi-4-mini-flash (huggingface.co/microsoft/Phi-4-mini-flash-reasoning,
``model_type: phi4flash``; the architecture is SambaY with differential
attention: Ren et al. 2025, "Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation", arXiv:2507.06607): a dense
decoder of ``L`` layers (``L % 4 == 0``) in two halves.  The first half
and two layers more (the *self-decoder*) alternate Mamba-1 with
differential attention; the rest (the *cross-decoder*) alternates gated
memory units that read one Mamba layer's output with differential
attention that reads one layer's keys and values.

Every layer ``l`` (0-based) is ``x += Mixer_l(LN(x)); x += MLP(LN(x))``,
``LN`` a LayerNorm with weight and bias, ``MLP(u) = (silu(g) * h) W_down``
with ``[g | h] = u W_gate_up``, no bias; no rotary or other positional
term anywhere; a final LayerNorm; the head is the embedding table, tied.
The kind of the mixer follows from ``l`` and ``L`` alone
(:func:`layer_kinds`):

- **Mamba-1** (``l`` even, ``l <= L/2``; Gu & Dao 2023, arXiv:2312.00752):
  ``[u | z] = x W_in``; ``u = silu(conv(u) + b_c)``, depthwise, causal,
  ``mamba_d_conv`` taps, reading 0 across a document's start; ``[delta | B
  | C] = u W_x`` (``mamba_dt_rank + 2 mamba_d_state`` wide); the selective
  scan (ops/selective_scan.py) with the step ``softplus(delta W_dt +
  b_dt)``, ``A = -exp(A_log)`` [d_inner, d_state] and the skip ``D``, its
  state zero at a document's start -> ``y``; out ``= (y * silu(z))
  W_out``.  **Layer ``L/2`` also hands ``m = y``, before the gate, to the
  gated memory units.**
- **GMU** (``l`` even, ``l >= L/2 + 2``): ``Mixer(x) = (m * silu(x W_1))
  W_2`` with ``m`` layer ``L/2``'s.
- **Differential attention** (``l`` odd; Ye et al. 2024,
  arXiv:2410.05258): ``[q | k | v] = x W_qkv + b`` (``num_heads`` query
  heads, ``num_kv_heads`` key and value heads, ``head_dim`` wide).  Heads
  pair up as (2j, 2j+1): differential head ``j`` with its key/value pair
  ``g = j // (num_heads / num_kv_heads)``: ``A1 = softmax(q_2j k_2g^T /
  sqrt(head_dim))``, ``A2 = softmax(q_2j+1 k_2g+1^T / sqrt(head_dim))``,
  ``V = [v_2g | v_2g+1]``; ``o_j = (A1 - lambda A2) V`` with ``lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 -
  0.6 exp(-0.3 l)``; ``o_j <- RMSNorm(o_j) (1 - lambda_init)`` (a weight
  ``2 head_dim`` wide, shared by the heads); out ``= concat(o) W_o + b_o``.
  Causal inside a document, and: ``l < L/2`` — also ``query - key <
  sliding_window``; ``l = L/2 + 1`` — every earlier key, **and its ``k``,
  ``v`` are handed on**; ``l >= L/2 + 3`` — ``W_qkv`` is ``W_q`` alone and
  ``k``, ``v`` are layer ``L/2 + 1``'s.

Each map is one flash call (ops/pallas/ds_flash_attention.py) of
``num_heads / 2`` query heads over ``num_kv_heads / 2`` key heads at score
width ``head_dim`` and value width ``2 head_dim`` — wider values than keys
— so a layer makes two, windowed or causal; the source's own code makes
four at one width (``attn11/12/21/22``), the same mathematics with every
score computed twice.

**The layer loop is unrolled.**  ``models/model.py scan_layer_kinds``
carries ``x`` alone; here ``m`` and ``(k, v)`` leave one layer and enter
every later one of a kind, so each layer is its own (rematerialised)
function of ``x``, its parameters and what it reads, the parameters a
subtree a layer (``params["layers"]["07"]``), and ``m``, ``k``, ``v``
plain values whose gradients autodiff sums over their readers.

Not built: serving — the cross-decoder exists to stop a prefill half way
and to share one layer's cache, which needs a decode cache that holds one
layer's keys and values for many reader layers, one state-space layer's
output for the gated readers and a Mamba-1 state a sequence (the entry
points raise); ZeRO-3 and parameter streaming (no stacked subtree);
tensor parallelism (every leaf is replicated over ``model``).
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.gpt2 import _layer_norm
from deepspeed_tpu.models.llama import _rms_norm
from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        maybe_stream, param_count, qdot,
                                        refuse_param_stream, remat_policy,
                                        resolve_size, segment_ids_of)
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.ops.linear_attention import causal_conv
from deepspeed_tpu.ops.selective_scan import selective_scan
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_BLOCK, SCOPE_COMBINE, SCOPE_CONV, SCOPE_DIFF_ATTN, SCOPE_FLASH,
    SCOPE_GATE, SCOPE_GMU, SCOPE_HEAD_LOSS, SCOPE_IN_PROJ, SCOPE_MAMBA,
    SCOPE_MLP, SCOPE_OUT_PROJ, SCOPE_QKV, SCOPE_SCAN)

MAMBA, SWA, FULL, GMU, CROSS = "mamba", "swa", "full", "gmu", "cross"


def layer_kinds(num_layers: int) -> tuple:
    """The kind of each layer's mixer, by the source's rule."""
    if num_layers % 4 or num_layers < 8:
        raise ValueError(
            f"phi4flash: {num_layers} layers; the layout wants a multiple "
            f"of 4, and at least 8 for every kind to be there")
    half = num_layers // 2
    kinds = []
    for l in range(num_layers):
        if l % 2 == 0:
            kinds.append(MAMBA if l <= half else GMU)
        elif l < half:
            kinds.append(SWA)
        else:
            kinds.append(FULL if l == half + 1 else CROSS)
    return tuple(kinds)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    max_seq_len: int = 262144
    num_layers: int = 32
    d_model: int = 2560
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    d_ff: int = 10240
    sliding_window: int = 512
    # Mamba-1 (the defaults of the source's configuration class)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160            # ceil(d_model / 16)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    layer_norm_eps: float = 1e-5
    #: the sub-norm of a differential head
    subln_eps: float = 1e-5
    #: std of the four lambda vectors a layer at initialisation
    lambda_std: float = 0.1
    #: tokens of one chunk of the selective scan
    scan_chunk: int = 128
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"
    attention_impl: str = "auto"

    def __post_init__(self):
        if self.num_heads % 2 or self.num_kv_heads % 2 \
                or (self.num_heads // 2) % (self.num_kv_heads // 2):
            raise ValueError(
                f"phi4flash: {self.num_heads} query and {self.num_kv_heads} "
                f"key heads do not pair up into differential heads")
        layer_kinds(self.num_layers)

    @property
    def kinds(self) -> tuple:
        return layer_kinds(self.num_layers)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    #: the layer whose scan output the gated memory units read, and the
    #: layer whose keys and values the cross layers read
    @property
    def memory_layer(self) -> int:
        return self.num_layers // 2

    @property
    def kv_layer(self) -> int:
        return self.num_layers // 2 + 1


PHI4FLASH_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=256, num_layers=8, d_model=64,
                 num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                 sliding_window=16, mamba_dt_rank=4, scan_chunk=16),
    # huggingface.co/microsoft/Phi-4-mini-flash-reasoning config.json: the
    # defaults above.  3.85B parameters whole; one chip trains 8 layers at
    # an eighth of the vocabulary (benchmarks/configs)
    "mini-flash": dict(),
}


# ------------------------------------------------------------- parameters
def _init_layer(config: Phi4FlashConfig, kind: str, rng) -> dict:
    D, F = config.d_model, config.d_ff
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    d_in, N = config.d_inner, config.mamba_d_state
    R, K = config.mamba_dt_rank, config.mamba_d_conv
    std = 0.02
    k = iter(jax.random.split(rng, 12))
    norm = lambda shape, s=std: jax.random.normal(
        next(k), shape, jnp.float32) * s
    layer = {"ln1_w": jnp.ones((D,)), "ln1_b": jnp.zeros((D,)),
             "ln2_w": jnp.ones((D,)), "ln2_b": jnp.zeros((D,)),
             "w_gate_up": norm((D, 2 * F)), "w_down": norm((F, D))}
    if kind == MAMBA:
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            next(k), (d_in,), minval=math.log(config.time_step_min),
            maxval=math.log(config.time_step_max))), config.time_step_floor)
        layer.update(
            w_in=norm((D, 2 * d_in)), conv_w=norm((K, d_in)),
            conv_b=jnp.zeros((d_in,)), w_x=norm((d_in, R + 2 * N)),
            w_dt=norm((R, d_in)), dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=jnp.float32), (d_in, N))),
            D=jnp.ones((d_in,)), w_out=norm((d_in, D)))
    elif kind == GMU:
        layer.update(w_1=norm((D, d_in)), w_2=norm((d_in, D)))
    else:
        if kind == CROSS:
            layer.update(w_q=norm((D, H * hd)), b_q=jnp.zeros((H * hd,)))
        else:
            layer.update(w_qkv=norm((D, (H + 2 * KV) * hd)),
                         b_qkv=jnp.zeros(((H + 2 * KV) * hd,)))
        layer.update(
            lambda_q1=norm((hd,), config.lambda_std),
            lambda_k1=norm((hd,), config.lambda_std),
            lambda_q2=norm((hd,), config.lambda_std),
            lambda_k2=norm((hd,), config.lambda_std),
            subln=jnp.ones((2 * hd,)),
            w_o=norm((H * hd, D)), b_o=jnp.zeros((D,)))
    return layer


def layer_name(l: int) -> str:
    return f"{l:02d}"


def init_params(config: Phi4FlashConfig, rng) -> dict:
    """Seeded.  Assumed where the published config is silent: normal
    weights of std 0.02 (the convolution's taps too), norm weights 1,
    biases 0; the four lambda vectors a layer normal ``lambda_std``
    (arXiv:2410.05258); ``A_log = log(1..d_state)`` a channel, ``D = 1``,
    ``dt = exp U(log time_step_min, log time_step_max)`` floored at
    ``time_step_floor`` with ``dt_bias`` its inverse softplus
    (arXiv:2312.00752)."""
    keys = jax.random.split(rng, config.num_layers + 1)
    return {
        "wte": jax.random.normal(
            keys[-1], (config.vocab_size, config.d_model),
            jnp.float32) * 0.02,
        "layers": {layer_name(l): _init_layer(config, kind, keys[l])
                   for l, kind in enumerate(config.kinds)},
        "lnf_w": jnp.ones((config.d_model,)),
        "lnf_b": jnp.zeros((config.d_model,)),
    }


def logical_specs(config: Phi4FlashConfig) -> dict:
    """Every leaf replicated over ``model``: tensor parallelism of the
    scan's channels and of the paired heads is not built."""
    shapes = jax.eval_shape(partial(init_params, config),
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda _: P(), shapes)


# ----------------------------------------------------------------- mixers
def _mlp(x, layer, config):
    with jax.named_scope(SCOPE_MLP):
        h = _layer_norm(x, layer["ln2_w"], layer["ln2_b"],
                        config.layer_norm_eps)
        gate_up = qdot(h, layer["w_gate_up"])
        F = config.d_ff
        return x + qdot(jax.nn.silu(gate_up[..., :F]) * gate_up[..., F:],
                        layer["w_down"])


def _mamba(x, layer, config: Phi4FlashConfig, segment_ids, index):
    """-> (x + the mixer, y: the scan's output before the gate)."""
    d_in, N, R = config.d_inner, config.mamba_d_state, config.mamba_dt_rank
    f32 = lambda a: a.astype(jnp.float32)
    with jax.named_scope(SCOPE_IN_PROJ):
        h = _layer_norm(x, layer["ln1_w"], layer["ln1_b"],
                        config.layer_norm_eps)
        uz = qdot(h, layer["w_in"])
    with jax.named_scope(SCOPE_CONV):
        # u read from the projection itself, by the kernels' blocks
        u = causal_conv(uz, layer["conv_w"], segment_ids,
                        bias=layer["conv_b"], activation="silu")
    with jax.named_scope(SCOPE_IN_PROJ):
        # the scan's own inputs: the raw step (through its rank), B and C
        dbc = qdot(u, layer["w_x"])
        dt = qdot(dbc[..., :R], layer["w_dt"])
    with jax.named_scope(SCOPE_SCAN):
        y = selective_scan(
            u, dt, -jnp.exp(f32(layer["A_log"])), dbc[..., R:R + N],
            dbc[..., R + N:], f32(layer["D"]), f32(layer["dt_bias"]),
            segment_ids, chunk=config.scan_chunk, layer=index)
    with jax.named_scope(SCOPE_GATE):
        gated = (f32(y) * jax.nn.silu(f32(uz[..., d_in:]))).astype(x.dtype)
    with jax.named_scope(SCOPE_OUT_PROJ):
        return x + qdot(gated, layer["w_out"]), _memory(y, gated)


def _memory(y, gated):
    """What a Mamba layer hands to the gated memory units: the scan's
    output before the gate."""
    return y


def _gmu(x, layer, config: Phi4FlashConfig, memory):
    f32 = lambda a: a.astype(jnp.float32)
    h = _layer_norm(x, layer["ln1_w"], layer["ln1_b"], config.layer_norm_eps)
    gate = jax.nn.silu(f32(qdot(h, layer["w_1"])))
    return x + qdot((f32(memory) * gate).astype(x.dtype), layer["w_2"])


def _pairs(t, heads, hd):
    """[B, S, heads * hd] -> the even and the odd heads, [B, S, heads / 2,
    hd] each."""
    B, S, _ = t.shape
    t = t.reshape(B, S, heads // 2, 2, hd)
    return t[:, :, :, 0], t[:, :, :, 1]


def _value_pairs(v, heads, hd):
    """[B, S, heads * hd] -> [B, S, heads / 2, 2 hd]: ``[v_2g | v_2g+1]``."""
    B, S, _ = v.shape
    return v.reshape(B, S, heads // 2, 2 * hd)


def _window_of(kind, config):
    return config.sliding_window if kind == SWA else None


def _combine(a1, a2, layer, config, index):
    """``RMSNorm(a1 - lambda a2) (1 - lambda_init)`` a differential head,
    float32 -> the maps' dtype."""
    f32 = lambda a: a.astype(jnp.float32)
    init = lambda_init(index)
    lam = jnp.exp(jnp.sum(f32(layer["lambda_q1"]) * f32(layer["lambda_k1"]))) \
        - jnp.exp(jnp.sum(f32(layer["lambda_q2"])
                          * f32(layer["lambda_k2"]))) + init
    return (_rms_norm(f32(a1) - lam * f32(a2), f32(layer["subln"]),
                      config.subln_eps) * (1.0 - init)).astype(a1.dtype)


def _diff_attn(x, layer, config: Phi4FlashConfig, segment_ids, index, kind,
               kv=None):
    """-> (x + the mixer, (k, v) as the flash calls read them: ``k`` the
    even and the odd key heads, ``v`` [B, S, num_kv_heads / 2, 2
    head_dim])."""
    B, S, _ = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    with jax.named_scope(SCOPE_QKV):
        h = _layer_norm(x, layer["ln1_w"], layer["ln1_b"],
                        config.layer_norm_eps)
        if kind == CROSS:
            q = qdot(h, layer["w_q"]) + layer["b_q"].astype(x.dtype)
            (k1, k2), v = kv
        else:
            qkv = qdot(h, layer["w_qkv"]) + layer["b_qkv"].astype(x.dtype)
            q = qkv[..., :H * hd]
            k1, k2 = _pairs(qkv[..., H * hd:(H + KV) * hd], KV, hd)
            v = _value_pairs(qkv[..., (H + KV) * hd:], KV, hd)
        q1, q2 = _pairs(q, H, hd)
    with jax.named_scope(SCOPE_FLASH):
        attend = partial(
            causal_attention, impl=config.attention_impl,
            segment_ids=segment_ids, window=_window_of(kind, config),
            kv_of=config.kv_layer if kind == CROSS else None)
        a1, a2 = attend(q1, k1, v), attend(q2, k2, v)
    a1 = jax.ad_checkpoint.checkpoint_name(a1, "attn_out")
    a2 = jax.ad_checkpoint.checkpoint_name(a2, "attn_out")
    with jax.named_scope(SCOPE_COMBINE):
        o = _combine(a1, a2, layer, config, index).reshape(B, S, H * hd)
    with jax.named_scope(SCOPE_OUT_PROJ):
        out = x + qdot(o, layer["w_o"]) + layer["b_o"].astype(x.dtype)
    return out, ((k1, k2), v)


@jax.named_scope(SCOPE_BLOCK)
def _block(x, layer, shared, config: Phi4FlashConfig, index, kind,
           segment_ids):
    """Layer ``index``: -> (x, what it hands on: ``y`` of the Mamba layer
    the memory units read, ``(k, v)`` of the attention layer the cross
    layers read, else None)."""
    layer = maybe_stream(layer)
    if kind == MAMBA:
        with jax.named_scope(SCOPE_MAMBA):
            x, kept = _mamba(x, layer, config, segment_ids, index)
    elif kind == GMU:
        with jax.named_scope(SCOPE_GMU):
            x, kept = _gmu(x, layer, config, shared), None
    else:
        with jax.named_scope(SCOPE_DIFF_ATTN):
            x, kept = _diff_attn(x, layer, config, segment_ids, index, kind,
                                 kv=shared)
    if index not in (config.memory_layer, config.kv_layer):
        kept = None         # not an output: nothing keeps it for anyone
    return _mlp(x, layer, config), kept


def head_inputs(params, batch, config: Phi4FlashConfig) -> Head:
    """The head's inputs: the normed last hidden state, and the embedding
    table on its own axis."""
    refuse_param_stream("phi4flash", "a subtree a layer, walked unrolled")
    dtype = jnp.dtype(config.dtype)
    segment_ids = segment_ids_of(batch)
    x = embed_tokens(params["wte"], batch["input_ids"], dtype)
    memory = kv = None
    for index, kind in enumerate(config.kinds):
        block = partial(_block, config=config, index=index, kind=kind,
                        segment_ids=segment_ids)
        if config.remat:
            block = jax.checkpoint(
                block, policy=remat_policy(config.remat_policy))
        shared = {GMU: memory, CROSS: kv}.get(kind)
        x, kept = block(x, params["layers"][layer_name(index)], shared)
        if index == config.memory_layer:
            memory = kept
        elif index == config.kv_layer:
            kv = kept
    with jax.named_scope(SCOPE_HEAD_LOSS):
        return Head(_layer_norm(x, params["lnf_w"], params["lnf_b"],
                                config.layer_norm_eps),
                    params["wte"], tied=True)


def forward(params, batch, config: Phi4FlashConfig):
    return head_inputs(params, batch, config).logits()


def count_params(config: Phi4FlashConfig) -> int:
    return param_count(partial(init_params, config))


def _gradient_views(config: Phi4FlashConfig) -> dict:
    """Parts of leaves whose gradients arrive through later layers: the
    key and the value columns of the layer whose ``k``, ``v`` are read
    again (scripts/olmoe_grad_check.py gives each a row)."""
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    name = layer_name(config.kv_layer)
    w = lambda tree: tree["layers"][name]["w_qkv"]
    return {f"layers[{name}].w_qkv[keys]":
            lambda tree: w(tree)[:, H * hd:(H + KV) * hd],
            f"layers[{name}].w_qkv[values]":
            lambda tree: w(tree)[:, (H + KV) * hd:]}


def phi4flash_model(size: str = "mini-flash", **overrides) -> Model:
    config = Phi4FlashConfig(**{
        **resolve_size(PHI4FLASH_SIZES, size, "phi4flash"), **overrides})
    n_params = count_params(config)

    def apply(params, batch, rng=None):
        return forward(params, batch, config)

    def loss(params, batch, rng=None):
        return head_inputs(params, batch, config).token_loss(batch)

    def no_serving(what):
        def refuse(*_, **__):
            raise NotImplementedError(
                f"phi4flash: {what} is not built — serving this model "
                f"needs a decode cache that holds one layer's keys and "
                f"values for every cross layer, one Mamba layer's output "
                f"for the gated memory units and a Mamba-1 state (and "
                f"convolution history) a sequence, and a prefill that "
                f"stops before the cross-decoder (ROADMAP)")
        return refuse

    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=apply, loss_fn=loss,
        logical_specs=logical_specs(config),
        # the tied table multiplies once, as the head
        flops_per_token=6.0 * n_params,
        meta={"name": f"phi4flash-{size}", "n_params": n_params,
              "gradient_views": _gradient_views(config)},
        init_cache_fn=no_serving("init_cache"),
        prefill_fn=no_serving("prefill"),
        decode_fn=no_serving("decode"),
        verify_fn=no_serving("verify"),
    )
