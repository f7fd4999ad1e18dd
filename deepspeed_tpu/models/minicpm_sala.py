"""MiniCPM-SALA (huggingface.co/openbmb/MiniCPM-SALA, ``model_type:
minicpm_sala``, 2026-02, 9B): a dense decoder whose mixers are of two
kinds by a published list (``mixer_types``) — one layer of **attention over
the key blocks each query picks for itself** (``minicpm4``: InfLLM-V2,
MiniCPM4, arXiv:2506.07900 and arXiv:2509.24663; no positions) to three of
**Lightning linear attention** under rotary positions (``lightning-attn``:
Lightning Attention-2, arXiv:2401.04658) — over SwiGLU feed-forwards, with
the family's muP scalings on the embedding, the residual and the logits.

``N(x; w) = x / rms(x) * w``, eps ``norm_eps``.  ``h_0 = scale_emb *
E[ids]``; every sublayer ``h <- h + (scale_depth / sqrt(depth_scale_layers))
* f(N(h))`` with ``depth_scale_layers`` the **published** 32 whatever the
depth built; ``MLP(u) = W_down(silu(W_gate u) * W_up u)``; logits ``=
W_head N(h) / (d_model / dim_model_base)``; no bias; untied head.
``mup_denominator`` of the source is read by no layer.

**``lightning-attn``** (``lightning_heads`` heads of ``lightning_head_dim``
for q, k and v alike): ``q, k, v = W_q u, W_k u, W_v u``; ``N`` with a weight
over each head's width on q and k; rotary (``rope_theta``, the whole head,
halves paired) on q and k; per head a float32 state ``S`` [hd, hd], zero at
a document's first token:

    S_t = lambda_h S_{t-1} + k_t^T v_t      o_t = (q_t / sqrt(hd)) S_t
    lambda_h = exp(-s_h)      s_h = 2^(-8 h / heads), h = 1..heads

(ops/state_space.py ``lightning_attention``: the state-space scan at a
step of 1); ``y = W_o(sigmoid(W_g u) * N(o; w_o))``, that norm over the
joined heads' ``heads * hd``.

**``minicpm4``** (``num_heads`` query heads, ``num_kv_heads`` key/value
heads of ``head_dim``; query heads ``R g .. R g + R - 1`` read key/value
head ``g``, ``R = num_heads / num_kv_heads``): ``q, k, v`` as above with
``N`` on each head of q and k and **nothing rotated**.  Inside a document,
positions counted from its first token (ops/sparse_attention.py
``select_blocks`` / ``selected_attention``):

1. pooled keys ``K_j = mean(k[kernel_stride j : kernel_stride j +
   kernel_size])`` while the window lies inside the document;
2. ``p[h, t, :] = softmax_j(q[h, t] . K_j / sqrt(hd))`` over the windows
   that end at or before ``t`` (float32); ``a[g, t, j]`` its sum over the
   query heads of ``g``;
3. key block ``b`` = positions ``[block_size b, block_size (b + 1))``; its
   score is the maximum of ``a`` over the windows that touch it (``j = 4 b
   - 1 .. 4 b + 3`` at 64 / 32 / 16);
4. blocks ``b < init_blocks`` and the ``window_size / block_size`` blocks
   that end with the query's own score +inf; the ``topk`` highest blocks
   with ``b <= t // block_size`` are kept, the lower index on a tie; a
   query with at most ``topk`` causal blocks keeps them all, and so does
   every query of a document shorter than ``dense_len``;
5. ``o[h, t] = softmax_s(q[h, t] . k_s / sqrt(hd)) v_s`` over the keys ``s
   <= t`` of the kept blocks; ``y = W_o(sigmoid(W_g u) * o)``.

Steps 1-4 have no parameter and carry no gradient; the gradient is step
5's.

**Assumed** (the published ``config.json`` carries the widths and the
layer order, not these; benchmarks/configs/minicpm-sala.json ``assumed``
has each with the alternative not taken): the selection's seven numbers
(64, 32, 16, top-64, 1, 2048, 8192 — MiniCPM4's), an exact softmax in step
2 where the source's kernels estimate its normaliser from coarser windows,
``dense_len`` applied per document of a packed row, the slope rule
(without MiniMax-01's per-layer factor), the output norm over the joined
heads rather than a head, rotary positions that run on through a packed
row (relative inside a document either way), and the initialisation.

The layer loop is unrolled, a subtree a layer (``params["layers"]["07"]``):
the published order is irregular (runs of 8, 6, 4 and 6 Lightning layers,
adjacent sparse pairs).  Under ``remat`` a layer's mixer and its
feed-forward are rematerialised apart, and the feed-forward in tiles of
``mlp_token_tile`` tokens: at 16,384 tokens a 16,384-wide SwiGLU's three
activations are 0.5 GiB each.

Not built: serving (a recurrent state a Lightning layer beside a key/value
cache and a cache of pooled keys and selections for the sparse layers — the
entry points raise); a kernel that visits only the kept blocks (the attend
stage's kernels, ops/pallas/selected_attention.py, mask them on the tile and
leave out what causality does); ZeRO-3 and
parameter streaming (no stacked subtree); tensor parallelism (every leaf
is replicated over ``model``).
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import _rms_norm, rope
from deepspeed_tpu.models.model import (Head, Model, embed_tokens,
                                        maybe_stream, param_count, qdot,
                                        refuse_param_stream, remat_policy,
                                        resolve_size, segment_ids_of)
from deepspeed_tpu.ops.sparse_attention import (BlockSelection,
                                                select_blocks,
                                                selected_attention,
                                                selection_counts)
from deepspeed_tpu.ops.state_space import (lightning_attention,
                                           lightning_slopes)
from deepspeed_tpu.telemetry.tracing import (
    SCOPE_ATTEND, SCOPE_BLOCK, SCOPE_GATE_NORM, SCOPE_HEAD_LOSS,
    SCOPE_IN_PROJ, SCOPE_LIGHTNING, SCOPE_MLP, SCOPE_OUT_PROJ, SCOPE_QKV,
    SCOPE_ROPE, SCOPE_SCAN, SCOPE_SELECT, SCOPE_SPARSE_ATTN)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_LETTER = {SPARSE: "S", LIGHTNING: "L"}
#: the published order: 8 sparse layers to 24 of Lightning attention
MIXER_TYPES = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32))


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    max_seq_len: int = 524288
    num_layers: int = 32
    #: the kind of each layer's mixer; a depth cut keeps the first
    #: ``num_layers`` of them
    mixer_types: tuple = MIXER_TYPES
    d_model: int = 4096
    d_ff: int = 16384
    # the sparse layers
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    block_size: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    #: the attend stage's XLA form (where its kernels do not run): queries
    #: scored at a time, and in how many spans of growing key length a
    #: sequence is walked
    attend_query_chunk: int = 128
    attend_key_spans: int = 4
    # the Lightning layers
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    scan_chunk: int = 128
    # muP
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    #: the depth under the root of the residual's scale: the published
    #: model's, whatever ``num_layers`` is built
    depth_scale_layers: int = 32
    dim_model_base: int = 256
    norm_eps: float = 1e-6
    #: tokens of one rematerialised tile of the feed-forward (None: whole)
    mlp_token_tile: "int | None" = 4096
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing"

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        unknown = set(self.mixer_types) - set(_LETTER)
        if unknown or len(self.mixer_types) < self.num_layers:
            raise ValueError(
                f"minicpm-sala: mixer_types names {len(self.mixer_types)} "
                f"layers for {self.num_layers}, kinds {sorted(unknown)} "
                f"unknown (known: {sorted(_LETTER)})")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"minicpm-sala: {self.num_heads} query heads over "
                f"{self.num_kv_heads} key/value heads")
        self.selection  # refuses numbers that do not fit each other

    @property
    def kinds(self) -> tuple:
        return self.mixer_types[:self.num_layers]

    @property
    def layer_kinds(self) -> str:
        """A letter a layer: ``S`` sparse attention, ``L`` Lightning."""
        return "".join(_LETTER[kind] for kind in self.kinds)

    @property
    def selection(self) -> BlockSelection:
        return BlockSelection(
            block_size=self.block_size, kernel_size=self.kernel_size,
            kernel_stride=self.kernel_stride, topk=self.topk,
            init_blocks=self.init_blocks, window_size=self.window_size,
            dense_len=self.dense_len)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth_scale_layers)


MINICPM_SALA_SIZES = {
    "tiny": dict(vocab_size=256, max_seq_len=128, num_layers=4,
                 mixer_types=(SPARSE, LIGHTNING, LIGHTNING, LIGHTNING),
                 d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                 head_dim=16, block_size=4, kernel_size=2, kernel_stride=1,
                 topk=4, init_blocks=1, window_size=8, dense_len=32,
                 attend_query_chunk=16, attend_key_spans=2,
                 lightning_heads=4, lightning_head_dim=16, scan_chunk=16,
                 mlp_token_tile=32),
    # huggingface.co/openbmb/MiniCPM-SALA config.json: the defaults above.
    # 9.5B parameters whole; one chip trains the first four layers at an
    # eighth of the vocabulary (benchmarks/configs)
    "9b": dict(),
}


# ------------------------------------------------------------- parameters
def _init_layer(config: MiniCPMSALAConfig, kind: str, rng) -> dict:
    D, F = config.d_model, config.d_ff
    k = iter(jax.random.split(rng, 8))
    norm = lambda shape: jax.random.normal(next(k), shape, jnp.float32) * 0.02
    if kind == SPARSE:
        H, G, hd = config.num_heads, config.num_kv_heads, config.head_dim
        mixer = {"w_k": norm((D, G * hd)), "w_v": norm((D, G * hd))}
    else:
        H = G = config.lightning_heads
        hd = config.lightning_head_dim
        mixer = {"w_k": norm((D, H * hd)), "w_v": norm((D, H * hd)),
                 "o_norm": jnp.ones((H * hd,))}
    return {
        "attn_norm": jnp.ones((D,)),
        "w_q": norm((D, H * hd)), **mixer,
        "q_norm": jnp.ones((hd,)), "k_norm": jnp.ones((hd,)),
        "w_g": norm((D, H * hd)), "w_o": norm((H * hd, D)),
        "mlp_norm": jnp.ones((D,)),
        "w_gate": norm((D, F)), "w_up": norm((D, F)),
        "w_down": norm((F, D)),
    }


def layer_name(l: int) -> str:
    return f"{l:02d}"


def init_params(config: MiniCPMSALAConfig, rng) -> dict:
    """Seeded.  Assumed (the published config says ``rand_init: false`` and
    nothing more): every matrix normal of std 0.02, norm weights 1."""
    keys = jax.random.split(rng, config.num_layers + 2)
    V, D = config.vocab_size, config.d_model
    return {
        "wte": jax.random.normal(keys[-1], (V, D), jnp.float32) * 0.02,
        "layers": {layer_name(l): _init_layer(config, kind, keys[l])
                   for l, kind in enumerate(config.kinds)},
        "final_norm": jnp.ones((D,)),
        "lm_head": jax.random.normal(keys[-2], (D, V), jnp.float32) * 0.02,
    }


def logical_specs(config: MiniCPMSALAConfig) -> dict:
    """Every leaf replicated over ``model``: tensor parallelism of the
    selection (a vote over a group's query heads) and of the scan's heads
    is not built."""
    shapes = jax.eval_shape(partial(init_params, config),
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda _: P(), shapes)


# ------------------------------------------------------------------ mixers
def _heads(t, heads, hd):
    return t.reshape(t.shape[:2] + (heads, hd))


def _gate(h, layer):
    """``sigmoid(W_g u)`` in float32."""
    return jax.nn.sigmoid(qdot(h, layer["w_g"]).astype(jnp.float32))


def sparse_qkv(x, layer, config: MiniCPMSALAConfig):
    """-> (``N(x)``, q [B, S, H, hd], k, v [B, S, G, hd]) of a ``minicpm4``
    layer, q and k normalised a head: what the selection and the attention
    both read."""
    H, G, hd = config.num_heads, config.num_kv_heads, config.head_dim
    eps = config.norm_eps
    h = _rms_norm(x, layer["attn_norm"], eps)
    return (h,
            _rms_norm(_heads(qdot(h, layer["w_q"]), H, hd),
                      layer["q_norm"], eps),
            _rms_norm(_heads(qdot(h, layer["w_k"]), G, hd),
                      layer["k_norm"], eps),
            _heads(qdot(h, layer["w_v"]), G, hd))


def sparse_mixer(x, layer, config: MiniCPMSALAConfig, segment_ids):
    """The ``minicpm4`` branch alone (the caller adds ``x``); the caller's
    scope is ``sparse_attn``."""
    H, hd, sel = config.num_heads, config.head_dim, config.selection
    with jax.named_scope(SCOPE_QKV):
        h, q, k, v = sparse_qkv(x, layer, config)
    with jax.named_scope(SCOPE_SELECT):
        blocks, _ = select_blocks(q, k, segment_ids, sel)
    with jax.named_scope(SCOPE_ATTEND):
        o = selected_attention(q, k, v, blocks, segment_ids, sel,
                               query_chunk=config.attend_query_chunk,
                               key_spans=config.attend_key_spans)
    o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope(SCOPE_OUT_PROJ):
        gated = (_gate(h, layer) * o.reshape(x.shape[:2] + (H * hd,))
                 .astype(jnp.float32)).astype(x.dtype)
        return qdot(gated, layer["w_o"])


def lightning_mixer(x, layer, config: MiniCPMSALAConfig, segment_ids):
    """The ``lightning-attn`` branch alone; the caller's scope is
    ``lightning``."""
    H, hd = config.lightning_heads, config.lightning_head_dim
    eps = config.norm_eps
    with jax.named_scope(SCOPE_IN_PROJ):
        h = _rms_norm(x, layer["attn_norm"], eps)
        # the scale of o = (q / sqrt(hd)) S rides on the norm's weight, in
        # float32 before q is rounded: rotary is linear
        q = _rms_norm(_heads(qdot(h, layer["w_q"]), H, hd),
                      layer["q_norm"].astype(jnp.float32) * hd ** -0.5, eps)
        k = _rms_norm(_heads(qdot(h, layer["w_k"]), H, hd),
                      layer["k_norm"], eps)
        v = _heads(qdot(h, layer["w_v"]), H, hd)
        gate = _gate(h, layer)
    with jax.named_scope(SCOPE_ROPE):
        q, k = rope(q, config.rope_theta), rope(k, config.rope_theta)
    with jax.named_scope(SCOPE_SCAN):
        o = lightning_attention(q, k, v, lightning_slopes(H), segment_ids,
                                chunk=config.scan_chunk)
    o = jax.ad_checkpoint.checkpoint_name(o, "attn_out")
    with jax.named_scope(SCOPE_GATE_NORM):
        y = (gate * _rms_norm(o.reshape(x.shape[:2] + (H * hd,)),
                              layer["o_norm"], eps).astype(jnp.float32)
             ).astype(x.dtype)
    with jax.named_scope(SCOPE_OUT_PROJ):
        return qdot(y, layer["w_o"])


@jax.named_scope(SCOPE_BLOCK)
def _mixed(x, layer, config: MiniCPMSALAConfig, kind, segment_ids):
    """``x + residual_scale * Mixer(N(x))`` of a layer of ``kind``."""
    layer = maybe_stream(layer)
    scope, mixer = {SPARSE: (SCOPE_SPARSE_ATTN, sparse_mixer),
                    LIGHTNING: (SCOPE_LIGHTNING, lightning_mixer)}[kind]
    with jax.named_scope(scope):
        out = mixer(x, layer, config, segment_ids)
        with jax.named_scope(SCOPE_OUT_PROJ):
            return x + (config.residual_scale * out).astype(x.dtype)


@jax.named_scope(SCOPE_BLOCK)
def _fed_forward(x, layer, config: MiniCPMSALAConfig):
    """``x + residual_scale * MLP(N(x))`` for any leading shape of
    tokens."""
    layer = maybe_stream(layer)
    with jax.named_scope(SCOPE_MLP):
        h = _rms_norm(x, layer["mlp_norm"], config.norm_eps)
        out = qdot(jax.nn.silu(qdot(h, layer["w_gate"]))
                   * qdot(h, layer["w_up"]), layer["w_down"])
        return x + (config.residual_scale * out).astype(x.dtype)


def _layer_fn(config: MiniCPMSALAConfig, kind, segment_ids):
    """``fn(x, layer)`` of one layer.  Under ``remat`` its mixer and its
    feed-forward are rematerialised apart (a layer keeps ``x`` and the
    mixer's output), and the feed-forward in tiles of ``mlp_token_tile``
    tokens, each its own rematerialised call: its backward recomputes one
    tile's three ``[tile, d_ff]`` activations at a time, once."""
    mix = partial(_mixed, config=config, kind=kind, segment_ids=segment_ids)
    feed = partial(_fed_forward, config=config)
    if not config.remat:
        return lambda x, layer: feed(mix(x, layer), layer)
    keep = partial(jax.checkpoint, policy=remat_policy(config.remat_policy))
    mix, feed = keep(mix), keep(feed)

    def fn(x, layer):
        x = mix(x, layer)
        tile = config.mlp_token_tile
        tokens = x.shape[0] * x.shape[1]
        if not tile or tokens <= tile or tokens % tile:
            return feed(x, layer)
        tiles = lax.map(lambda t: feed(t, layer),
                        x.reshape(tokens // tile, tile, x.shape[-1]))
        return tiles.reshape(x.shape)
    return fn


def embedded(params, batch, config: MiniCPMSALAConfig):
    """``scale_emb * E[ids]`` in the model's dtype."""
    dtype = jnp.dtype(config.dtype)
    x = embed_tokens(params["wte"], batch["input_ids"], dtype)
    return (x.astype(jnp.float32) * config.scale_emb).astype(dtype)


def head_inputs(params, batch, config: MiniCPMSALAConfig) -> Head:
    """The head's inputs: the normed last hidden state under its muP
    scale, and the head."""
    refuse_param_stream("minicpm-sala", "a subtree a layer, walked unrolled")
    segment_ids = segment_ids_of(batch)
    x = embedded(params, batch, config)
    for index, kind in enumerate(config.kinds):
        x = _layer_fn(config, kind, segment_ids)(
            x, params["layers"][layer_name(index)])
    with jax.named_scope(SCOPE_HEAD_LOSS):
        x = _rms_norm(x, params["final_norm"], config.norm_eps)
        return Head(x * (config.dim_model_base / config.d_model),
                    params["lm_head"])


def forward(params, batch, config: MiniCPMSALAConfig):
    return head_inputs(params, batch, config).logits()


def sparse_counts(params, batch, config: MiniCPMSALAConfig):
    """What the first sparse layer's selection adds up to for this batch
    (ops/sparse_attention.py ``selection_counts``: blocks kept and keys
    required a query, the share of queries in documents under
    ``dense_len``) — the counts that depend on the data, which the step's
    static account cannot hold.  A diagnostic: the layers up to that one
    written out."""
    seg = segment_ids_of(batch)
    x = embedded(params, batch, config)
    for index, kind in enumerate(config.kinds):
        layer = params["layers"][layer_name(index)]
        if kind == SPARSE:
            _, q, k, _ = sparse_qkv(x, layer, config)
            blocks, count = select_blocks(q, k, seg, config.selection)
            return selection_counts(blocks, count, seg, config.selection)
        x = _fed_forward(_mixed(x, layer, config, kind, seg), layer, config)
    raise ValueError("minicpm-sala: no sparse layer among "
                     f"{config.layer_kinds}")


def count_params(config: MiniCPMSALAConfig) -> int:
    return param_count(partial(init_params, config))


def minicpm_sala_model(size: str = "9b", **overrides) -> Model:
    config = MiniCPMSALAConfig(**{
        **resolve_size(MINICPM_SALA_SIZES, size, "minicpm_sala"),
        **overrides})
    n_params = count_params(config)

    def apply(params, batch, rng=None):
        return forward(params, batch, config)

    def loss(params, batch, rng=None):
        return head_inputs(params, batch, config).token_loss(batch)

    def no_serving(what):
        def refuse(*_, **__):
            raise NotImplementedError(
                f"minicpm-sala: {what} is not built — serving this model "
                f"needs a float32 state (heads x hd x hd) a sequence for "
                f"the Lightning layers beside a paged key/value cache, the "
                f"pooled keys and each sequence's kept blocks for the "
                f"sparse ones (ROADMAP)")
        return refuse

    return Model(
        config=config,
        init_fn=partial(init_params, config),
        apply_fn=apply, loss_fn=loss,
        logical_specs=logical_specs(config),
        # the untied embedding is a lookup; the head multiplies
        flops_per_token=6.0 * (n_params
                               - config.vocab_size * config.d_model),
        meta={"name": f"minicpm-sala-{size}", "n_params": n_params,
              "sparse_counts": lambda p, b: sparse_counts(p, b, config)},
        init_cache_fn=no_serving("init_cache"),
        prefill_fn=no_serving("prefill"),
        decode_fn=no_serving("decode"),
        verify_fn=no_serving("verify"),
    )
