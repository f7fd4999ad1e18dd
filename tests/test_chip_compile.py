"""Compiles for a TPU v5e that is described, not attached (the chip's
compiler is installed on CPU-only boxes): the training path's kernels at
GPT-2 760M width, the grouped GEMM kernels at OLMoE-1B-7B's (their
weight panels resident in VMEM) and at Mixtral-8x7B's, the flash kernels at JoyAI-LLM-Flash's two head widths (a
192-wide score, a 128-wide value) and the grouped ones at its 768-wide
experts, the gated delta rule's two at Qwen3-Next's and its two with a
decay a key channel at Kimi-Linear's (one packed sequence of 16,384), the state-space scan's two at Nemotron-H's
and the short causal convolution's two at both hybrids' (each in its
orientation), the selected-block attention's three at MiniCPM-SALA's (32
query heads to 2, 64 kept blocks a token, one packed sequence of 16,384),
the selective scan's two and the flash kernels at a 64-wide
score and a 128-wide value head at Phi-4-mini-flash's (one packed sequence
of 16,384) go through Mosaic, AdamW's update of Granite's and Nemotron-H's
stacked expert leaves is one fusion over the donated state, the head and
its loss at Phi-4-mini-flash's and GPT-2 760M's shapes hold a chunk of
logits and never the whole, the
data-sharded flash kernel goes through the partitioner, and the library
knows the chip's peaks.

A compile that passes is not a chip run — it says nothing about results
or times.  ``chip_smoke.py`` is the run."""
import os
import subprocess
import sys

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = "tpu_custom_call"
B, S, H, HD = 12, 1024, 16, 96          # gpt2-760m micro-batch 12, seq 1024


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a described v5e 2x2 host.  Executables built
    for them land in the persistent cache but cannot be read back without
    a chip, so the cache is off while this module runs."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:      # no libtpu / no topology support here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield devices
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _arg(dev, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(dev))


def _sum_sq(fn):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)


def _ds_flash(q, k, v):
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    return ds_flash_attention(q, k, v, causal=True)


def _ds_flash_packed(q, k, v, seg):
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    return ds_flash_attention(q, k, v, segment_ids=seg, causal=True)


def _ds_flash_windowed(q, k, v, seg):
    """As the dispatch calls it (ops/attention.py): a 512-key window at
    the blocks it chose."""
    from deepspeed_tpu.ops.attention import WINDOW_BLOCKS
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    return ds_flash_attention(q, k, v, segment_ids=seg, causal=True,
                              window=512, block_q=WINDOW_BLOCKS[0],
                              block_k=WINDOW_BLOCKS[1])


def _stock_flash(q, k, v):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=True)


def _quantize(x):
    from deepspeed_tpu.ops.pallas.quantization import _pallas_quantize_2d
    return _pallas_quantize_2d(x)


def _qgemm(x, q, s):
    from deepspeed_tpu.ops.pallas.qgemm import ds_qgemm
    return ds_qgemm(x, q, s, interpret=False)


def _decode(q, k, v, n, ks=None, vs=None):
    from deepspeed_tpu.ops.pallas.decode_attention import \
        decode_attention_pallas
    return decode_attention_pallas(q, k, v, n, k_scale=ks, v_scale=vs)


def _ggemm(x, w, gids, used, blocks=None, live_only=False):
    """The differentiable grouped GEMM as moe/layer.py's training path
    calls it (ds_ggemm with the reference switched off: no TPU here),
    with the blocks the library chooses for a v5e."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    return gg._ggemm_diff(x, w, (gids, used), gg.DEFAULT_BLOCK_M,
                          w.shape[0], blocks, False, live_only)


def _ggemm_held(x, w, gids, used):
    """... over a held plan (``live_only``): a trailing tile's grid steps
    stay on the last live tile's output block and write nothing."""
    return _ggemm(x, w, gids, used, live_only=True)


def _ggemm_streamed(x, w, gids, used):
    """The K-innermost tiling, asked for as DS_GGEMM_BLOCKS would."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    return _ggemm(x, w, gids, used, gg._BLOCKS_KN)


def _gdr(q, k, v, g, beta, seg):
    """The delta rule's kernels as ops/linear_attention.py calls them on
    one TPU (the choice switched off: no TPU here), with the blocking the
    library chooses."""
    from deepspeed_tpu.ops.pallas import gated_delta_rule as gdr
    (B, S, Hk, dk), (Hv, dv) = q.shape, v.shape[2:]
    blocking = gdr.chunks_per_step(S // 64, 64, Hv // Hk, dk, dv,
                                   v.dtype.itemsize)
    return gdr.gated_delta_rule_kernels(q, k, v, g, beta, seg, blocking,
                                        scales=(dk ** -0.5, 1.0))


def _kda(q, k, v, g, beta, seg):
    """The same rule with a decay a key channel (``g`` of rank four):
    ops/pallas/kda.py's kernels, with the blocking the library chooses."""
    from deepspeed_tpu.ops.pallas import kda
    (B, S, H, dk), dv = q.shape, v.shape[3]
    blocking = kda.chunks_per_step(S // 64, 64, 1, dk, dv, v.dtype.itemsize)
    return kda.kda_kernels(q, k, v, g, beta, seg, blocking,
                           scales=(dk ** -0.5, 1.0))


def _ssd(x, dt, A, Bm, Cm, D, seg):
    """The state-space scan's kernels as ops/state_space.py calls them on
    one TPU (the choice switched off: no TPU here), with the blocking the
    library chooses."""
    from deepspeed_tpu.ops.pallas import state_space as ssd
    (b, S, H, P), (G, N) = x.shape, Bm.shape[2:]
    blocking = ssd.chunks_per_step(S // 128, 128, H // G, P, N,
                                   x.dtype.itemsize)
    return ssd.ssd_kernels(x, dt, A, Bm, Cm, D, seg, blocking)


def _sel(q, k, v, blocks, seg):
    """The sparse layer's attend stage as ops/sparse_attention.py calls it
    on one TPU (the choice switched off: no TPU here): the mask's operands
    made from ``blocks`` and the three kernels, at the device kind's
    tiles."""
    from deepspeed_tpu.ops import sparse_attention as sa
    from deepspeed_tpu.ops.pallas import selected_attention as sel
    (B, S, H, hd), G = q.shape, k.shape[2]
    chosen = sa.BlockSelection()
    tiles = sel.blocking(S, H // G, hd, chosen.block_size, q.dtype.itemsize)
    return sel.selected_attention_kernels(
        q, k, v, *sa.mask_operands(blocks, seg, chosen, q.dtype), tiles)


def _sscan(u, dt, A, Bm, Cm, D, bias, first):
    """The selective scan's kernels as ops/selective_scan.py calls them on
    one TPU (the choice switched off: no TPU here), with the blocking the
    library chooses."""
    from deepspeed_tpu.ops.pallas import selective_scan as sscan
    blocking = sscan.blocking(u.shape[2], A.shape[1], 128, u.dtype.itemsize)
    return sscan.sscan_kernels(u, dt, A, Bm, Cm, D, bias, first, blocking)


def _conv(positions, first=0):
    """The causal convolution's kernels as ops/linear_attention.py calls
    them on one TPU (the choice switched off: no TPU here), with the slab
    the library chooses, bias and silu inside; ``x`` from its channel
    ``first`` on, as wide as the weights."""
    def conv(x, w, bias, seg):
        from deepspeed_tpu.ops.pallas import causal_conv as cc
        blocking = cc.slab_width(x.shape[1], w.shape[1], x.dtype.itemsize,
                                 positions, first)
        return cc.causal_conv_kernels(x, w, seg, bias, "silu", blocking,
                                      first)
    return conv


def _ggemm_args(experts, rows, k, n):
    return [((rows, k), jnp.bfloat16), ((experts, k, n), jnp.bfloat16),
            ((rows // 128,), jnp.int32), ((1,), jnp.int32)]


# olmoe-1b-7b.packed-s4096-gas8: 4096 tokens x 8 choices = 32,768 routed
# rows over 64 experts, padded to 32,768 + 64 * 128; D 2048 -> F 1024
# (gate, up) and back (down)
GG_E, GG_ROWS, GG_D, GG_F = 64, 32768 + 64 * 128, 2048, 1024
_GGEMM = _ggemm_args(GG_E, GG_ROWS, GG_D, GG_F)
_GGEMM_DOWN = _ggemm_args(GG_E, GG_ROWS, GG_F, GG_D)
# mixtral-8x7b's gate projection, a prefill of 4096 tokens x 2 choices
_GGEMM_MIXTRAL = _ggemm_args(8, 8192 + 8 * 128, 4096, 14336)
# qwen3-next-80b-a3b.packed-s8192-gas2: 32 experts held of 512, a plan of
# held_rows_bound 20,480 + 32 * 128 rows; D 2048 -> F 512 and back
_GGEMM_HELD = _ggemm_args(32, 20480 + 32 * 128, 2048, 512)
_GGEMM_HELD_DOWN = _ggemm_args(32, 20480 + 32 * 128, 512, 2048)
# ... and its one full-attention layer: S 8192 packed, 16 query heads to 2
# KV heads of width 256 — a working set of 29 MB, over what Mosaic grants
# unasked, so the calls raise their VMEM limit
_QKV_GQA_8K = [((2, 8192, 16, 256), jnp.bfloat16),
               ((2, 8192, 2, 256), jnp.bfloat16),
               ((2, 8192, 2, 256), jnp.bfloat16), ((2, 8192), jnp.int32)]
# ... and its three delta-rule layers: 16 key / 32 value heads of 128,
# the state 128 x 128 float32 per value head, 128 chunks of 64
_GDR_8K = [((2, 8192, 16, 128), jnp.bfloat16)] * 2 + [
    ((2, 8192, 32, 128), jnp.bfloat16), ((2, 8192, 32), jnp.float32),
    ((2, 8192, 32), jnp.float32), ((2, 8192), jnp.int32)]
# kimi-linear-48b-a3b.packed-s16384-traces' six delta-rule layers: 32 heads
# of 128, a decay a key channel, 256 chunks of 64 of one packed sequence
_KDA_16K = [((1, 16384, 32, 128), jnp.bfloat16)] * 3 + [
    ((1, 16384, 32, 128), jnp.float32), ((1, 16384, 32), jnp.float32),
    ((1, 16384), jnp.int32)]
# nemotron-3-nano-30b-a3b.packed-s8192-gas2: 8 experts held of 128, 16,384
# tokens x 6 choices, a plan of held_rows_bound 24,576 (four times the even
# share) + 8 * 128 rows; D 2688 -> F 1856 = 14.5 x 128 and back: one block
# spans 1856, nothing padded
_GGEMM_RELU2 = _ggemm_args(8, 24576 + 8 * 128, 2688, 1856)
_GGEMM_RELU2_DOWN = _ggemm_args(8, 24576 + 8 * 128, 1856, 2688)
# ... and its one attention layer: 32 query heads to 2 KV heads of 128
_QKV_GQA16_8K = [((2, 8192, 32, 128), jnp.bfloat16),
                 ((2, 8192, 2, 128), jnp.bfloat16),
                 ((2, 8192, 2, 128), jnp.bfloat16), ((2, 8192), jnp.int32)]
# ... and its four Mamba-2 layers: 64 heads of 64 in 8 groups of state 128,
# a group's state 128 x 512 float32, 64 chunks of 128
_SSD_8K = [((2, 8192, 64, 64), jnp.bfloat16), ((2, 8192, 64), jnp.float32),
           ((64,), jnp.float32), ((2, 8192, 8, 128), jnp.bfloat16),
           ((2, 8192, 8, 128), jnp.bfloat16), ((64,), jnp.float32),
           ((2, 8192), jnp.int32)]
# minicpm-sala.packed-s16384-longdocs: Lightning attention is the same scan
# at one group a head — 32 heads of 128 with a state of 128 (v, a step of
# 1, the slopes, k, q)
_SSD_LIGHTNING_16K = [
    ((1, 16384, 32, 128), jnp.bfloat16), ((1, 16384, 32), jnp.float32),
    ((32,), jnp.float32), ((1, 16384, 32, 128), jnp.bfloat16),
    ((1, 16384, 32, 128), jnp.bfloat16), ((32,), jnp.float32),
    ((1, 16384), jnp.int32)]
# granite-4.0-h-small.packed-s4096-gas1: a chip's 16 of 128 Mamba-2 heads
# over the ONE group's B and C (r = 16 heads a group, twice Nemotron-H's 8)
_SSD_ONE_GROUP_4K = [
    ((1, 4096, 16, 64), jnp.bfloat16), ((1, 4096, 16), jnp.float32),
    ((16,), jnp.float32), ((1, 4096, 1, 128), jnp.bfloat16),
    ((1, 4096, 1, 128), jnp.bfloat16), ((16,), jnp.float32),
    ((1, 4096), jnp.int32)]
# joyai-llm-flash.packed-s8192-gas2: latent attention, 32 heads (no
# grouping), a score head of 128 + 64 = 192 = 1.5 lane tiles and a value
# head of 128: v, o, do and dv are 128 wide in HBM, nothing padded to 192
_QKV_MLA_8K = [((2, 8192, 32, 192), jnp.bfloat16),
               ((2, 8192, 32, 192), jnp.bfloat16),
               ((2, 8192, 32, 128), jnp.bfloat16), ((2, 8192), jnp.int32)]
# ... and its five expert blocks: 16 experts held of 256, 16,384 tokens x 8
# choices, a plan of held_rows_bound 131,072 (sixteen times the even
# share: every routed row) + 16 * 128 rows; D 2048 -> F 768 = 6 x 128
# (gate, up) and back (down)
_GGEMM_W768 = _ggemm_args(16, 131072 + 16 * 128, 2048, 768)
_GGEMM_W768_DOWN = _ggemm_args(16, 131072 + 16 * 128, 768, 2048)
# Laguna-S-2.1's cell: S 8192 packed, micro-batch 1; a sliding layer's 72
# query heads to 8 KV heads (9 to a group, window 512) and a full layer's 48
# (6 to a group), head 128 ...
_QKV_GQA9_8K = [((1, 8192, 72, 128), jnp.bfloat16),
                ((1, 8192, 8, 128), jnp.bfloat16),
                ((1, 8192, 8, 128), jnp.bfloat16), ((1, 8192), jnp.int32)]
# phi-4-mini-flash-reasoning.packed-s16384-traces: one map of a differential
# layer — 20 query heads to 10, a score head of 64 (half a lane tile) and a
# value head of 128: wider values than keys — and a Mamba-1 layer's scan:
# 5,120 channels, 16 states
_QKV_DIFF_16K = [((1, 16384, 20, 64), jnp.bfloat16),
                 ((1, 16384, 10, 64), jnp.bfloat16),
                 ((1, 16384, 10, 128), jnp.bfloat16), ((1, 16384), jnp.int32)]
_SSCAN_16K = [((1, 16384, 5120), jnp.bfloat16),
              ((1, 16384, 5120), jnp.bfloat16), ((5120, 16), jnp.float32),
              ((1, 16384, 16), jnp.bfloat16), ((1, 16384, 16), jnp.bfloat16),
              ((5120,), jnp.float32), ((5120,), jnp.float32),
              ((1, 16384), jnp.bool_)]
# minicpm-sala.packed-s16384-longdocs: a sparse layer's 32 query heads to 2
# key/value heads of 128, each (token, key/value head) keeping 64 blocks
_SEL_16K = [((1, 16384, 32, 128), jnp.bfloat16),
            ((1, 16384, 2, 128), jnp.bfloat16),
            ((1, 16384, 2, 128), jnp.bfloat16),
            ((1, 2, 16384, 64), jnp.int32), ((1, 16384), jnp.int32)]
_QKV_GQA6_8K = [((1, 8192, 48, 128), jnp.bfloat16),
                ((1, 8192, 8, 128), jnp.bfloat16),
                ((1, 8192, 8, 128), jnp.bfloat16), ((1, 8192), jnp.int32)]
# ... and its four expert layers: 8 experts held of 256, 8,192 tokens x 10
# choices, a plan of held_rows_bound 66,560 (26 times the even share: every
# row 8 experts can be sent) + 8 * 128 rows; D 3072 -> F 1024 and back
_GGEMM_W1024 = _ggemm_args(8, 66560 + 8 * 128, 3072, 1024)
_GGEMM_W1024_DOWN = _ggemm_args(8, 66560 + 8 * 128, 1024, 3072)
# the short causal convolution of both hybrids' mixers, packed, S 8192:
# Qwen3-Next's q | k | v (8192 channels, positions down sublanes) and
# Nemotron-H's x | B | C (6144 channels, positions along lanes) whole, and
# as the models call it: v (4096 channels from 4096 on of the projection's
# 12,288) and x (4096 from 4096 on of 10,304 = 80.5 lane tiles)
def _conv_args(width, channels):
    return [((2, 8192, width), jnp.bfloat16), ((4, channels), jnp.bfloat16),
            ((channels,), jnp.bfloat16), ((2, 8192), jnp.int32)]


_QKV = [((B, S, H, HD), jnp.bfloat16)] * 3
_CACHE = (8, 1024, 16, 96)
KERNEL_CASES = {
    "ds_flash_fwd": (_ds_flash, _QKV),
    "ds_flash_fwd_bwd": (jax.grad(_sum_sq(_ds_flash), (0, 1, 2)), _QKV),
    "ds_ggemm_fwd": (_ggemm, _GGEMM),
    "ds_ggemm_fwd_bwd": (jax.grad(_sum_sq(_ggemm), (0, 1)), _GGEMM),
    "ds_ggemm_down_fwd": (_ggemm, _GGEMM_DOWN),
    "ds_ggemm_down_fwd_bwd": (jax.grad(_sum_sq(_ggemm), (0, 1)),
                              _GGEMM_DOWN),
    "ds_ggemm_mixtral_fwd_bwd": (jax.grad(_sum_sq(_ggemm), (0, 1)),
                                 _GGEMM_MIXTRAL),
    "ds_ggemm_held_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                              _GGEMM_HELD),
    "ds_ggemm_held_down_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                                   _GGEMM_HELD_DOWN),
    "ds_ggemm_relu2_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                               _GGEMM_RELU2),
    "ds_ggemm_relu2_down_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                                    _GGEMM_RELU2_DOWN),
    "ds_flash_gqa_s8192_hd256_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_packed), (0, 1, 2)), _QKV_GQA_8K),
    "ds_flash_gqa16_s8192_hd128_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_packed), (0, 1, 2)), _QKV_GQA16_8K),
    "ds_flash_mla_s8192_dk192_dv128_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_packed), (0, 1, 2)), _QKV_MLA_8K),
    "ds_flash_win512_gqa9_s8192_hd128_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_windowed), (0, 1, 2)), _QKV_GQA9_8K),
    "ds_flash_gqa6_s8192_hd128_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_packed), (0, 1, 2)), _QKV_GQA6_8K),
    "ds_flash_diff_s16384_dk64_dv128_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_packed), (0, 1, 2)), _QKV_DIFF_16K),
    "ds_flash_win512_diff_s16384_dk64_dv128_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ds_flash_windowed), (0, 1, 2)), _QKV_DIFF_16K),
    "ds_sel_s16384_r16_packed_fwd": (_sel, _SEL_16K),
    "ds_sel_s16384_r16_packed_fwd_bwd": (
        jax.grad(_sum_sq(_sel), (0, 1, 2)), _SEL_16K),
    "ds_sscan_s16384_packed_fwd": (_sscan, _SSCAN_16K),
    "ds_sscan_s16384_packed_fwd_bwd": (
        jax.grad(_sum_sq(_sscan), (0, 1, 2, 3, 4, 5, 6)), _SSCAN_16K),
    "ds_ggemm_w1024_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                               _GGEMM_W1024),
    "ds_ggemm_w1024_down_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                                    _GGEMM_W1024_DOWN),
    "ds_ggemm_w768_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                              _GGEMM_W768),
    "ds_ggemm_w768_down_fwd_bwd": (jax.grad(_sum_sq(_ggemm_held), (0, 1)),
                                   _GGEMM_W768_DOWN),
    "ds_ggemm_mixtral_streamed_fwd_bwd": (
        jax.grad(_sum_sq(_ggemm_streamed), (0, 1)), _GGEMM_MIXTRAL),
    "ds_gdr_s8192_packed_fwd": (_gdr, _GDR_8K),
    "ds_gdr_s8192_packed_fwd_bwd": (
        jax.grad(_sum_sq(_gdr), (0, 1, 2, 3, 4)), _GDR_8K),
    "ds_kda_s16384_packed_fwd": (_kda, _KDA_16K),
    "ds_kda_s16384_packed_fwd_bwd": (
        jax.grad(_sum_sq(_kda), (0, 1, 2, 3, 4)), _KDA_16K),
    "ds_ssd_s8192_packed_fwd": (_ssd, _SSD_8K),
    "ds_ssd_s8192_packed_fwd_bwd": (
        jax.grad(_sum_sq(_ssd), (0, 1, 2, 3, 4, 5)), _SSD_8K),
    "ds_ssd_s16384_one_head_a_group_fwd_bwd": (
        jax.grad(_sum_sq(_ssd), (0, 3, 4)), _SSD_LIGHTNING_16K),
    "ds_ssd_s4096_sixteen_heads_a_group_fwd_bwd": (
        jax.grad(_sum_sq(_ssd), (0, 1, 2, 3, 4, 5)), _SSD_ONE_GROUP_4K),
    "ds_conv_sublanes_s8192_packed_fwd": (_conv("sublanes"),
                                          _conv_args(8192, 8192)),
    "ds_conv_sublanes_s8192_packed_part_fwd_bwd": (
        jax.grad(_sum_sq(_conv("sublanes", 4096)), (0, 1, 2)),
        _conv_args(12288, 4096)),
    "ds_conv_lanes_s8192_packed_fwd": (_conv("lanes"),
                                       _conv_args(6144, 6144)),
    "ds_conv_lanes_s8192_packed_part_fwd_bwd": (
        jax.grad(_sum_sq(_conv("lanes", 4096)), (0, 1, 2)),
        _conv_args(10304, 4096)),
    "stock_flash_fwd": (_stock_flash, _QKV),
    "stock_flash_fwd_bwd": (jax.grad(_sum_sq(_stock_flash), (0, 1, 2)),
                            _QKV),
    "quantize_bf16": (_quantize, [((1024, 1536), jnp.bfloat16)]),
    "quantize_f32": (_quantize, [((1024, 1536), jnp.float32)]),
    "qgemm_m8": (_qgemm, [((8, 1536), jnp.bfloat16),
                          ((1536, 6144), jnp.int8),
                          ((1536, 24), jnp.float32)]),
    "qgemm_m512": (_qgemm, [((512, 1536), jnp.bfloat16),
                            ((1536, 6144), jnp.int8),
                            ((1536, 24), jnp.float32)]),
    "decode_bf16": (_decode, [((8, 16, 96), jnp.bfloat16),
                              (_CACHE, jnp.bfloat16), (_CACHE, jnp.bfloat16),
                              ((8,), jnp.int32)]),
    "decode_int8": (_decode, [((8, 16, 96), jnp.bfloat16),
                              (_CACHE, jnp.int8), (_CACHE, jnp.int8),
                              ((8,), jnp.int32),
                              (_CACHE[:3], jnp.float32),
                              (_CACHE[:3], jnp.float32)]),
}


#: the kernel names (``name=`` of the pl.pallas_call) a case's compiled
#: text must hold, where the benchmark reads a kernel by its name
NAMED_KERNELS = {
    "ds_flash_fwd_bwd": {"ds_flash_fwd", "ds_flash_bwd_dkv",
                         "ds_flash_bwd_dq"},
    "ds_flash_gqa_s8192_hd256_packed_fwd_bwd": {
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"},
    "ds_flash_gqa16_s8192_hd128_packed_fwd_bwd": {
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"},
    "ds_flash_mla_s8192_dk192_dv128_packed_fwd_bwd": {
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"},
    "ds_flash_win512_gqa9_s8192_hd128_packed_fwd_bwd": {
        "ds_flash_win_fwd", "ds_flash_win_bwd_dkv", "ds_flash_win_bwd_dq"},
    "ds_flash_gqa6_s8192_hd128_packed_fwd_bwd": {
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"},
    "ds_flash_diff_s16384_dk64_dv128_packed_fwd_bwd": {
        "ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"},
    "ds_flash_win512_diff_s16384_dk64_dv128_packed_fwd_bwd": {
        "ds_flash_win_fwd", "ds_flash_win_bwd_dkv", "ds_flash_win_bwd_dq"},
    "ds_sel_s16384_r16_packed_fwd": {"ds_sel_fwd"},
    "ds_sel_s16384_r16_packed_fwd_bwd": {"ds_sel_fwd", "ds_sel_bwd_dq",
                                         "ds_sel_bwd_dkv"},
    "ds_sscan_s16384_packed_fwd": {"ds_sscan_fwd"},
    "ds_sscan_s16384_packed_fwd_bwd": {"ds_sscan_fwd", "ds_sscan_bwd"},
    "ds_ggemm_fwd": {"ds_ggemm_fwd"},
    "ds_ggemm_w768_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"},
    "ds_ggemm_w768_down_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx",
                                   "ds_ggemm_dw"},
    "ds_ggemm_held_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"},
    "ds_ggemm_relu2_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"},
    "ds_ggemm_relu2_down_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx",
                                    "ds_ggemm_dw"},
    "ds_ggemm_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"},
    "ds_ggemm_down_fwd_bwd": {"ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"},
    "ds_gdr_s8192_packed_fwd": {"ds_gdr_fwd"},
    "ds_gdr_s8192_packed_fwd_bwd": {"ds_gdr_fwd", "ds_gdr_bwd"},
    "ds_kda_s16384_packed_fwd": {"ds_kda_fwd"},
    "ds_kda_s16384_packed_fwd_bwd": {"ds_kda_fwd", "ds_kda_bwd"},
    "ds_ssd_s8192_packed_fwd": {"ds_ssd_fwd"},
    "ds_ssd_s8192_packed_fwd_bwd": {"ds_ssd_fwd", "ds_ssd_bwd"},
    "ds_ssd_s16384_one_head_a_group_fwd_bwd": {"ds_ssd_fwd", "ds_ssd_bwd"},
    "ds_ssd_s4096_sixteen_heads_a_group_fwd_bwd": {"ds_ssd_fwd",
                                                   "ds_ssd_bwd"},
    "ds_conv_sublanes_s8192_packed_fwd": {"ds_conv_fwd"},
    "ds_conv_sublanes_s8192_packed_part_fwd_bwd": {"ds_conv_fwd",
                                                   "ds_conv_bwd"},
    "ds_conv_lanes_s8192_packed_fwd": {"ds_conv_fwd"},
    "ds_conv_lanes_s8192_packed_part_fwd_bwd": {"ds_conv_fwd",
                                                "ds_conv_bwd"},
}

#: the regime each grouped kernel of a case takes (the step account's
#: word, telemetry/tracing.py grouped_gemm_rows): Mosaic proves below that
#: the resident panels fit a v5e's VMEM, and that the K-innermost tiling
#: still compiles where blocks ask for it
_ALL_THREE = ("ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw")
GGEMM_REGIMES = {
    "ds_ggemm_fwd": {"ds_ggemm_fwd": "resident"},
    "ds_ggemm_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_down_fwd": {"ds_ggemm_fwd": "resident"},
    "ds_ggemm_down_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_mixtral_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_held_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_held_down_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_relu2_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_relu2_down_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_w768_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    "ds_ggemm_w768_down_fwd_bwd": dict.fromkeys(_ALL_THREE, "resident"),
    # (512, 1024) swapped for dx is one block over its contraction of 1024
    "ds_ggemm_mixtral_streamed_fwd_bwd": {
        "ds_ggemm_fwd": "streamed", "ds_ggemm_dx": "streamed",
        "ds_ggemm_dw": "streamed"},
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(v5e, case, monkeypatch):
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.telemetry import tracing
    fn, args = KERNEL_CASES[case]
    # the library asks jax.devices() what it runs on, and here that is a CPU
    monkeypatch.setattr(gg.vmem, "device_kind",
                        lambda: v5e[0].device_kind.lower())
    with tracing.step_account("test/compile"):
        tracing.count_in_step(grouped_routed_rows=0, grouped_padded_rows=0)
        lowered = jax.jit(fn).lower(
            *(_arg(v5e[0], shape, dtype) for shape, dtype in args))
        compiled = lowered.compile()
    assert KERNEL in compiled.as_text()
    if case.startswith("ds_flash"):
        # the calls ask Mosaic for what they asked before PR 49 touched
        # the tile body (nothing at S 1024; vmem.limit_for's 96 MiB where
        # S 8192 stages 19-29 MB) and the compile above took it; the
        # working set is inside the budget; the account's [interior,
        # boundary] tiles of a head
        from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
        for c in tracing.flash_calls("test/compile"):
            assert c["vmem_limit_bytes"] == (
                None if c["seq_len"] == S else 96 << 20), c
            assert dsf.working_set_bytes(
                c["seq_len"], c["dk"], 2, *c["blocks"], c["packed"],
                c["dv"]) <= gg.vmem.budget()
            assert c["tiles"] == {
                (S, None): [1, 2], (8192, None): [120, 16],
                (8192, 512): [0, 31], (16384, None): [496, 32],
                (16384, 512): [0, 63]}[c["seq_len"], c.get("window")], c
    if "relu2" in case or "mla" in case:
        # a dim of 14.5 x 128 is one block: no padded copy of the expert
        # stack (or of the rows) is written beside the kernels; a value
        # head of 128 beside a score head of 192 stays 128 wide: no padded
        # copy of v, o, do or dv
        assert "stablehlo.pad" not in lowered.as_text()
    if "win512" in case:
        # the windowed calls' row of the account: the window, the blocks
        # the dispatch chose and the key tiles a q-block visits
        from deepspeed_tpu.ops.attention import WINDOW_BLOCKS
        from deepspeed_tpu.ops.pallas.ds_flash_attention import \
            window_k_tiles
        assert [(c["heads"], c["kv_heads"], c["window"], c["blocks"],
                 c["k_tiles_per_q_block"])
                for c in tracing.flash_calls("test/compile")] \
            == [((20, 10) if "diff" in case else (72, 8))
                + (512, list(WINDOW_BLOCKS),
                   window_k_tiles(512, *WINDOW_BLOCKS))]
    if "mla" in case or "diff" in case:
        # the step's account of its flash calls has both widths, the value
        # head's narrower or wider than the score head's
        assert [(c["dk"], c["dv"], c["heads"], c["kv_heads"], c["packed"])
                for c in tracing.flash_calls("test/compile")] \
            == [(192, 128, 32, 32, True) if "mla" in case
                else (64, 128, 20, 10, True)]
    if "ds_sel" in case:
        # 16 heads' tiles of 512 tokens pass what a call is granted unasked:
        # the three calls ask for vmem.limit_for's 96 MiB, Mosaic took it,
        # and the working set is inside the budget
        from deepspeed_tpu.ops.pallas import selected_attention as sel
        tiles = sel.blocking(16384, 16, 128, 64, 2)
        assert tiles[:2] == (512, 512)
        assert gg.vmem.UNASKED < tiles.vmem_bytes <= gg.vmem.budget()
        assert gg.vmem.limit_for(tiles.vmem_bytes) == 96 << 20
        assert compiled.as_text().count(
            f'"memory_space":"1","offset":"0","size":"{96 << 20}"') \
            == len(NAMED_KERNELS[case])
    if "sscan" in case:
        from deepspeed_tpu.ops.pallas import selective_scan as sscan
        assert sscan.blocking(5120, 16, 128, 2).channels == 512
        assert sscan.blocking(5120, 16, 128, 2).vmem_bytes \
            <= gg.vmem.UNASKED
    if case in GGEMM_REGIMES:
        calls = tracing.grouped_gemm_rows("test/compile")["calls"]
        assert {c["kernel"]: c["regime"] for c in calls} \
            == GGEMM_REGIMES[case], calls
    if case in NAMED_KERNELS:
        # the program's own map tells these Mosaic calls apart by name
        from deepspeed_tpu.telemetry.tracing import parse_program_text
        named = {row["kernel"] for row in
                 parse_program_text(compiled.as_text()).values()}
        assert NAMED_KERNELS[case] <= named, named


def _passes_over(compiled, shape):
    """The entry computation's fusions and copies that read or write a
    bf16 array of ``shape``: (name, arrays read, arrays written,
    op_name) each."""
    import re
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY"):]
    leaf = "bf16[%s]" % ",".join(map(str, shape))
    typed = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) [\w-]+\(", line)
        if m:
            typed[m.group(1)] = m.group(2)
    passes = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) (?:fusion|copy)\((.*?)\)",
                     line)
        if not m:
            continue
        reads = sum(typed.get(name, "").startswith(leaf)
                    for name in re.findall(r"%([\w.\-]+)", m.group(3)))
        writes = m.group(2).count(leaf)
        if reads or writes:
            op = re.search(r'op_name="([^"]*)', line)
            passes.append((m.group(1), reads, writes,
                           op.group(1) if op else ""))
    return passes


@pytest.mark.parametrize("shape", [
    (1, 9, 9, 4096, 768),       # granite-4.0-h-small: nine layers' nine
                                # held experts, gate / up
    (1, 4, 8, 2688, 1856),      # nemotron-3-nano: the chip keeps the 2688
                                # minor (no padded lanes)
    (1, 9, 4096, 2320),         # a Mamba layer's W_in = [z|xBC|dt]
], ids=["granite_experts", "nemotron_h_experts", "granite_w_in"])
def test_a_stacked_leafs_update_is_one_fusion_in_place_on_a_v5e(v5e, shape):
    """``mp_adamw.update_in_place`` at the cells' stacked leaves, compiled
    for the chip: ONE fusion reads the leaf's five operands and writes its
    four results and the four sums; the donated state is updated where it
    lies (no array copied, nothing of a leaf's size reserved beside it)."""
    from deepspeed_tpu.runtime.bf16_optimizer import mp_adamw
    tx = mp_adamw(1e-4, weight_decay=0.1, mu_dtype="bfloat16",
                  nu_dtype="bfloat16", master_dtype="bfloat16")
    params = {"w": _arg(v5e[0], shape)}
    state = jax.tree.map(lambda x: _arg(v5e[0], x.shape, x.dtype),
                         jax.eval_shape(tx.init, params))

    def step(params, state, grads):
        return tx.update_in_place(grads, state, params)

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, state, params).compile()
    assert [row[1:3] for row in _passes_over(compiled, shape)] == [(5, 4)]
    leaf_bytes = 2 * int(jnp.prod(jnp.asarray(shape)))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * leaf_bytes
    assert memory.temp_size_in_bytes < leaf_bytes // 8


@pytest.mark.parametrize("tokens,d_model,vocab,chunk,share", [
    # phi-4-mini-flash-reasoning.packed-s16384-traces: a chunk's float32
    # logits (98 MiB) stay in VMEM, so the loop holds its float32 ``dw``
    # carry (244 MiB) and little else
    (16384, 2560, 25008, 1024, 1 / 3),
    # gpt2-760m.dense-s1024: 2,048 tokens' logits (393 MiB) are in HBM
    # beside the carry (294) and their bf16 gradient (196) — 982 MiB where
    # whole logits compile to 7,071; 1,024 would be 687 and 3.4 ms a step
    # slower than whole logits (scripts/head_loss_table.py)
    (12288, 1536, 50257, 2048, 1 / 2),
], ids=["phi4_mini_flash", "gpt2_760m_dense"])
def test_the_head_holds_a_chunk_of_logits_on_a_v5e(v5e, tokens, d_model,
                                                   vocab, chunk, share):
    """``head_token_loss`` with both gradients at a cell's shapes, the head
    tied, alone: its ``temp`` is under ``share`` of what the whole float32
    logits would take, and no ``[tokens, vocab]`` array is in the text."""
    from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
    from deepspeed_tpu.models.model import head_chunk_tokens, head_token_loss
    set_topology(MeshTopology(devices=v5e[:1]))
    assert head_chunk_tokens(tokens, vocab) == chunk
    batch = {"input_ids": _arg(v5e[0], (1, tokens), jnp.int32),
             "segment_ids": _arg(v5e[0], (1, tokens), jnp.int32)}
    compiled = jax.jit(jax.value_and_grad(
        lambda h, w, batch: head_token_loss(h, w, batch, tied=True),
        argnums=(0, 1))).lower(
            _arg(v5e[0], (1, tokens, d_model)),
            _arg(v5e[0], (vocab, d_model)), batch).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < share * 4 * tokens * vocab, temp
    text = compiled.as_text()
    assert f"[{chunk},{vocab}]" in text
    assert f"{tokens},{vocab}]" not in text


def test_a_split_head_is_gathered_and_its_gradient_summed_once_on_a_v5e(v5e):
    """gpt2-2.7b-zero3x4.dense-s2048's head over the four described chips:
    ``wte`` [50257, 2560] split four ways along its width (ZeRO-3), four
    sequences of 2,048 a chip.  The manual region gathers the table once
    and each chip's float32 share of its gradient is summed over the chips
    once, in float32, outside the chunk loop — over 50,272 rows: at 50,257,
    no multiple of eight a chip, the same sum stops this compiler (``Check
    failed: s_count.has_value()`` in its reduce-scatter emitter;
    ``model._sum_of_chips`` pads for it)."""
    import re
    from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
    from deepspeed_tpu.models.model import head_token_loss
    topo = MeshTopology(devices=v5e)
    set_topology(topo)
    on = lambda *spec: NamedSharding(topo.mesh, P(*spec))
    rows, split = on(topo.data_parallel_axes), on(None, topo.zero_shard_axes)
    tokens = jax.ShapeDtypeStruct((16, 2048), jnp.int32, sharding=rows)
    text = jax.jit(
        jax.value_and_grad(lambda h, w, batch: head_token_loss(
            h, w, batch, tied=True), argnums=(0, 1)),
        out_shardings=(on(), (rows, split))).lower(
            jax.ShapeDtypeStruct((16, 2048, 2560), jnp.bfloat16,
                                 sharding=rows),
            jax.ShapeDtypeStruct((50257, 2560), jnp.bfloat16, sharding=split),
            {"input_ids": tokens}).compile().as_text()
    moved = re.findall(
        r"= (\S+?)\{\S* (all-gather|all-reduce|reduce-scatter)(?:-start)?\(",
        text)
    assert sorted(moved) == [("bf16[50257,2560]", "all-gather"),
                             ("f32[50272,2560]", "all-reduce"),
                             ("f32[]", "all-reduce")], moved
    assert "f32[2048,50257]" in text and "8192,50257]" not in text


@pytest.mark.parametrize("entry,passes", [("optax", 5), ("in_place", 2)])
def test_a_gradient_handed_over_in_pieces_is_joined_once_on_a_v5e(
        v5e, entry, passes):
    """A layer loop that unrolls a period (``w[0, i]`` for every ``i`` in
    one body) hands a stacked leaf's gradient over as pieces.  Left free,
    XLA duplicates their join into every consumer and the update falls
    into four fusions over the operands (the Granite cell before PR 67);
    behind ``update_in_place``'s barrier the pieces are joined once and
    the update is one fusion."""
    import optax
    from deepspeed_tpu.runtime.bf16_optimizer import mp_adamw
    from deepspeed_tpu.runtime.step_programs import global_norm
    from deepspeed_tpu.telemetry.numerics import group_stats
    layers, experts, d, f, tokens = 9, 4, 1024, 512, 2048
    shape = (1, layers, experts, d, f)
    tx = mp_adamw(1e-4, weight_decay=0.1, mu_dtype="bfloat16",
                  nu_dtype="bfloat16", master_dtype="bfloat16")

    def loss(params, x):
        for i in range(layers):
            y = jnp.einsum("td,edf->etf", x, params["w"][0, i])
            x = x + jnp.einsum("etf,efd->td", jax.nn.silu(y),
                               params["w2"][0, i])
        return jnp.sum(x.astype(jnp.float32) ** 2)

    def step(params, state, x):
        grads = jax.grad(loss)(params, x)
        if entry == "in_place":
            return tx.update_in_place(grads, state, params)
        # what ``apply_grads`` asks for beside the optax entry
        updates, state = tx.update(grads, state, params)
        return (optax.apply_updates(params, updates), state,
                (global_norm(grads), group_stats(grads, [0, 1], 2),
                 global_norm(updates), global_norm(params)))

    params = {"w": _arg(v5e[0], shape),
              "w2": _arg(v5e[0], (1, layers, experts, f, d))}
    state = jax.tree.map(lambda x: _arg(v5e[0], x.shape, x.dtype),
                         jax.eval_shape(tx.init, params))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, state, _arg(v5e[0], (tokens, d))).compile()
    # the model's own matmuls read ``w`` too; so do the copies in and out
    # that the toy's einsum costs (it wants another layout than the
    # entry's), on both entries
    fusions = [row for row in _passes_over(compiled, shape)
               if "dot_general" not in row[3] and "copy" not in row[0]]
    assert len(fusions) == passes, fusions
    if entry == "in_place":
        # the join writes the gradient once; the update reads the five
        # operands and writes four
        assert sorted(row[1:3] for row in fusions) == [(0, 1), (5, 4)]


def test_flash_vmem_budget_is_the_device_kinds(monkeypatch):
    """S 8192 at head width 256, packed, stages 29 MB: over what Mosaic
    grants unasked, inside a v5e's budget — and only then do the calls ask
    for a limit; the shapes that always fitted ask for nothing."""
    from deepspeed_tpu.ops.pallas import ds_flash_attention as flash
    big = jax.ShapeDtypeStruct((2, 8192, 16, 256), jnp.bfloat16)
    small = jax.ShapeDtypeStruct((12, 1024, 16, 96), jnp.bfloat16)
    monkeypatch.delenv("DS_FLASH_VMEM_MB", raising=False)
    monkeypatch.setattr(flash.vmem, "device_kind", lambda: "cpu")
    assert not flash.vmem_fits(8192, 256, 2, packed=True)
    assert flash.vmem_fits(1024, 96, 2)
    monkeypatch.setattr(flash.vmem, "device_kind", lambda: "tpu v5 lite")
    assert flash.vmem_fits(8192, 256, 2, packed=True)
    assert flash._compiler_kw(small, 512, 512, False) == {}
    limit = flash._compiler_kw(big, 512, 512, True)[
        "compiler_params"].vmem_limit_bytes
    assert flash.working_set_bytes(8192, 256, 2, packed=True) < limit \
        <= 128 << 20


def test_a_held_layer_walks_its_live_prefix_on_a_v5e(v5e, monkeypatch):
    """joyai-llm-flash.packed-s8192-gas2's expert layer (16 of 256 experts
    held, ``held_rows_factor`` 16: a plan of 133,120 rows for 16,384
    tokens), forward and backward: the loops' buffers are ``ds_unwritten_*``
    calls, each loop updates its buffer in place (no copy of an
    ``[Mp, ·]`` array anywhere in the compiled text), and no instruction
    outside a loop makes a pass over the plan's rows but the kernels.  The
    two sums into tokens (``combine`` forward, ``dispatch`` backward) are
    the kernel ``ds_rowsum``, which fetches its rows from the plan itself:
    no ``conditional`` chooses a way, no sort but the plan's one, and the
    one scatter left under those scopes is the gates' (``dgates``: single
    float32s)."""
    import re
    from deepspeed_tpu.comm.mesh import sharding_pin_scope
    from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                         moe_layer)
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(gg.vmem, "device_kind",
                        lambda: v5e[0].device_kind.lower())
    monkeypatch.setattr(gg, "_use_reference",
                        lambda interpret: (False, False))
    config = MoEConfig(d_model=2048, d_ff=768, num_experts=256, top_k=8,
                       experts_held=16, expert_offset=32,
                       held_rows_factor=16, router="sigmoid",
                       dispatch_mode="grouped")
    params = jax.tree.map(
        lambda a: _arg(v5e[0], a.shape,
                       jnp.bfloat16 if a.ndim == 3 else a.dtype),
        jax.eval_shape(lambda: init_moe_params(config,
                                               jax.random.PRNGKey(0))))

    def loss(params, x):
        out, aux = moe_layer(params, x, config, train=True)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    with sharding_pin_scope(False), tracing.step_account("a_held_layer"):
        text = jax.jit(jax.grad(loss, (0, 1))).lower(
            params, _arg(v5e[0], (2, 8192, 2048))).compile().as_text()
    rows = 16 * 8 * 16384 * 16 // 256 + 16 * 128
    assert rows == 133120
    for what in ("rows", "mapped", "dy", "pulled0", "pulled1", "meta"):
        assert f"ds_unwritten_{what}" in text
    # the way back: the kernel, twice; nothing chooses; no scatter of rows
    sums = [row for row in tracing.parse_program_text(text).values()
            if row["kernel"] == "ds_rowsum"]
    assert len(sums) == 2
    assert sorted(re.search(r"(dispatch|combine)\)*/ds_rowsum/",
                            row["scope"]).group(1) for row in sums) \
        == ["combine", "dispatch"], sums
    assert " conditional(" not in text
    assert len(re.findall(r" sort\(", text)) == 1      # the plan's own
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert scatters and all(re.fullmatch(r"f32\[\d+\]\S*", s)
                            for s in scatters), scatters
    assert tracing.held_row_sums("a_held_layer") == [{
        "tokens": 16384, "width": 2048, "plan_rows": rows,
        "blocks": (512, 512), "path": "kernel"}]
    lines = text.splitlines()
    whole = re.compile(rf"= (?:bf16|f32)\[{rows},(?:768|2048)\]\S* (\S+?)\(")
    ops = {m.group(1) for line in lines
           if " ROOT " not in line for m in [whole.search(line)] if m}
    # the kernels and the buffers; a loop's result; its in-place update
    assert ops <= {"custom-call", "while", "get-tuple-element", "parameter",
                   "dynamic-update-slice", "fusion", "bitcast"}, ops
    assert not re.search(rf" copy\(\S+\), .*\[{rows},", text)
    fusions = [line for line in lines
               if whole.search(line) and " fusion(" in line]
    # a fusion that results in an [Mp, ·] array is an update in place
    assert all("dynamic-update-slice" in line or "dynamic_update_slice"
               in line for line in fusions), fusions[:2]


#: temporaries of the parent's layer (commit 1a39731, the rotary on strided
#: lane pairs and q joined from its parts), by the same compile: a CPU count
JOYAI_ATTENTION_TEMP_BYTES_AT_PR_44 = 2138551296


@pytest.fixture(scope="module")
def latent_attention_on_a_v5e(v5e):
    """joyai-llm-flash.packed-s8192-gas2's latent attention alone, forward
    and backward, ``x`` ``[2, 8192, 2048]`` packed, compiled once for the
    two tests below: (the compiled program, its entry computation's lines
    each with its ``op_name``)."""
    import re
    from deepspeed_tpu.comm.mesh import sharding_pin_scope
    from deepspeed_tpu.models import joyai
    from deepspeed_tpu.ops.pallas import ds_flash_attention as flash
    config = joyai.JoyAIConfig(num_layers=5, attention_impl="flash")
    layer = jax.tree.map(
        lambda a: _arg(v5e[0], a.shape,
                       jnp.bfloat16 if a.ndim == 2 else a.dtype),
        jax.eval_shape(lambda: joyai._attn_params(config,
                                                  jax.random.PRNGKey(0))))

    def loss(layer, x, seg):
        with jax.named_scope("ds.block"):
            out = joyai._latent_attention(x, layer, config, seg)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    with pytest.MonkeyPatch.context() as patch, sharding_pin_scope(False):
        patch.setattr(flash.vmem, "device_kind",
                      lambda: v5e[0].device_kind.lower())
        compiled = jax.jit(jax.grad(loss, (0, 1))).lower(
            layer, _arg(v5e[0], (2, 8192, 2048)),
            _arg(v5e[0], (2, 8192), jnp.int32)).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):].splitlines()
    assert sum(KERNEL in line for line in entry) == 3       # the flash three
    scoped = []
    for line in entry:
        m = re.search(r'op_name="([^"]*)"', line)
        scoped.append((m.group(1) if m else "", line))
    return compiled, scoped


def test_latent_attention_turns_q_in_one_pass_on_a_v5e(
        latent_attention_on_a_v5e):
    """``q`` ``[2, 8192, 32, 192]``: the interleaved rotary is
    the product with the signed permutation and its epilogue, one fusion
    over ``q`` a direction (the forward's writes what ``ds_flash_fwd``
    reads), nothing under ``attn/rope`` gathers or scatters (the parent:
    four gathers and two scatter-adds of float32 ``[2, 4096, 32, 64]``),
    no float32 array of ``q``'s size under ``rope`` or ``q_latent``, and
    the layer's temporaries are not above the parent's (1.805 GiB against
    1.992 when this was written)."""
    import re
    compiled, scoped = latent_attention_on_a_v5e
    turning = [line for scope, line in scoped if "/attn/rope/" in scope]
    assert turning
    assert not [line for line in turning
                if re.search(r'op_name="[^"]*(gather|scatter)', line)]
    products = [line for line in turning if "kind=kOutput" in line
                and re.search(r"= bf16\[2,(?:32,8192|8192,32),192\]", line)]
    assert len(products) == 2, products                     # there and back
    assert sum(line.split("=")[1].startswith(" bf16[2,32,8192,192]")
               for line in products) == 1       # the kernels' own layout
    whole_f32 = re.compile(r"= \(?f32\[2,(?:32,8192|8192,32),192\]")
    assert not [line for scope, line in scoped
                if re.search(r"/attn/(rope|q_latent)/", scope)
                and whole_f32.search(line)]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= JOYAI_ATTENTION_TEMP_BYTES_AT_PR_44, temp


def test_latent_attention_writes_k_and_v_where_the_kernels_read_them(
        latent_attention_on_a_v5e):
    """The same compile (PR 57): ``k`` leaves one product —
    ``[c_kv | k_r] [W_uk 0 ; 0 I]`` — as ``bf16[2,32,8192,192]`` in the
    kernels' own layout and ``v`` its own as ``bf16[2,32,8192,128]``, so
    nothing stands between them and ``ds_flash_fwd``; the kernels' float32
    ``dk`` / ``dv`` are the operands of the products that take them back.
    Under ``attn/kv_latent`` and ``attn/scores`` the only instructions
    that write 60 MB or more are the three kernels, products, and the one
    copy that turns the kernels' output ``[2, 32, 8192, 128]`` for the
    output projection (the parent had it too; beside it: a broadcast of
    ``k_r``, the slices of ``kv``, the join of ``k``, the casts of ``dk``
    and ``dv``, a split, a pad-and-add of ``[2, 8192, 32, 256]`` and its
    copy).  The layer's temporaries stay under the constant above
    (1,949,622,272 when this was written; the parent's 1,938,547,712)."""
    import math
    import re
    compiled, scoped = latent_attention_on_a_v5e
    size = {"bf16": 2, "f32": 4, "s32": 4}
    result = re.compile(r"= \(?(\w+)\[([\d,]+)\]")

    def written(line):
        m = result.search(line)
        return m and size.get(m.group(1), 0) * math.prod(
            map(int, m.group(2).split(",")))

    here = [line for scope, line in scoped
            if re.search(r"/attn/(kv_latent|scores)/", scope)]
    born = [line for scope, line in scoped if "/attn/kv_latent/" in scope
            and re.search(r"= bf16\[2,32,8192,(?:192|128)\]", line)]
    assert sorted(line.split(" = ")[1].split(":")[0] for line in born) == [
        "bf16[2,32,8192,128]{3,2,1,0", "bf16[2,32,8192,192]{3,2,1,0"], born
    assert all(" fusion(" in line and "kind=kOutput" in line
               for line in born), born
    large = [line for line in here if (written(line) or 0) >= 60e6
             and not re.search(r" (get-tuple-element|bitcast)\(", line)]
    others = [line for line in large if KERNEL not in line
              and not (" fusion(" in line and "kind=kOutput" in line)]
    assert len(others) == 1 and re.search(
        r"= bf16\[2,8192,32,128\]\S* copy\(.*attn/scores/transpose",
        others[0]), others
    assert sum(KERNEL in line for line in large) == 3, large
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= JOYAI_ATTENTION_TEMP_BYTES_AT_PR_44, temp


@pytest.mark.parametrize("manual_outside", [False, True],
                         ids=["gspmd", "inside_data_manual_shard_map"])
def test_data_sharded_flash_grad_partitions(v5e, manual_outside):
    """jax.grad of causal_attention with the batch sharded over data=4:
    bare, the partitioner refuses the Mosaic call; through the shard_map
    wrap each chip runs the kernel on its B/4 slice.  The second case
    nests it inside a shard_map already manual over data (the quantized
    gradient-exchange tier), where the wrap maps only the axes left."""
    from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
    from deepspeed_tpu.ops.attention import causal_attention
    from deepspeed_tpu.utils.jax_compat import shard_map
    topo = MeshTopology(devices=v5e)
    set_topology(topo)
    assert topo.mesh.shape["data"] == 4
    batch_axes = ("data", "hpz") if manual_outside \
        else topo.data_parallel_axes
    loss = _sum_sq(lambda q, k, v: causal_attention(q, k, v, impl="flash"))
    if manual_outside:
        def loss(q, k, v, local=loss):
            return shard_map(
                lambda *a: jax.lax.psum(local(*a), batch_axes),
                mesh=topo.mesh, in_specs=(P(batch_axes),) * 3,
                out_specs=P(), axis_names=set(batch_axes),
                check_vma=False)(q, k, v)
    x = jax.ShapeDtypeStruct(
        (4 * B, S, H, HD), jnp.bfloat16,
        sharding=NamedSharding(topo.mesh, P(batch_axes)))
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile() \
        .as_text()
    calls = [l for l in text.splitlines() if KERNEL in l]
    assert calls
    assert all(f"bf16[{B},{H},{S},{HD}]" in l for l in calls), calls[0][:200]


def test_a_rematerialised_exchange_returns_no_rows_on_a_v5e(v5e, monkeypatch):
    """mellum2-12b-a2.5b-ep4's expert layer over the four described chips
    (8,192 tokens a chip, 64 experts, top 8, a bound of three times the
    even share), its gradient under ``jax.checkpoint`` as the model's
    rematerialised block runs it: the gate is applied where the experts
    are, so no row that came back is a residual and the recompute ends at
    the activation.  Counted in the compiled text: **five** row-wide
    ``ragged-all-to-all`` (forward 2, recompute 1, backward 2 — the
    parent's six), three narrow ones (the gates out in forward and
    recompute, their cotangent home), eleven grouped Mosaic calls (three
    forward, two in the recompute, six backward — the parent's twelve) and
    two ``ds_rowsum`` (the forward's sum, the dispatch's transpose): a
    later edit that keeps a returned row brings the sixth back, and fails
    here."""
    import math
    import re
    from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
    from deepspeed_tpu.moe import mappings
    from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                         moe_layer, moe_logical_specs)
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(gg.vmem, "device_kind",
                        lambda: v5e[0].device_kind.lower())
    monkeypatch.setattr(gg, "_use_reference",
                        lambda interpret: (False, False))
    monkeypatch.setattr(mappings, "exchange_path",
                        lambda: mappings.RAGGED_ALL_TO_ALL)
    D = 2304
    config = MoEConfig(d_model=D, d_ff=896, num_experts=64, top_k=8,
                       dispatch_mode="grouped", held_rows_factor=3)
    topo = MeshTopology(devices=v5e, expert_parallel_size=4)
    set_topology(topo)
    params = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim == 3 else a.dtype,
            sharding=NamedSharding(topo.mesh, spec)),
        jax.eval_shape(lambda: init_moe_params(config,
                                               jax.random.PRNGKey(0))),
        moe_logical_specs(config))
    x = jax.ShapeDtypeStruct((4, 8192, D), jnp.bfloat16,
                             sharding=NamedSharding(topo.mesh, P("expert")))

    @jax.checkpoint
    def block(params, x):
        out, aux = moe_layer(params, x, config, train=True)
        return x + out, aux

    def loss(params, x):
        out, aux = block(params, x)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    try:
        text = jax.jit(jax.grad(loss, (0, 1))).lower(
            params, x).compile().as_text()
    finally:
        from deepspeed_tpu.comm.mesh import reset_topology
        reset_topology()
    # (the compiler re-tiles a row: bf16[rows, 2, 1152], f32[rows, 1, 128])
    exchanged = [(dtype, math.prod(map(int, dims.split(",")[1:])))
                 for dtype, dims in re.findall(
                     r"= (\w+)\[([\d,]+)\]\S* ragged-all-to-all\(", text)]
    assert sorted(exchanged) == 5 * [("bf16", D)] + 3 * [("f32", 128)], \
        exchanged
    kernels = [row["kernel"] for row in
               tracing.parse_program_text(text).values() if row["kernel"]]
    grouped = [k for k in kernels if k.startswith("ds_ggemm")]
    assert len(grouped) == 11, sorted(kernels)
    # the rows summed by landed row and then by token, and each one's
    # transpose (the gathers' backward) — none in the recompute
    assert kernels.count("ds_rowsum") == 4, sorted(kernels)
    # a row on the wire is a (token, chip): no operand of a row-wide call
    # is longer than the chip's four times 8,192 tokens and a tile a chip
    # (the parent's send layout was 73,728 rows, its receive side 198,656)
    operands = [int(rows) for rows in re.findall(
        r"ragged-all-to-all\(bf16\[(\d+),", text)] or [
            int(rows) for name in re.findall(
                r"ragged-all-to-all\(%([\w.-]+),", text)
            for rows in re.findall(
                rf"%{re.escape(name)} = bf16\[(\d+),", text)]
    assert len(operands) == 5 and max(operands) <= 4 * 8192 + 4 * 128, \
        operands
    assert not re.findall(r"\b(?:pred|bf16|f32|s32)\[(?:8192|32768),64,\d+\]",
                          text)


def test_the_exchanged_layers_backward_waits_for_its_recompute(v5e,
                                                               monkeypatch):
    """mellum2-12b-a2.5b-ep4's loss and gradient (four layers, all 64
    experts over the four described chips, one micro-batch): no row that
    came back is a residual, so nothing of an expert layer's backward pass
    depends on its recompute, and a scheduler left free runs the
    cotangents' exchange first and keeps ``dy`` — ``[Mp, D]``, 0.87 GiB —
    live through the recompute's own: temporaries 6,051 MiB by this count
    (the step's peak on the chips + 2.6%, PERF §6 PR 51).  ``moe/layer.py
    _after`` ties the backward to the recompute's end by arithmetic the
    compiler cannot fold: 5,023 MiB (the parent's program 4,847).  A
    compiler that learns to fold the tie, or an edit that drops it, fails
    here and nowhere else.  (Since PR 63 a row on the wire is a (token,
    chip): temporaries 4,695 MiB and a peak of 6,949 where PR 62's program
    read 5,023 and 7,678 — the send layout and the way home are 33,280 rows
    for 73,728, and two 32,768-row buffers join.)

    The same text holds how an exchange's buffers are born (two layer
    bodies, the loop's and the full layer's; a rematerialised pass lands
    three row-wide calls in landing buffers — a row a (token, sender),
    ``[32768, ·]`` — and two narrow ones in plan-sized buffers, and brings
    two row-wide and one narrow home): every ``ragged-all-to-all`` whose
    result is the bound's ``[198656, ·]`` lands in the result of a
    ``ds_zeroed_padding_gates`` call and every one whose result is the
    landing buffer in that of a ``ds_unwritten_<what>`` call, as it lies —
    no ``broadcast``, and no ``copy`` or ``reshape`` between the two (the
    kernel makes the buffer in the shape the collective moves it in,
    ``mappings._as_sent``) — and every call home still lands in zeros.  The
    buffer waits for the rows *as they leave* (``mappings._forth``): tied
    to the array they came in, that array stays live beside its re-tiled
    self and the step's peak reads 0.51 GiB more (8,202 MiB here for
    7,678)."""
    import re
    from deepspeed_tpu.comm.mesh import (MeshTopology, reset_topology,
                                         set_topology)
    from deepspeed_tpu.models.mellum import mellum_model
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import vmem
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(vmem, "device_kind",
                        lambda: v5e[0].device_kind.lower())
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    topo = MeshTopology(devices=v5e, expert_parallel_size=4)
    set_topology(topo)
    try:
        model = mellum_model(size="12b-a2.5b", num_layers=4,
                             dtype="bfloat16", remat=True,
                             held_rows_factor=3)
        params = jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16 if a.ndim >= 2 else a.dtype,
                sharding=NamedSharding(topo.mesh, spec or P())),
            jax.eval_shape(model.init_fn, jax.random.PRNGKey(0)),
            model.logical_specs)
        tokens = jax.ShapeDtypeStruct(
            (4, 8192), jnp.int32, sharding=NamedSharding(topo.mesh,
                                                         P("expert")))
        compiled = jax.jit(jax.value_and_grad(
            model.loss_with_counts_fn, has_aux=True)).lower(
                params, {"input_ids": tokens, "segment_ids": tokens}
            ).compile()
    finally:
        reset_topology()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 5000 * 2 ** 20, \
        memory.temp_size_in_bytes / 2 ** 20
    assert memory.peak_memory_in_bytes < 7300 * 2 ** 20, \
        memory.peak_memory_in_bytes / 2 ** 20
    text = compiled.as_text()
    kernel_of = {name: row["kernel"] for name, row in
                 tracing.parse_program_text(text).items() if row["kernel"]}
    opcode = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = \S+ ([\w-]+)\(", text,
                             flags=re.M))
    landed = {"plan": [], "landing": [], "home": []}
    for rows, buffer in re.findall(
            r"= \w+\[(\d+),[\d,]+\]\S* ragged-all-to-all\(%\S+, %([\w.-]+),",
            text):
        landed[{"198656": "plan", "32768": "landing"}.get(rows, "home")] \
            .append(kernel_of.get(buffer, opcode[buffer]))
    # the lanes land in the plan's layout, its padding tiles zeroed; the
    # rows and the cotangents of what came back a row a (token, sender), in
    # a buffer nobody wrote: nothing of it is read but where a row landed
    assert landed["plan"] == 4 * ["ds_zeroed_padding_gates"]
    assert sorted(landed["landing"]) == 2 * ["ds_unwritten_cotangents"] \
        + 4 * ["ds_unwritten_rows"]
    assert landed["home"] == 6 * ["broadcast"]
    assert sum(kernel.startswith("ds_zeroed_padding")
               for kernel in kernel_of.values()) == 4


def test_a_looped_stack_sums_its_four_uses_in_one_stacked_gradient(
        v5e, monkeypatch):
    """Ouro at the published widths, four layers four times over, 2,048
    packed tokens, loss and every gradient compiled for the chip: the one
    scan of 16 applications adds a layer's gradient into the stacked
    accumulator where it lies (the gradients are the program's outputs:
    776 MiB, of which the stack's 392), so ``temp`` holds the carries, the
    head's float32 ``dw`` and a chunk's logits — 1,255 MiB — and not a
    second stacked gradient (392 MiB more, as a scan of passes around a
    scan of layers reads); the flash kernels are the loop's: forward,
    forward again in the recompute, and the backward's two."""
    from deepspeed_tpu.comm.mesh import (MeshTopology, reset_topology,
                                         set_topology)
    from deepspeed_tpu.models.ouro import ouro_model
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import vmem
    from deepspeed_tpu.telemetry import tracing
    monkeypatch.setattr(vmem, "device_kind",
                        lambda: v5e[0].device_kind.lower())
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    set_topology(MeshTopology(devices=v5e[:1]))
    try:
        model = ouro_model("2.6b", num_layers=4, remat=True)
        params = jax.tree.map(
            lambda a: _arg(v5e[0], a.shape),
            jax.eval_shape(model.init_fn, jax.random.PRNGKey(0)))
        tokens = _arg(v5e[0], (1, 2048), jnp.int32)
        compiled = jax.jit(jax.value_and_grad(model.loss)).lower(
            params, {"input_ids": tokens, "segment_ids": tokens}).compile()
    finally:
        reset_topology()
    memory = compiled.memory_analysis()
    stack = 2 * 4 * 51_380_224
    assert memory.output_size_in_bytes > stack
    assert memory.temp_size_in_bytes < 1255 * 2 ** 20 + stack // 2, \
        memory.temp_size_in_bytes / 2 ** 20
    kernels = [row["kernel"] for row in tracing.parse_program_text(
        compiled.as_text()).values() if row["kernel"]]
    assert sorted(kernels) == ["ds_flash_bwd_dkv", "ds_flash_bwd_dq",
                               "ds_flash_fwd", "ds_flash_fwd"]


def test_library_knows_the_chips_peaks(v5e):
    from deepspeed_tpu.telemetry.mfu import peak_flops_per_device
    from deepspeed_tpu.telemetry.roofline import (hbm_bytes_per_s,
                                                  ici_bytes_per_s)
    assert v5e[0].device_kind == "TPU v5 lite"
    assert peak_flops_per_device(v5e[0], env={}) == 197e12
    assert hbm_bytes_per_s(v5e[0], env={}) == 819e9
    assert ici_bytes_per_s(v5e[0], env={}) == 200e9


@pytest.mark.parametrize("script", [
    "chip_smoke.py", "bench.py", "scripts/delta_rule_table.py --seed 1",
    "scripts/kda_rule_bench.py",
    "scripts/ssd_table.py", "scripts/conv_table.py", "scripts/rope_table.py",
    "scripts/latent_attention_table.py --seed 1",
    "scripts/latent_attention_table.py --bits",
    "scripts/optimizer_table.py --seed 1",
    "scripts/head_loss_table.py --seed 1"])
def test_measurement_scripts_refuse_the_cpu(script):
    script, *args = script.split()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert '"metric"' not in out.stdout
    assert "TPU" in out.stderr
