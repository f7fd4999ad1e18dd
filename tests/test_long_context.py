"""Long-context paths: ring attention (context parallelism) and block-sparse
attention (reference: ops/sparse_attention/ + the ring/blockwise CP that
SURVEY §2.3 requires beyond the reference)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.sparse_attention import (
    DenseSparsityConfig, FixedSparsityConfig, BigBirdSparsityConfig,
    BSLongformerSparsityConfig, VariableSparsityConfig, layout_to_mask,
    sparse_self_attention)


def _dense_causal(q, k, v):
    B, S, H, hd = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ------------------------------------------------------------ sparse attention

def test_dense_config_equals_full_attention():
    rng = np.random.default_rng(4)
    B, S, H, hd = 2, 64, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    cfg = DenseSparsityConfig(num_heads=H, block=16)
    out = sparse_self_attention(q, k, v, cfg, causal=True)
    want = _dense_causal(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fixed_layout_structure():
    cfg = FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2,
                              num_global_blocks=1)
    layout = cfg.make_layout(128)       # 8x8 blocks
    assert layout.shape == (2, 8, 8)
    assert (layout[0] == layout[1]).all()       # propagated first head
    assert layout[0, 0, 0] == 1                 # local window
    assert layout[0, 0, 1] == 1                 # global col (end of window 0)
    assert layout[0, 0, 2] == 0                 # outside window+globals
    assert layout[0, 7, 7] == 1


def test_fixed_unidirectional_is_lower_triangular():
    cfg = FixedSparsityConfig(num_heads=1, block=16, num_local_blocks=4,
                              attention="unidirectional")
    layout = cfg.make_layout(128)
    assert (np.triu(layout[0], 1) == 0).all()


def test_bigbird_layout_has_window_random_global():
    cfg = BigBirdSparsityConfig(num_heads=1, block=16, num_random_blocks=1,
                                num_sliding_window_blocks=3,
                                num_global_blocks=1)
    layout = cfg.make_layout(256)       # 16x16
    n = layout.shape[1]
    assert (layout[0, 0, :] == 1).all()          # global row
    assert (layout[0, :, 0] == 1).all()          # global col
    for i in range(1, n - 1):
        assert layout[0, i, i - 1] and layout[0, i, i] and layout[0, i, i + 1]
    density = layout[0].mean()
    assert density < 0.5                         # actually sparse


def test_bslongformer_layout():
    cfg = BSLongformerSparsityConfig(num_heads=1, block=16,
                                     num_sliding_window_blocks=3,
                                     global_block_indices=[0, 5])
    layout = cfg.make_layout(128)
    assert (layout[0, 0, :] == 1).all() and (layout[0, :, 5] == 1).all()


def test_variable_layout_windows():
    cfg = VariableSparsityConfig(num_heads=1, block=16,
                                 local_window_blocks=[1, 2, 4],
                                 global_block_indices=[0])
    layout = cfg.make_layout(256)
    assert layout[0, 0, 0] == 1
    assert layout[0, 1, 2] == 1 and layout[0, 2, 1] == 1    # window of 2
    assert (layout[0][:, 0] == 1).all()                     # global col


def test_layout_to_mask_expands_blocks():
    cfg = FixedSparsityConfig(num_heads=1, block=4, num_local_blocks=1,
                              num_global_blocks=0)
    layout = cfg.make_layout(16)
    mask = layout_to_mask(layout, 16)
    assert mask.shape == (1, 16, 16)
    assert bool(mask[0, 0, 3]) and not bool(mask[0, 0, 4])


def test_sparse_attention_masks_forbidden_positions():
    """A token outside every allowed block must not influence the output."""
    rng = np.random.default_rng(5)
    B, S, H, hd = 1, 64, 1, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=1,
                              num_global_blocks=0)
    out1 = sparse_self_attention(q, k, v, cfg)
    # perturb keys/values in a block the first window cannot see
    k2 = k.at[:, 48:].set(rng.normal(size=(B, 16, H, hd)))
    v2 = v.at[:, 48:].set(rng.normal(size=(B, 16, H, hd)))
    out2 = sparse_self_attention(q, k2, v2, cfg)
    np.testing.assert_allclose(np.asarray(out1[:, :16]),
                               np.asarray(out2[:, :16]), rtol=1e-6)
    assert not np.allclose(np.asarray(out1[:, 48:]), np.asarray(out2[:, 48:]))


# ---------------------------------------------- pallas block-skipping kernel

def test_pallas_block_sparse_matches_dense():
    """The block-skipping kernel reproduces the dense block-masked path
    (both causal and bidirectional) to fp32 tolerance."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention)
    rng = np.random.default_rng(7)
    B, S, H, hd = 2, 64, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2,
                              num_global_blocks=1)
    layout = cfg.make_layout(S)
    for causal in (False, True):
        dense = sparse_self_attention(q, k, v, cfg, causal=causal)
        kern = block_sparse_attention(q, k, v, layout, causal=causal)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(kern),
                                   rtol=2e-5, atol=2e-5)


def test_pallas_block_sparse_skips_masked_blocks():
    """Poison KV in blocks outside the layout with huge values: the kernel
    output must be bit-insensitive — those blocks are never loaded (the
    dense path merely masks them after multiplying)."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention)
    rng = np.random.default_rng(8)
    B, S, H, hd = 1, 64, 1, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=1,
                              num_global_blocks=0)
    layout = cfg.make_layout(S)
    out1 = block_sparse_attention(q, k, v, layout)
    # block rows 0 can only see kv block 0: poison kv blocks 2-3 with inf
    bad = jnp.float32(np.inf)
    k2 = k.at[:, 32:].set(bad)
    v2 = v.at[:, 32:].set(bad)
    out2 = block_sparse_attention(q, k2, v2, layout)
    np.testing.assert_array_equal(np.asarray(out1[:, :32]),
                                  np.asarray(out2[:, :32]))


def test_pallas_block_sparse_trainable_grads_match_dense():
    """Gradients through the trainable wrapper equal the dense path's."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention_trainable)
    rng = np.random.default_rng(9)
    B, S, H, hd = 1, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2,
                              num_global_blocks=0)
    layout = cfg.make_layout(S)

    def loss_kernel(q, k, v):
        return block_sparse_attention_trainable(q, k, v, layout,
                                                causal=True).sum()

    def loss_dense(q, k, v):
        return sparse_self_attention(q, k, v, cfg, causal=True).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_fully_masked_rows_emit_zero_both_paths():
    """A causal layout whose first block-row only sees an above-diagonal
    block leaves those rows fully masked: both paths emit exactly 0 (flash
    convention) instead of a masked-V average."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention)
    rng = np.random.default_rng(11)
    B, S, H, hd = 1, 32, 1, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    layout = np.array([[[0, 1], [1, 1]]])          # row 0: above-diag only

    class Cfg:
        def make_layout(self, seq_len):
            return layout

    dense = np.asarray(sparse_self_attention(q, k, v, Cfg(), causal=True))
    kern = np.asarray(block_sparse_attention(q, k, v, layout, causal=True))
    np.testing.assert_array_equal(dense[:, :16], np.zeros_like(dense[:, :16]))
    np.testing.assert_array_equal(kern[:, :16], np.zeros_like(kern[:, :16]))
    np.testing.assert_allclose(dense[:, 16:], kern[:, 16:], rtol=2e-5,
                               atol=2e-5)


def test_pallas_block_sparse_bwd_noncausal_and_empty_rows():
    """Fused backward: non-causal grads match dense, and rows left empty by
    the causal tril get exactly zero dq (their forward emits 0)."""
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention_trainable)
    rng = np.random.default_rng(12)
    B, S, H, hd = 2, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
               for _ in range(3))
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=1,
                              num_global_blocks=1)
    layout = cfg.make_layout(S)

    def loss_k(q, k, v, causal):
        return block_sparse_attention_trainable(q, k, v, layout,
                                                causal=causal).sum()

    def loss_d(q, k, v, causal):
        return sparse_self_attention(q, k, v, cfg, causal=causal).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v, False)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v, False)
    for a, b in zip(gk, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    # causal + above-diagonal-only first row block -> empty rows, zero dq
    layout2 = np.array([[[0, 1], [1, 1]]] * H)

    def loss2(q, k, v):
        return block_sparse_attention_trainable(q, k, v, layout2,
                                                causal=True).sum()

    dq = jax.grad(loss2)(q, k, v)
    np.testing.assert_array_equal(np.asarray(dq[:, :16]),
                                  np.zeros_like(np.asarray(dq[:, :16])))


# ------------------------------------------- from-scratch flash kernel

def _dense_ref_attn(q, k, v, seg=None, causal=True):
    import jax
    import jax.numpy as jnp
    S = q.shape[1]
    hd = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    mask = (jnp.tril(jnp.ones((S, S), bool)) if causal
            else jnp.ones((S, S), bool))[None, None]
    if seg is not None:
        mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("use_seg,causal", [
    (False, True), (True, True), (False, False), (True, False)])
def test_ds_flash_attention_fwd_bwd_parity(interpret_pallas, use_seg,
                                           causal):
    """round-2 VERDICT item 6: the from-scratch FlashAttention-2 kernel
    (fwd + recompute bwd, segment-id packing) matches the dense reference
    in interpret mode."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    rng = np.random.default_rng(3)
    B, S, H, hd = 2, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    seg = (jnp.asarray(np.repeat(rng.integers(0, 3, (B, 4)), S // 4,
                                 axis=1), jnp.int32) if use_seg else None)
    out = ds_flash_attention(q, k, v, segment_ids=seg, causal=causal,
                             block_q=64, block_k=32)
    ref = _dense_ref_attn(q, k, v, seg, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss(q, k, v):
        return jnp.sum(ds_flash_attention(q, k, v, segment_ids=seg,
                                          causal=causal, block_q=64,
                                          block_k=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_dense_ref_attn(q, k, v, seg, causal) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_ds_flash_segment_isolation(interpret_pallas):
    """Tokens must not attend across segment boundaries: perturbing
    segment 0 leaves segment 1's outputs bit-identical."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    rng = np.random.default_rng(4)
    B, S, H, hd = 1, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    seg = jnp.asarray([[0] * 64 + [1] * 64], jnp.int32)
    out1 = ds_flash_attention(q, k, v, segment_ids=seg, block_q=64,
                              block_k=64)
    k2 = k.at[0, :64].set(99.0)
    v2 = v.at[0, :64].set(-99.0)
    out2 = ds_flash_attention(q, k2, v2, segment_ids=seg, block_q=64,
                              block_k=64)
    np.testing.assert_array_equal(np.asarray(out1[0, 64:]),
                                  np.asarray(out2[0, 64:]))


def test_ds_flash_pad_mask_as_segments(interpret_pallas):
    """Padded encoder batches map onto the kernel's segment ids (real=1,
    pad=0): real-token outputs match the XLA masked path exactly; pad
    positions (whose outputs downstream losses discard) are isolated."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    from deepspeed_tpu.ops.attention import xla_bidirectional_attention
    rng = np.random.default_rng(8)
    B, S, H, hd = 2, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    lens = [96, 64]
    pad = np.zeros((B, S), np.int32)
    for b, L in enumerate(lens):
        pad[b, :L] = 1
    pad = jnp.asarray(pad)
    out = ds_flash_attention(q, k, v, segment_ids=pad, causal=False,
                             block_q=64, block_k=64)
    ref = xla_bidirectional_attention(q, k, v, pad_mask=pad)
    for b, L in enumerate(lens):
        np.testing.assert_allclose(np.asarray(out[b, :L]),
                                   np.asarray(ref[b, :L]), atol=2e-5)


def test_ds_flash_vmem_guard_routes_oversized_to_xla():
    """Advisor round 3: the kernels stage full-sequence K/V in VMEM per
    grid step, so shapes whose working set exceeds the ~16 MiB/core budget
    must never reach the Mosaic compiler — the dispatch layer's budget
    check routes them to the XLA path (eval_shape alone cannot see this)."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import vmem_fits
    from deepspeed_tpu.ops import attention as att
    # 1k bf16 fits comfortably; 16k fp32 exceeds 12 MiB (advisor's case)
    assert vmem_fits(1024, 64, 2)
    assert not vmem_fits(16384, 64, 4)
    # dispatch: a packed (segment-id) call on the oversized shape traces
    # through the XLA fallback instead of the kernel — eval_shape of the
    # kernel path would "pass" and then die in Mosaic on real hardware
    B, S, H, hd = 1, 16384, 2, 64
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.float32)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32)
    att._FLASH_STATUS.clear()
    out = jax.eval_shape(
        lambda q, k, v, s: att.flash_causal_attention(q, k, v,
                                                      segment_ids=s),
        q, q, q, seg)
    assert out.shape == (B, S, H, hd)
    key = ("vmem", S, hd, 4, True)
    assert key in att._FLASH_STATUS          # guard probed this shape
    assert att._FLASH_STATUS[key] is not True  # and fired (routed away)
    att._FLASH_STATUS.clear()


def test_ds_flash_gqa_parity(interpret_pallas):
    """Grouped-query attention: the kernel attends compact KV heads
    natively; parity vs the repeated-head dense reference for fwd and all
    gradients (dk/dv in the compact [B,S,KV,hd] layout)."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    rng = np.random.default_rng(11)
    B, S, H, KV, hd = 2, 128, 8, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)

    def ref(q, k, v):
        rep = H // KV
        kk = jnp.repeat(k, rep, axis=2)
        vv = jnp.repeat(v, rep, axis=2)
        return _dense_ref_attn(q, kk, vv, None, True)

    out = ds_flash_attention(q, k, v, block_q=64, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               atol=2e-5)
    g = jax.grad(lambda *a: jnp.sum(
        ds_flash_attention(*a, block_q=64, block_k=32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    assert g[1].shape == (B, S, KV, hd)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


# ---------------------------------------------------- packed-sequence training

def test_packed_training_segments_isolated(devices8):
    """Sequence packing is reachable from the model API
    (batch["segment_ids"]): perturbing segment 0's tokens leaves segment
    1's logits bit-identical (attention is segment-masked; positions are
    per-slot constants)."""
    from tests.util import tiny_gpt2
    import jax as _jax
    m = tiny_gpt2()
    params = m.init(_jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    ids = rng.integers(1, 128, (1, 16)).astype(np.int32)
    seg = np.array([[0] * 8 + [1] * 8], np.int32)
    out1 = np.asarray(m.apply(params, {"input_ids": ids,
                                       "segment_ids": seg}))
    ids2 = ids.copy()
    ids2[0, :8] = rng.integers(1, 128, 8)
    out2 = np.asarray(m.apply(params, {"input_ids": ids2,
                                       "segment_ids": seg}))
    np.testing.assert_array_equal(out1[0, 8:], out2[0, 8:])
    assert not np.array_equal(out1[0, :8], out2[0, :8])


def test_packed_loss_masks_segment_boundary(devices8):
    """The default LM loss drops cross-segment targets (last token of
    segment i must not be scored against segment i+1's first token)."""
    from tests.util import tiny_gpt2
    import jax as _jax
    import jax.numpy as _jnp
    import optax
    m = tiny_gpt2()
    params = m.init(_jax.random.PRNGKey(1))
    rng = np.random.default_rng(8)
    ids = rng.integers(1, 128, (1, 12)).astype(np.int32)
    seg = np.array([[0] * 5 + [1] * 7], np.int32)
    batch = {"input_ids": ids, "segment_ids": seg}
    got = float(m.loss(params, batch))
    logits = m.apply(params, batch)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        _jnp.asarray(logits[:, :-1], _jnp.float32), ids[:, 1:])
    keep = (seg[:, 1:] == seg[:, :-1]).astype(np.float32)
    want = float((np.asarray(ce) * keep).sum() / keep.sum())
    assert abs(got - want) < 1e-5
    # boundary target really excluded: 10 of 11 positions kept
    assert keep.sum() == 10


def test_packed_with_ulysses_and_dp(devices8):
    """Packed batches under sequence parallelism WITH data parallelism
    (review round 4: segment_ids must enter the Ulysses shard_map as a
    sharded operand, not a closure capture): sp=2 x dp=4 packed training
    matches the pure-DP packed run."""
    import deepspeed_tpu
    from tests.util import tiny_gpt2, base_config
    ref, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 2}))
    sp, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(), config=base_config(
            zero_optimization={"stage": 2},
            mesh={"sequence_parallel_size": 2}))
    rng = np.random.default_rng(10)
    for i in range(2):
        ids = rng.integers(1, 128, (1, 8, 16)).astype(np.int32)
        seg = np.tile(np.array([0] * 8 + [1] * 8, np.int32), (1, 8, 1))
        batch = {"input_ids": ids, "segment_ids": seg}
        l_ref = float(ref.train_batch(batch=batch))
        l_sp = float(sp.train_batch(batch=batch))
        assert abs(l_ref - l_sp) < 2e-4, f"step {i}: {l_ref} vs {l_sp}"


def test_packed_training_through_engine(devices8):
    """segment_ids ride the engine batch like any other leaf (sharded
    with the batch dims); a packed ZeRO-2 step trains finite, and llama's
    GQA path accepts the packed mask too."""
    import deepspeed_tpu
    from tests.util import tiny_gpt2, base_config
    from deepspeed_tpu.models.llama import llama_model
    from deepspeed_tpu.models.bloom import bloom_model
    from deepspeed_tpu.models.gptneo import gptneo_model
    for model in (tiny_gpt2(),
                  llama_model("tiny", dtype="float32",
                              attention_impl="xla", max_seq_len=64),
                  bloom_model("tiny"),
                  gptneo_model("tiny")):
        from deepspeed_tpu.comm import reset_topology
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=base_config(
                zero_optimization={"stage": 2}))
        rng = np.random.default_rng(9)
        vocab = model.config.vocab_size
        ids = rng.integers(1, vocab, (1, 8, 16)).astype(np.int32)
        seg = np.tile(np.array([0] * 8 + [1] * 8, np.int32), (1, 8, 1))
        loss = engine.train_batch(batch={"input_ids": ids,
                                         "segment_ids": seg})
        assert np.isfinite(float(loss))


def test_ds_flash_packed_segment_ids_are_tracer_safe(interpret_pallas):
    """Packed segment_ids must ride the kernel as a real custom_vjp
    argument: a closure capture breaks with 'No constant handler for
    DynamicJaxprTracer' once a jitted train step scans the blocks and
    segment_ids is a tracer (caught on the first real-TPU packed train
    drive, round 4 — unit tests only ever called the kernel with concrete
    arrays).  eval_shape reproduces the exact failure mode (tracing)
    without executing."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention

    B, S, H, hd = 1, 512, 2, 64

    def step(q, seg):
        def body(x, _):
            o = ds_flash_attention(x, x, x, segment_ids=seg, causal=True)
            return o, None
        out, _ = jax.lax.scan(body, q, None, length=2)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fn = jax.jit(jax.grad(step))
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((B, S), jnp.int32)
    dq = jax.eval_shape(grad_fn, q, seg)
    assert dq.shape == (B, S, H, hd)
