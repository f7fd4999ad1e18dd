"""A sliding window in the three flash kernels: query i attends keys j
with ``i - j < window`` — forward and all three gradients in Pallas'
interpreter against the XLA mask, with and without ``segment_ids``, for
windows below, at, between multiples of and beyond the block, at 6 and 9
query heads to a KV head; a window no shorter than the sequence is the
causal call itself (same kernels, same bits); and the tiles outside the
window are SKIPPED, not masked: NaN there changes nothing."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import xla_causal_attention
from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
from deepspeed_tpu.ops.pallas.ds_flash_attention import ds_flash_attention
from deepspeed_tpu.telemetry import tracing

S, BLOCK = 128, 32
#: below the block, at it, between two multiples of it, at the second,
#: beyond it and one key short of the sequence
WINDOWS = (1, 7, 32, 33, 50, 64, 100, 127)
#: query heads to a KV head: 48 / 8 and 72 / 8, and none
REPS = (1, 6, 9)


def _inputs(rep, kv=1, hd=16, seed=0, s=S):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k[0], (2, s, kv * rep, hd))
    kk = jax.random.normal(k[1], (2, s, kv, hd))
    v = jax.random.normal(k[2], (2, s, kv, hd))
    w = jax.random.normal(k[3], (2, s, kv * rep, hd))
    return q, kk, v, w


def _segments(packed, s=S):
    if not packed:
        return None
    cuts = np.array([[s // 4, s // 2 + 1, s - 3], [1, s // 3, s // 2]])
    return jnp.asarray((np.arange(s)[None, :, None]
                        >= cuts[:, None, :]).sum(-1).astype(np.int32))


def _einsum(q, k, v, seg, window):
    rep = q.shape[2] // k.shape[2]
    return xla_causal_attention(q, jnp.repeat(k, rep, axis=2),
                                jnp.repeat(v, rep, axis=2), seg, window)


def test_the_xla_mask_is_the_windows():
    """The oracle itself: position i's weights are zero outside
    ``(i - window, i]`` and a softmax inside."""
    q, k, v, _ = _inputs(1, s=16)
    eye = jnp.broadcast_to(jnp.eye(16)[None, :, None, :], (2, 16, 1, 16))
    probs = np.asarray(xla_causal_attention(q, k, eye, None, 5))[0, :, 0]
    for i in range(16):
        seen = np.nonzero(probs[i])[0]
        assert seen.min() == max(0, i - 4) and seen.max() == i
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-6)


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_kernels_match_the_xla_mask(window, packed,
                                             interpret_pallas):
    q, k, v, w = _inputs(2, kv=2)
    seg = _segments(packed)
    flash = lambda q, k, v: ds_flash_attention(
        q, k, v, segment_ids=seg, block_q=BLOCK, block_k=BLOCK,
        window=window)
    want = _einsum(q, k, v, seg, window)
    np.testing.assert_allclose(flash(q, k, v), want, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: jnp.sum(_einsum(*a, seg, window) * w),
                      (0, 1, 2))(q, k, v)
    for a, b in zip(got, wanted):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * (1 + float(jnp.abs(b).max())))


@pytest.mark.parametrize("window", [S, S + 1, 10 * S])
def test_a_window_over_the_sequence_is_the_causal_call(window,
                                                       interpret_pallas):
    """Same jaxpr (so the same kernels under the same names), same bits."""
    q, k, v, w = _inputs(2)
    seg = _segments(True)
    fn = lambda window: (lambda q, k, v: jnp.sum(ds_flash_attention(
        q, k, v, segment_ids=seg, block_q=BLOCK, block_k=BLOCK,
        window=window) * w))
    causal = jax.make_jaxpr(jax.value_and_grad(fn(None), (0, 1, 2)))(q, k, v)
    wide = jax.make_jaxpr(jax.value_and_grad(fn(window), (0, 1, 2)))(q, k, v)
    assert str(wide) == str(causal) and "ds_flash_win" not in str(causal)
    for a, b in zip(jax.tree.leaves(jax.grad(fn(window), (0, 1, 2))(q, k, v)),
                    jax.tree.leaves(jax.grad(fn(None), (0, 1, 2))(q, k, v))):
        np.testing.assert_array_equal(a, b)


def test_the_windowed_calls_carry_their_own_names(interpret_pallas):
    q, k, v, w = _inputs(2)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(ds_flash_attention(
        *a, block_q=BLOCK, block_k=BLOCK, window=40) * w), (0, 1, 2)))(
            q, k, v))
    names = ("ds_flash_win_fwd", "ds_flash_win_bwd_dkv",
             "ds_flash_win_bwd_dq")
    assert all(n in text for n in names)
    assert set(names) <= set(re.findall(r"name=(\w+)", text))
    for plain in ("ds_flash_fwd", "ds_flash_bwd_dkv", "ds_flash_bwd_dq"):
        assert plain not in text


def test_the_account_holds_the_window_and_the_tiles_visited(
        interpret_pallas):
    q, k, v, _ = _inputs(9)
    with tracing.step_account("test/window"):
        jax.eval_shape(lambda *a: ds_flash_attention(
            *a, block_q=64, block_k=32, window=40), q, k, v)
        jax.eval_shape(lambda *a: ds_flash_attention(
            *a, block_q=64, block_k=32), q, k, v)
    causal, windowed = sorted(tracing.flash_calls("test/window"),
                              key=lambda r: "window" in r)
    assert windowed["window"] == 40 and windowed["blocks"] == [64, 32]
    # two key blocks under the q-block's own rows, ceil(39 / 32) before
    assert windowed["k_tiles_per_q_block"] == 4
    assert windowed["heads"] == 9 and windowed["kv_heads"] == 1
    assert "window" not in causal and "k_tiles_per_q_block" not in causal
    assert dsf.window_k_tiles(512, 512, 512) == 2
    assert dsf.window_k_tiles(512, 256, 256) == 3
    assert dsf.window_k_tiles(512, 128, 128) == 5


def test_a_window_needs_the_causal_mask():
    q, k, v, _ = _inputs(1, s=16)
    with pytest.raises(ValueError, match="window"):
        ds_flash_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        ds_flash_attention(q, k, v, window=0)
