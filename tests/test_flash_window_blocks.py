"""The windowed flash kernels at other groups and block shapes, and what they
never read (tests/test_flash_window.py has the windows themselves, on the
same inputs).  A file of its own so that ``--dist loadfile`` gives the
window's tests to two workers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention
from deepspeed_tpu.ops.attention import causal_attention
from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
from deepspeed_tpu.ops.pallas.ds_flash_attention import ds_flash_attention
from deepspeed_tpu.telemetry import tracing

from tests.test_flash_window import (  # noqa: F401 (the fixtures come by name)
    BLOCK, _einsum, _inputs, REPS, S, _segments)


@pytest.mark.parametrize("blocks", [(64, 32), (64, 16), (16, 16)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("rep", REPS)
def test_groups_and_block_shapes(rep, packed, blocks, interpret_pallas):
    """6 and 9 query heads to a KV head (dK/dV accumulate over the group),
    a q-block of two and four key blocks, at a window between multiples."""
    q, k, v, w = _inputs(rep)
    seg = _segments(packed)
    flash = lambda q, k, v: ds_flash_attention(
        q, k, v, segment_ids=seg, block_q=blocks[0], block_k=blocks[1],
        window=40)
    np.testing.assert_allclose(flash(q, k, v), _einsum(q, k, v, seg, 40),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: jnp.sum(_einsum(*a, seg, 40) * w),
                      (0, 1, 2))(q, k, v)
    for a, b in zip(got, wanted):
        np.testing.assert_allclose(
            a, b, atol=2e-5 * (1 + float(jnp.abs(b).max())))


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("rep", [1, 9])
def test_keys_below_the_window_are_never_read(rep, packed, interpret_pallas):
    """K and V poisoned with NaN below the first tile a q-block visits:
    that block's output and dq are what they were (a masked-only loop would
    multiply the NaN by a zero weight and keep it)."""
    window = 40
    q, k, v, w = _inputs(rep)
    seg = _segments(packed)
    # q-blocks from 3 on start at tile (96 - 39) // 32 = 1: keys >= 32
    rows = slice(96, S)
    assert (96 - (window - 1)) // BLOCK * BLOCK >= 32
    poison = jnp.arange(S)[None, :, None, None] < 32
    kp, vp = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
    fn = lambda q, k, v: ds_flash_attention(
        q, k, v, segment_ids=seg, block_q=BLOCK, block_k=BLOCK,
        window=window)
    out, vjp = jax.vjp(fn, q, k, v)
    outp, vjpp = jax.vjp(fn, q, kp, vp)
    np.testing.assert_array_equal(outp[:, rows], out[:, rows])
    cot = jnp.where(jnp.arange(S)[None, :, None, None] >= 96, w, 0.0)
    dq, dqp = vjp(cot)[0], vjpp(cot)[0]
    assert np.isfinite(np.asarray(dq)).all()
    np.testing.assert_array_equal(dqp[:, rows], dq[:, rows])


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("rep", [1, 6])
def test_queries_past_the_window_are_never_read(rep, packed,
                                                interpret_pallas):
    """dK/dV's loop over q-blocks STOPS where the window ends: Q, dO, lse
    and delta poisoned from the first unvisited q-block on leave a key
    block's dk and dv what they were."""
    window = 40
    q, k, v, w = _inputs(rep)
    seg = _segments(packed)
    _, (q_, k_, v_, o, lse) = dsf._fwd(q, k, v, seg, True, None, BLOCK,
                                       BLOCK, window=window)
    delta = jnp.sum(jnp.transpose(w * o, (0, 2, 1, 3)), axis=-1)
    clean = dsf._bwd_calls(q, k, v, w, lse, delta, seg, True, None, BLOCK,
                           BLOCK, window=window)
    # key block 0 (keys 0..31) is seen by queries up to 31 + 39 = 70:
    # q-blocks 0..2; from row 96 on nothing of it may be read
    bad = jnp.arange(S) >= 96
    nan4 = lambda x: jnp.where(bad[None, :, None, None], jnp.nan, x)
    nan3 = lambda x: jnp.where(bad[None, None, :], jnp.nan, x)
    dirty = dsf._bwd_calls(nan4(q), k, v, nan4(w), nan3(lse), nan3(delta),
                           seg, True, None, BLOCK, BLOCK, window=window)
    for a, b in zip(dirty[1:], clean[1:]):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_array_equal(a[:, :32], b[:, :32])
    assert not np.isfinite(np.asarray(dirty[1][:, 96:])).all()


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_causal_attention_hands_the_window_down(packed, monkeypatch,
                                                interpret_pallas):
    """``impl="auto"`` on a TPU takes the windowed kernels (at the blocks
    the dispatch chose), ``"xla"`` the masked einsum, and a window over
    the sequence the causal path."""
    q, k, v, _ = _inputs(9, s=256)
    seg = _segments(packed, s=256)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_STATUS", {})
    monkeypatch.setattr(attention, "WINDOW_BLOCKS", (64, 64))
    want = _einsum(q, k, v, seg, 100)
    with tracing.step_account("test/dispatch_window"):
        got = causal_attention(q, k, v, impl="auto", segment_ids=seg,
                               window=100)
        causal_attention(q, k, v, impl="auto", segment_ids=seg, window=256)
    np.testing.assert_allclose(got, want, atol=2e-5)
    rows = sorted(tracing.flash_calls("test/dispatch_window"),
                  key=lambda r: "window" in r)
    assert [r.get("window") for r in rows] == [None, 100]
    assert rows[1]["blocks"] == [64, 64]
    assert all(s is True for s in attention.flash_status().values())
    np.testing.assert_allclose(
        causal_attention(q, k, v, impl="xla", segment_ids=seg, window=100),
        want, atol=1e-6)
    np.testing.assert_allclose(
        causal_attention(q, k, v, impl="flash", segment_ids=seg, window=100),
        want, atol=2e-5)
