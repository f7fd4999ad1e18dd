"""The flash kernels with a value head of another width than the score
head (latent attention: q, k 128 + 64 wide, v 128; differential attention:
q, k 64 wide, v a pair of heads, 128): forward and all three
gradients in Pallas' interpreter against the XLA einsum, the sizing
functions and the dispatch taking both widths, the step's account of its
flash calls — and, where the two widths are one, every family the
benchmark held lowering to the text it had at the parent commit."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import attention
from deepspeed_tpu.ops.attention import causal_attention, \
    xla_causal_attention
from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
from deepspeed_tpu.ops.pallas.ds_flash_attention import ds_flash_attention
from deepspeed_tpu.telemetry import tracing
from tests import flash_step_texts

HERE = os.path.dirname(os.path.abspath(__file__))

#: (S, H, KV, dk, dv, block): the toy widths over two blocks of keys, GQA
#: and not, and the configuration's own 192 / 128 at a short S
SHAPES = {
    "toy_24_16": (64, 4, 4, 24, 16, 32),
    "toy_24_16_gqa": (64, 4, 2, 24, 16, 32),
    "mla_192_128": (32, 2, 2, 192, 128, 16),
    "mla_192_128_gqa": (32, 2, 1, 192, 128, 16),
    "toy_16_24_wider": (64, 4, 2, 16, 24, 32),
    "diff_64_128_gqa": (32, 4, 2, 64, 128, 16),
}


def _inputs(S, H, KV, dk, dv, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k[0], (2, S, H, dk))
    kk = jax.random.normal(k[1], (2, S, KV, dk))
    v = jax.random.normal(k[2], (2, S, KV, dv))
    w = jax.random.normal(k[3], (2, S, H, dv))
    return q, kk, v, w


def _segments(S, packed):
    if not packed:
        return None
    cuts = np.array([[S // 4, S // 2 + 1, S - 3], [1, S // 3, S // 2]])
    return jnp.asarray((np.arange(S)[None, :, None]
                        >= cuts[:, None, :]).sum(-1).astype(np.int32))


def _einsum(q, k, v, seg):
    rep = q.shape[2] // k.shape[2]
    return xla_causal_attention(q, jnp.repeat(k, rep, axis=2),
                                jnp.repeat(v, rep, axis=2), seg)


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_two_widths_match_the_einsum(shape, packed, interpret_pallas):
    S, H, KV, dk, dv, block = SHAPES[shape]
    q, k, v, w = _inputs(S, H, KV, dk, dv)
    seg = _segments(S, packed)
    flash = lambda q, k, v: ds_flash_attention(
        q, k, v, segment_ids=seg, block_q=block, block_k=block // 2)
    out = flash(q, k, v)
    assert out.shape == (2, S, H, dv)
    want = _einsum(q, k, v, seg)
    np.testing.assert_allclose(out, want, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: jnp.sum(_einsum(*a, seg) * w),
                      (0, 1, 2))(q, k, v)
    for a, b, width in zip(got, wanted, (dk, dk, dv)):
        assert a.shape == b.shape and a.shape[-1] == width
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_the_scale_is_the_score_heads(interpret_pallas):
    """1/sqrt(dk) by default, whatever v's width; a scale given is used."""
    q, k, v, _ = _inputs(32, 2, 2, 24, 16)
    out = ds_flash_attention(q, k, v, block_q=16, block_k=16)
    np.testing.assert_allclose(
        out, ds_flash_attention(q, k, v, sm_scale=24 ** -0.5, block_q=16,
                                block_k=16), atol=1e-6)
    other = ds_flash_attention(q, k, v, sm_scale=16 ** -0.5, block_q=16,
                               block_k=16)
    assert float(jnp.abs(out - other).max()) > 1e-3


def test_unlike_score_heads_are_refused_and_a_wider_value_head_is_not():
    q, k, v, _ = _inputs(32, 2, 2, 16, 24)
    with pytest.raises(ValueError, match="share the score width"):
        ds_flash_attention(q, k[..., :8], v[..., :8])
    assert jax.eval_shape(ds_flash_attention, q, k, v).shape \
        == (2, 32, 2, 24)


@pytest.mark.parametrize("window", [None, 24], ids=["causal", "window_24"])
def test_wider_values_packed_as_differential_attention_calls_them(
        window, interpret_pallas):
    """Score width 64, value width 128, two query heads to a key head,
    packed — under a window and without: one of the two maps of a
    differential layer (models/phi4flash.py)."""
    S, H, KV, dk, dv, block = 64, 4, 2, 64, 128, 16
    q, k, v, w = _inputs(S, H, KV, dk, dv, seed=3)
    seg = _segments(S, True)
    rep = H // KV
    flash = lambda q, k, v: ds_flash_attention(
        q, k, v, segment_ids=seg, window=window, block_q=block,
        block_k=block)
    einsum = lambda q, k, v: xla_causal_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), seg,
        window)
    np.testing.assert_allclose(flash(q, k, v), einsum(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(einsum(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_the_working_set_counts_both_widths():
    """v (and o, do) at its own padded width beside k (and q): 192 / 128
    stages 1.5 + 1 lane tiles... padded 2 + 1, where 192 / 192 stages 2 +
    2; one width is what it always was."""
    same = dsf.working_set_bytes(8192, 192, 2, packed=True)
    assert dsf.working_set_bytes(8192, 192, 2, packed=True,
                                 v_head_dim=192) == same
    two = dsf.working_set_bytes(8192, 192, 2, packed=True, v_head_dim=128)
    assert same - two == 2 * 8192 * 128 * 2
    assert dsf.working_set_bytes(8192, 128, 2, packed=True) < two < same
    assert dsf.vmem_fits(8192, 192, 2, budget_bytes=two, packed=True,
                         v_head_dim=128)
    assert not dsf.vmem_fits(8192, 192, 2, budget_bytes=two, packed=True)
    # wider values than keys: 64 / 128 stages, and tiles, as 128 / 128 does
    # (64 pads to a lane tile); 64 / 256 more than that
    wider = dsf.working_set_bytes(16384, 64, 2, packed=True, v_head_dim=128)
    assert wider == dsf.working_set_bytes(16384, 128, 2, packed=True)
    assert dsf.working_set_bytes(16384, 64, 2, packed=True,
                                 v_head_dim=256) > wider


def test_the_dispatch_checks_and_routes_by_both_widths(monkeypatch,
                                                       interpret_pallas):
    """On a TPU ``auto`` hands two widths to the from-scratch kernel
    (never to the stock wrapper, which takes one), with the VMEM check
    sized from both; off it, and under ``xla``, the einsum takes them."""
    q, k, v, _ = _inputs(256, 2, 2, 24, 16)
    seen = []
    fits = dsf.vmem_fits
    monkeypatch.setattr(dsf, "vmem_fits", lambda *a, **kw: (
        seen.append(kw.get("v_head_dim")), fits(*a, **kw))[1])
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_FLASH_STATUS", {})
    want = _einsum(q, k, v, None)
    with tracing.step_account("test/dispatch"):
        got = causal_attention(q, k, v, impl="auto")
        packed = causal_attention(q, k, v, impl="auto",
                                  segment_ids=jnp.zeros((2, 256), jnp.int32))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(packed, want, atol=2e-5)
    assert seen == [16, 16]
    rows = tracing.flash_calls("test/dispatch")
    assert [(r["dk"], r["dv"], r["packed"]) for r in rows] \
        == [(24, 16, False), (24, 16, True)]
    assert all(status is True for status in attention.flash_status().values())
    np.testing.assert_allclose(causal_attention(q, k, v, impl="xla"), want,
                               atol=1e-6)
    np.testing.assert_allclose(causal_attention(q, k, v, impl="flash"),
                               want, atol=2e-5)


def test_flash_calls_is_every_familys_account(interpret_pallas):
    """One row per shape, both widths in it, the limit the calls ask for,
    a head's [interior, boundary] tiles; None where no flash call was
    traced."""
    assert tracing.flash_calls("test/none") is None
    q, k, v, _ = _inputs(64, 4, 2, 24, 16)
    with tracing.step_account("test/flash"):
        jax.eval_shape(lambda *a: ds_flash_attention(*a), q, k, v)
        jax.eval_shape(lambda *a: ds_flash_attention(*a), q, k, v)
        jax.eval_shape(lambda *a: ds_flash_attention(
            *a, segment_ids=jnp.zeros((2, 64), jnp.int32)),
            q, k, k)
    one_tile = [0, 1]       # [interior, boundary]: one tile, on the diagonal
    assert tracing.flash_calls("test/flash") == [
        {"batch": 2, "seq_len": 64, "heads": 4, "kv_heads": 2, "dk": 24,
         "dv": 16, "packed": False, "blocks": [64, 64],
         "vmem_limit_bytes": None, "tiles": one_tile},
        {"batch": 2, "seq_len": 64, "heads": 4, "kv_heads": 2, "dk": 24,
         "dv": 24, "packed": True, "blocks": [64, 64],
         "vmem_limit_bytes": None, "tiles": one_tile}]


@pytest.mark.parametrize("family", sorted(flash_step_texts.FAMILIES))
def test_with_one_width_the_step_lowers_to_the_parents_text(family):
    """``dk == dv``: the toy step of each family the benchmark held lowers
    to the text it had before the kernels took two widths (digests taken
    at the parent commit with tests/flash_step_texts.py: PERF.md section
    6, PR 38).  ``qwen3_next`` and ``nemotron_h`` were taken again at PR
    39 and at PR 44, which changed their expert layers on purpose (a held
    plan's consumers walk its live prefix: tests/test_held_live_prefix.py
    says which steps left the parent's text and why; the two sums into
    tokens are one scatter-add each off the chip, with no ``cond``:
    tests/test_held_row_sum.py); the flash kernels' part
    of them is the ``gpt2`` and ``olmoe`` digests', which PR 39 left.
    All four were taken again at PR 49: the forward selects once and the
    backward kernels scale no tile, so every family's kernels' text changed
    on purpose and the old digests went with the old bodies — which
    tests/test_flash_tile_bodies.py keeps as its oracle, result for
    result.  ``olmoe`` was taken again at PR 59, which changed its expert
    layer on purpose (a row is weighted where its expert is and the way
    back is ``sum_rows``: tests/test_grouped_gemm.py holds it to the dense
    per-expert reference); the three others stood.  All four were taken
    again at PR 69: each step ends in the loss, which no longer forms
    whole logits (``models/model.py head_token_loss``, held to
    ``token_loss`` by tests/test_head_loss.py), and at PR 71: the toy
    batch is packed, and a packed call's tile loops take one more bound,
    from its documents (tests/test_flash_document_skip.py holds the
    results to the old calls', to the bit, and the call with no
    ``segment_ids`` to its old text); tests/flash_step_texts.py says which
    digests and why."""
    with open(os.path.join(HERE, "data", "flash_step_digests.json")) as f:
        want = json.load(f)
    assert flash_step_texts.digest(family) == want[family]
