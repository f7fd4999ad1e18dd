"""The head and the loss a chunk of tokens at a time
(``models/model.py head_token_loss``) against ``token_loss`` of whole
logits — loss, ``dh`` and ``dw`` — over the maskings, layouts and scales a
family hands it, on one chip and on the eight virtual ones; the chips'
shares of the head's gradient summed in float32 and rounded once; the chunk
rule over the benchmark's cells; that no ``[tokens, vocabulary]`` array is left
in the gradient's program; and the row the step's account gets.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
from deepspeed_tpu.models import model
from deepspeed_tpu.models.model import (Head, head_chunk_tokens,
                                        head_token_loss, token_loss)
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, S, D, V = 8, 48, 16, 96
#: tokens of a chunk in these tests (the rule's own would take them all)
CHUNK = 32


def _seg(boundaries, batch=B, seq=S):
    """Every row packed alike: a new document at each of ``boundaries``."""
    row = np.zeros(seq, np.int32)
    for at in boundaries:
        row[at:] += 1
    return jnp.asarray(np.tile(row, (batch, 1)))


def _inputs(seed=0, batch=B, seq=S, dtype=jnp.float32, tied=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(k[0], (batch, seq, D)).astype(dtype)
    w = (jax.random.normal(k[1], (V, D) if tied else (D, V)) * 0.3
         ).astype(dtype)
    return h, w, jax.random.randint(k[2], (batch, seq), 0, V)


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(model, "head_chunk_tokens",
                        lambda t, v: min(t, CHUNK))


@pytest.fixture(params=["one_chip", "eight_chips", "four_by_model_2"])
def chips(request):
    """One device (the chunk loop alone), the tests' eight (a row of the
    batch a chip), or data 4 x model 2 — the region is manual over the
    data axes, ``model`` left to the partitioner."""
    devices = jax.devices()
    topo = {"one_chip": lambda: MeshTopology(devices=devices[:1]),
            "eight_chips": lambda: MeshTopology(devices=devices),
            "four_by_model_2": lambda: MeshTopology(
                devices=devices, model_parallel_size=2)}[request.param]()
    set_topology(topo)
    with topo.mesh:
        yield request.param


def _both(h, w, batch, tied=False, h_scale=1.0):
    """((loss, (dh, dw)) of whole float32 logits, the same of the shared
    head)."""
    def whole(h, w):
        logits = (h * h_scale).astype(jnp.float32) @ (
            w.T if tied else w).astype(jnp.float32)
        return token_loss(logits, batch)

    def chunked(h, w):
        return head_token_loss(h * jnp.asarray(h_scale, h.dtype), w, batch,
                               tied=tied)

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(h, w)
    return grad(whole), grad(chunked)


def _assert_same(want, got, rtol=1e-5, atol=1e-6):
    (l0, (dh0, dw0)), (l1, (dh1, dw1)) = want, got
    np.testing.assert_allclose(l1, l0, rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(dh1, np.float32),
                               np.asarray(dh0, np.float32),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(dw1, np.float32),
                               np.asarray(dw0, np.float32),
                               rtol=rtol, atol=atol)


MASKINGS = {
    "no_mask": lambda ids: {},
    "attention_mask": lambda ids: {
        "attention_mask": (ids % 5 != 0).astype(jnp.int32)},
    # one chip's tokens are [B * S] in rows of 48 and chunks of 32: a
    # document ends on a chunk's edge (token 32) and inside one (40)
    "documents_on_and_inside_a_chunk": lambda ids: {
        "segment_ids": _seg((32, 40))},
    "documents_and_a_mask": lambda ids: {
        "segment_ids": _seg((7, 32)),
        "attention_mask": (ids % 7 != 0).astype(jnp.int32)},
}


@pytest.mark.parametrize("masking", MASKINGS)
def test_the_shared_head_is_token_loss(masking, chips, small_chunks):
    h, w, ids = _inputs()
    batch = {"input_ids": ids, **MASKINGS[masking](ids)}
    _assert_same(*_both(h, w, batch))


@pytest.mark.parametrize("how", [
    dict(tied=True), dict(h_scale=1 / 16.0), dict(tied=True, h_scale=0.5)],
    ids=["tied", "scaled_h", "tied_and_scaled_h"])
def test_a_head_that_is_not_a_plain_product(how, chips, small_chunks):
    h, w, ids = _inputs(seed=1, tied=how.get("tied", False))
    batch = {"input_ids": ids, "segment_ids": _seg((20,))}
    _assert_same(*_both(h, w, batch, **how))


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_bf16_inputs_differ_by_the_products_rounding_alone(tied, chips,
                                                           small_chunks):
    """bf16 ``h`` and ``w``: the float32 reference multiplies the same
    rounded inputs, so what is left is ``dlogits`` rounded to bf16 before
    its two products and bf16 results — 2**-8 of a gradient's size."""
    h, w, ids = _inputs(seed=2, dtype=jnp.bfloat16, tied=tied)
    batch = {"input_ids": ids, "segment_ids": _seg((11, 32))}
    (l0, (dh0, dw0)), (l1, (dh1, dw1)) = _both(h, w, batch, tied=tied)
    assert dh1.dtype == dw1.dtype == jnp.bfloat16
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    for want, got in ((dh0, dh1), (dw0, dw1)):
        want, got = (np.asarray(a, np.float32) for a in (want, got))
        assert np.max(np.abs(got - want)) <= 2.0 ** -6 * np.max(np.abs(want))


@pytest.mark.parametrize("seq,chunks,chunk", [(47, 12, 32), (9, 3, 24)],
                         ids=["47x8_tokens", "9x8_tokens"])
def test_tokens_no_chunk_divides_are_padded_unscored(seq, chunks, chunk,
                                                     small_chunks):
    """376 tokens in chunks of at most 32: 12 of 32 with 8 unscored ones
    at the end, not 47 of 8 (its largest divisor); 72: 3 of 24."""
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    h, w, ids = _inputs(seed=3, seq=seq)
    batch = {"input_ids": ids, "segment_ids": _seg((5,), seq=seq)}
    with tracing.step_account("test/head"):
        _assert_same(*_both(h, w, batch))
    row, = tracing.head_chunks("test/head")
    assert (row["tokens"], row["chunks"], row["chunk"]) == (
        B * seq, chunks, chunk)


def test_tokens_under_the_rules_chunk_are_one_chunk(chips):
    """The rule's own chunk at 96 ids is far past these 384 tokens."""
    h, w, ids = _inputs(seed=4)
    batch = {"input_ids": ids, "segment_ids": _seg((13,))}
    with tracing.step_account("test/head"):
        _assert_same(*_both(h, w, batch))
    row, = tracing.head_chunks("test/head")
    assert row["chunks"] == 1 and row["chunk"] == row["tokens"]


def test_nothing_scored_is_a_loss_of_zero_and_no_nan(chips, small_chunks):
    h, w, ids = _inputs(seed=5)
    batch = {"input_ids": ids, "attention_mask": jnp.zeros_like(ids)}
    loss, (dh, dw) = jax.jit(jax.value_and_grad(
        lambda h, w: head_token_loss(h, w, batch), argnums=(0, 1)))(h, w)
    assert float(loss) == 0.0
    assert not np.any(np.asarray(dh)) and not np.any(np.asarray(dw))


def test_the_value_alone_is_the_gradients_value(chips, small_chunks):
    """Outside ``jax.grad`` (an evaluation) the loss is the forward
    rule's own sum."""
    h, w, ids = _inputs(seed=6)
    batch = {"input_ids": ids, "segment_ids": _seg((32,))}
    loss = lambda h, w: head_token_loss(h, w, batch)
    alone = jax.jit(loss)(h, w)
    with_grad, _ = jax.jit(jax.value_and_grad(loss))(h, w)
    np.testing.assert_allclose(alone, with_grad, rtol=1e-6)
    np.testing.assert_allclose(alone, token_loss(h @ w, batch), rtol=1e-5)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_chips_shares_are_summed_in_float32_and_rounded_once(
        tied, small_chunks):
    """bf16 ``h`` and ``w`` over the eight chips, a row of the batch each:
    the head's ``dw`` is the float32 sum of the chips' float32 shares,
    scaled by the loss's divisor in float32, rounded to bf16 once — within
    half a bf16 place (2**-8 of its size) of it, element for element.  Shares rounded
    before their sum, or a sum kept in bf16, are not."""
    topo = MeshTopology()
    set_topology(topo)
    h, w, ids = _inputs(seed=11, dtype=jnp.bfloat16, tied=tied)
    batch = {"input_ids": ids, "segment_ids": _seg((9, 32))}
    with topo.mesh:
        dw = jax.jit(jax.grad(lambda w: head_token_loss(
            h, w, batch, tied=tied)))(w)
    assert dw.dtype == jnp.bfloat16
    targets, scored = model.next_token_targets(batch)
    scored = scored.astype(jnp.float32)
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    shares = [model._chunk_nll(h[b], w, targets[b], scored[b], tied,
                               "main")[2] for b in range(B)]
    assert shares[0].dtype == jnp.float32
    want = np.sum(np.asarray(shares, np.float64), axis=0) / float(
        scored.sum())
    got = np.asarray(dw, np.float64)
    assert np.all(np.abs(got - want) <= 2.0 ** -8 * 1.001 * np.abs(want)
                  + 1e-30)
    each_rounded = np.sum([np.asarray(s.astype(jnp.bfloat16), np.float64)
                           for s in shares], axis=0) / float(scored.sum())
    assert np.any(np.abs(each_rounded - want) > 2.0 ** -8 * np.abs(want))


@pytest.mark.parametrize("rows,padded", [(64, False), (50, True)],
                         ids=["whole_tiles", "padded_for_the_sum"])
def test_the_sum_of_the_chips_pads_rows_that_are_no_eight_a_chip(rows,
                                                                 padded):
    """``_sum_of_chips``: float32 in, their sum out; rows that are no
    multiple of eight a chip are padded for the all-reduce and cut again
    (the TPU compiler refuses the sum otherwise:
    tests/test_chip_compile.py)."""
    dw = jax.random.normal(jax.random.PRNGKey(12), (8, rows, 16))
    summed = jax.jit(model._sum_of_chips)(dw)
    assert summed.shape == (rows, 16) and summed.dtype == jnp.float32
    np.testing.assert_allclose(summed, np.sum(np.asarray(dw), axis=0),
                               rtol=1e-6, atol=1e-6)
    text = jax.make_jaxpr(model._sum_of_chips)(dw).pretty_print()
    assert ("pad" in text) == padded


def test_a_prediction_modules_targets(chips, small_chunks):
    """Token t+2 at position t, scored where t+1 and t+2 are in t's
    document and unmasked: ``next_token_targets(ahead=2)`` through
    ``targets=``, against the sum written out."""
    h, w, ids = _inputs(seed=7)
    seg = _seg((10, 32))
    mask = (ids % 6 != 0).astype(jnp.int32)
    batch = {"input_ids": ids, "segment_ids": seg, "attention_mask": mask}
    targets, scored = model.next_token_targets(batch, ahead=2)
    got = jax.jit(lambda h, w: head_token_loss(
        h, w, batch, targets=(targets, scored), name="mtp"))(h, w)
    logp = jax.nn.log_softmax(h @ w, axis=-1)
    want, n = 0.0, 0
    for b in range(B):
        for t in range(S - 2):
            if seg[b, t] == seg[b, t + 1] == seg[b, t + 2] \
                    and mask[b, t + 1] and mask[b, t + 2]:
                want -= float(logp[b, t, ids[b, t + 2]])
                n += 1
    assert n == int(scored.sum()) > 0
    np.testing.assert_allclose(got, want / n, rtol=1e-5)


def test_a_heads_logits_and_loss_are_one_heads(chips, small_chunks):
    """``Head``: what a family hands over — its logits are ``apply_fn``'s,
    its loss the training step's, under ``ds.head_loss``."""
    h, w, ids = _inputs(seed=8, tied=True)
    batch = {"input_ids": ids}
    head = Head(h, w, tied=True)
    np.testing.assert_allclose(head.logits(), h @ w.T, rtol=1e-6)
    np.testing.assert_allclose(
        jax.jit(lambda: head.token_loss(batch))(),
        token_loss(h @ w.T, batch), rtol=1e-5)
    text = jax.jit(lambda h, w: Head(h, w, True).token_loss(batch)).lower(
        h, w).compile().as_text()
    assert tracing.SCOPE_HEAD_LOSS in text


sys.path.insert(0, os.path.join(REPO, "scripts"))
from head_loss_table import CELLS  # noqa: E402

#: cell -> the chunk its shapes get (PERF.md section 6, PR 69)
CELL_CHUNKS = {
    "gpt2-760m.dense-s1024": 2048,
    "gpt2-760m.packed-s2048-gas4": 2048,
    "gpt2-2.7b-zero3x4.dense-s2048": 2048,
    "olmoe-1b-7b.packed-s4096-gas8": 2048,
    "qwen3-next-80b-a3b.packed-s8192-gas2": 1024,
    "nemotron-3-nano-30b-a3b.packed-s8192-gas2": 1024,
    "joyai-llm-flash.packed-s8192-gas2": 1024,
    "laguna-s-2.1.packed-s8192-gas4": 2048,
    "mellum2-12b-a2.5b-ep4.packed-s8192-gas4-ep": 1024,
    "kimi-linear-48b-a3b.packed-s16384-traces": 1024,
    "xing4.0-29b-a4b.packed-s4096-pretrain": 1024,
    "phi-4-mini-flash-reasoning.packed-s16384-traces": 1024,
    "minicpm-sala.packed-s16384-longdocs": 2048,
    "granite-4.0-h-small.packed-s4096-gas1": 2048,
    "ouro-2.6b.packed-s16384-traces": 2048,
}


@pytest.mark.parametrize("cell", CELLS)
def test_the_chunk_rule_at_a_cells_shapes(cell):
    tokens, _, vocab, _ = CELLS[cell]
    chunk = head_chunk_tokens(tokens, vocab)
    assert chunk == CELL_CHUNKS[cell]
    assert tokens % chunk == 0          # nothing padded in any cell
    # a chunk's float32 logits stay on the chip, or (past 25,600 ids) are
    # at most four times that in HBM: Mellum2's 384 MiB the most
    from deepspeed_tpu.ops.pallas.vmem import xla_keeps
    on_chip = xla_keeps()
    assert 4 * chunk * vocab <= (on_chip if vocab <= 25600 else 4 * on_chip)


def test_the_cells_are_the_benchmarks():
    import json
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert set(CELLS) == {w["name"] for w in json.load(f)["workloads"]}


def _shapes_of(jaxpr, found):
    """Every array shape in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            if hasattr(var.aval, "shape"):
                found.add(tuple(var.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes_of(sub, found)
    return found


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_gradients_program_holds_no_tokens_by_vocabulary(tied,
                                                             small_chunks):
    set_topology(MeshTopology(devices=jax.devices()[:1]))
    h, w, ids = _inputs(seed=9, tied=tied)
    batch = {"input_ids": ids, "segment_ids": _seg((32,))}
    grad = lambda loss: jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(h, w)
    tokens = B * S
    whole = {(B, S, V), (B, S - 1, V), (tokens, V)}
    shapes = _shapes_of(grad(lambda h, w: head_token_loss(
        h, w, batch, tied=tied)).jaxpr, set())
    assert (CHUNK, V) in shapes
    assert not shapes & whole and not any(
        V in s and int(np.prod(s)) >= tokens * V for s in shapes)
    # the check sees them where they are
    assert _shapes_of(grad(lambda h, w: token_loss(
        h @ (w.T if tied else w), batch)).jaxpr, set()) & whole


def test_head_chunks_states_what_was_traced(small_chunks):
    """One row a call site, the shapes one chip's chunk loop was traced
    at; a step whose loss takes whole logits has none."""
    set_topology(MeshTopology())        # eight chips: a row of B a chip
    h, w, ids = _inputs(seed=10, tied=True)
    batch = {"input_ids": ids}
    with tracing.step_account("test/head"):
        jax.jit(jax.grad(lambda h: head_token_loss(
            h, w, batch, tied=True)
            + head_token_loss(h, w, batch, tied=True, name="mtp")))(h)
    assert tracing.head_chunks("test/head") == [
        {"name": name, "tokens": S, "d_model": D, "vocab": V, "chunk": 24,
         "chunks": 2, "whole_logits_bytes": 4 * S * V,
         "chunk_logits_bytes": 4 * 24 * V, "tied": True}
        for name in ("main", "mtp")]
    with tracing.step_account("test/whole"):
        jax.jit(lambda h: token_loss(h @ w.T, batch))(h)
    assert tracing.head_chunks("test/whole") is None
