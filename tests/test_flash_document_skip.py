"""The packed flash kernels' tile loops start (dK/dV's: stop) at the
q-block's own documents (PR 71): ``document_block_tables`` /
``document_block_bounds`` against a brute-force table of "this tile holds a
pair of equal ids"; the three kernels against the same kernels with the
documents' bounds taken away (every tile visited and masked, to the bit)
and against the parent's (the fixture ``parent_kernels`` of
tests/test_flash_tile_bodies.py); tiles that are skipped are not read; the
counter that leaves the compiled step beside its loss against the host
function and benchmarks/scripts/visited_tiles.py on the same rows; the
dense call's lowered text against the parent commit's."""
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops.pallas import ds_flash_attention as dsf
from deepspeed_tpu.telemetry import tracing
from tests.test_flash_tile_bodies import (  # noqa: F401 (a fixture)
    _all_three, _hold_to_the_parent, parent_kernels)
from tests.util import base_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(*path):
    spec = importlib.util.spec_from_file_location(
        path[-1][:-3], os.path.join(REPO, "benchmarks", *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _rows(lengths, seq_len):
    """[1, seq_len] ids of documents of ``lengths`` laid end to end (the
    last one cut, or run on, to the row's end)."""
    ids = np.repeat(np.arange(len(lengths)), lengths)[:seq_len]
    return np.concatenate(
        [ids, np.full(seq_len - len(ids), len(lengths) - 1)])[None].astype(
            np.int32)


def _traffic_rows(name, rows, seed):
    """``rows`` rows drawn as the traffic file ``name`` draws them."""
    datagen = _bench("harness", "datagen.py")
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           name + ".json")) as f:
        traffic = json.load(f)
    documents = datagen.Documents(np.random.default_rng(seed),
                                  traffic["documents"])
    return np.concatenate([
        _rows([n for n, _ in documents.row(traffic["seq_len"])],
              traffic["seq_len"]) for _ in range(rows)])


def _tiles_with_a_pair(segments, bq, bk):
    """[rows, S / bq, S / bk] bool by brute force: some query of the
    q-block and some key of the key block have the same id."""
    rows, seq = segments.shape
    out = np.zeros((rows, seq // bq, seq // bk), bool)
    for r in range(rows):
        for i in range(seq // bq):
            same = segments[r, i * bq:(i + 1) * bq, None] \
                == segments[r, None, :]
            out[r, i] = same.reshape(bq, seq // bk, bk).any((0, 2))
    return out


def _brute_bounds(segments, bq, bk):
    """(the first key block with a pair a q-block, the last q-block with a
    pair a key block) of the brute-force table."""
    pairs = _tiles_with_a_pair(segments, bq, bk)
    assert pairs.any(2).all() and pairs.any(1).all()
    return (pairs.argmax(2),
            pairs.shape[1] - 1 - pairs[:, ::-1].argmax(1))


MONOTONE = {
    "s8192_web": lambda: (_traffic_rows("packed-s8192-gas2", 4, 11), 512, 512),
    "s16384_traces": lambda: (_traffic_rows("packed-s16384-traces", 2, 5),
                              512, 512),
    "s4096_bq_2bk": lambda: (_traffic_rows("packed-s4096-gas8", 3, 7),
                             512, 256),
    "starts_mid_block": lambda: (_rows([64 + 17, 100, 64 * 3 - 5, 1, 300],
                                       512), 64, 64),
    "one_document": lambda: (np.zeros((2, 512), np.int32), 64, 32),
}
ANY_ORDER = {
    # trailing padding under the first document's id, under an id of its
    # own below every document's, and ids in no order at all
    "padding_id_0": lambda: (np.where(np.arange(512) < 400,
                                      _rows([90, 110, 200], 512) + 1, 0),
                             64, 64),
    "padding_id_minus_1": lambda: (np.where(np.arange(512) < 333,
                                            _rows([300, 33], 512), -1),
                                   64, 32),
    "ids_return": lambda: (_rows([70, 50, 100, 36], 256) % 2, 64, 32),
    "random_ids": lambda: (np.random.default_rng(3).integers(
        0, 40, (3, 512)).astype(np.int32) // np.array([[1], [7], [20]]),
        64, 64),
}


@pytest.mark.parametrize("case", sorted(MONOTONE))
def test_the_bounds_of_monotone_rows_are_the_brute_force_tables(case):
    segments, bq, bk = MONOTONE[case]()
    first, last = dsf.document_block_bounds(segments, bq, bk)
    want_first, want_last = _brute_bounds(segments, bq, bk)
    np.testing.assert_array_equal(first, want_first)
    np.testing.assert_array_equal(last, want_last)
    assert first.dtype == last.dtype == np.int32
    # the device's tables are the host's
    on_device = dsf.document_block_tables(jnp.asarray(segments), bq, bk)
    np.testing.assert_array_equal(on_device[0], first)
    np.testing.assert_array_equal(on_device[1], last)
    causal_first = np.zeros_like(first)
    causal_last = np.full_like(last, segments.shape[1] // bq - 1)
    if case == "one_document":      # position's own bounds, nothing more
        np.testing.assert_array_equal(first, causal_first)
        np.testing.assert_array_equal(last, causal_last)
    else:
        assert (first > causal_first).any() and (last < causal_last).any()


@pytest.mark.parametrize("case", sorted(ANY_ORDER))
def test_no_order_of_ids_loses_a_tile_with_a_visible_pair(case):
    segments, bq, bk = ANY_ORDER[case]()
    pairs = _tiles_with_a_pair(segments, bq, bk)
    first, last = dsf.document_block_bounds(segments, bq, bk)
    rows, nq, nk = pairs.shape
    inside_first = np.arange(nk)[None, None, :] >= first[:, :, None]
    inside_last = np.arange(nq)[None, :, None] <= last[:, None, :]
    assert not (pairs & ~inside_first).any()
    assert not (pairs & ~inside_last).any()


# ------------------------------------------------------------ the kernels
S, BQ = 384, 64
WINDOWS = {"causal": None, "w512": BQ, "w1024": 2 * BQ}
WIDTHS = {"dk=dv=128": (128, 128), "mla_192_128": (192, 128),
          "gpt2_96": (96, 96)}
#: two rows of one call with documents of their own: starts inside blocks,
#: a document of several blocks, one of a token, a row that ends in a long
#: document — the table is read by the row
PACKED = np.concatenate([
    _rows([64 + 17, 100, 64 * 2 - 5, 1, 300], S),
    _rows([30, 160, 64, 10], S)])


def _inputs(rep, dk, dv):
    key = jax.random.split(jax.random.PRNGKey(7), 4)
    rows = PACKED.shape[0]
    return (jax.random.normal(key[0], (rows, S, rep, dk)),
            jax.random.normal(key[1], (rows, S, 1, dk)),
            jax.random.normal(key[2], (rows, S, 1, dv)),
            jax.random.normal(key[3], (rows, S, rep, dv)))


def _bounded_by_position(segment_ids, block_q, block_k):
    """``document_block_tables`` with nothing to say: every q-block from
    key block 0, every key block to the last q-block."""
    rows, seq = segment_ids.shape
    return (jnp.zeros((rows, seq // block_q), jnp.int32),
            jnp.full((rows, seq // block_k), seq // block_q - 1, jnp.int32))


@pytest.mark.parametrize("rep", [1, 4], ids=["rep1", "rep4"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_skipped_tiles_gave_exact_zeros(window, widths, rep, monkeypatch,
                                        interpret_pallas, parent_kernels):
    """``o``, ``lse``, ``dq``, ``dk``, ``dv`` with other documents' tiles
    skipped are, to the bit, those with the same tiles visited and masked
    (what a masked tile adds is an exact zero: ``alpha`` 1, ``p`` 0) — and
    the parent's kernels' as far as PR 49's tile body is theirs."""
    window = WINDOWS[window]
    q, k, v, w = _inputs(rep, *WIDTHS[widths])
    seg, blocks = jnp.asarray(PACKED), (BQ, BQ // 2)
    first, _ = dsf.document_block_bounds(PACKED, *blocks)
    visited, positional = dsf.visited_tiles(first, S, *blocks, True, window)
    assert visited < (0.6 if window is None else 1) * positional
    new = _all_three(q, k, v, w, seg, blocks, window)
    monkeypatch.setattr(dsf, "document_block_tables", _bounded_by_position)
    masked = _all_three(q, k, v, w, seg, blocks, window)
    for a, b in zip((*new[:2], *new[2]), (*masked[:2], *masked[2])):
        np.testing.assert_array_equal(a, b)
    parent_kernels()
    _hold_to_the_parent(new, _all_three(q, k, v, w, seg, blocks, window))


def test_a_skipped_tile_is_never_read(monkeypatch, interpret_pallas):
    """Two documents that meet at a block's edge: NaN in the first one's
    values reaches nothing of the second — its tiles of the first are
    skipped, where a masked tile's ``0 * NaN`` would have been NaN — and the
    first one's NaN queries and cotangents nothing of the second one's
    dK / dV."""
    cut = 3 * BQ
    seg = jnp.asarray(_rows([cut, S - cut], S))
    q, k, v, w = (x[:1] for x in _inputs(2, 32, 32))
    first_doc = jnp.arange(S)[None, :, None, None] < cut
    o, lse, (dq, dk, dv) = _all_three(
        q, k, jnp.where(first_doc, jnp.nan, v), w, seg, (BQ, BQ), None)
    assert np.isfinite(o[:, cut:]).all() and np.isfinite(dq[:, cut:]).all()
    assert np.isnan(np.asarray(o[:, :cut])).all()
    # dK/dV of the first document's blocks stop before the second's queries
    clean = _all_three(q, k, v, w, seg, (BQ, BQ), None)
    loud_q = jnp.where(first_doc, q, jnp.nan)
    _, _, (_, dk2, dv2) = _all_three(loud_q, k, v, w, seg, (BQ, BQ), None)
    np.testing.assert_array_equal(dk2[:, :cut], clean[2][1][:, :cut])
    np.testing.assert_array_equal(dv2[:, :cut], clean[2][2][:, :cut])
    # with the bounds taken away the same tiles are read, and it shows
    monkeypatch.setattr(dsf, "document_block_tables", _bounded_by_position)
    masked = _all_three(q, k, jnp.where(first_doc, jnp.nan, v), w, seg,
                        (BQ, BQ), None)
    assert np.isnan(np.asarray(masked[0][:, cut:])).all()


def test_a_packed_kernel_holds_one_tile_loop_and_no_cond(interpret_pallas):
    """As many ``while`` as the dense calls have (a kernel's one tile loop
    and the interpreter's grid loop) and as many ``cond`` (dK/dV's
    ``pl.when`` pair, which writes or adds a group's head): the documents
    move the bound of the loop that was there."""
    q, k, v, w = _inputs(1, 32, 32)

    def loops(seg):
        jaxpr = str(jax.make_jaxpr(lambda *a: _all_three(
            *a, seg, (BQ, BQ), None))(q, k, v, w))
        return jaxpr.count("while["), jaxpr.count("cond[")

    assert loops(jnp.asarray(PACKED)) == loops(None)


# ------------------------------------------------------------ the counter
TOY_S = 2048


def _toy_engine():
    from deepspeed_tpu.models.mixtral import mixtral_model
    model = mixtral_model(
        size="olmoe-1b-7b", num_layers=1, d_model=32, num_heads=2,
        num_kv_heads=2, d_ff=32, num_experts=4, top_k=2, vocab_size=128,
        max_seq_len=TOY_S, moe_dispatch="grouped", remat=True,
        attention_impl="flash", dtype="float32")
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=2,
            gradient_accumulation_steps=2, seed=1),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",)))
    return engine


def _toy_batch(seed, packed=True):
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(0, 128, (2, 2, TOY_S), np.int32)}
    if packed:
        lengths = lambda: rng.integers(40, 700, 64)
        batch["segment_ids"] = np.stack([
            np.concatenate([_rows(lengths(), TOY_S) for _ in range(2)])
            for _ in range(2)])
    return batch


def test_the_step_says_what_its_documents_let_it_skip(interpret_pallas):
    visited_tiles = _bench("scripts", "visited_tiles.py")
    tracing.reset_programs()
    engine = _toy_engine()
    batches = [_toy_batch(seed) for seed in (0, 1)]
    for batch in batches:
        engine.train_batch(batch=batch)
    load = engine.step_load()
    assert load["steps"] == 2
    row, = tracing.flash_calls()
    assert row["packed"] and row["blocks"] == [512, 512]
    for step, batch in zip(load["last"], batches):
        rows = batch["segment_ids"].reshape(-1, TOY_S)
        first, _ = dsf.document_block_bounds(rows, 512, 512)
        visited, positional = dsf.visited_tiles(first, TOY_S, 512, 512)
        assert step[dsf.VISITED_TILES] == visited \
            == visited_tiles.visited(rows, 512, 512)
        assert step[dsf.POSITIONAL_TILES] == positional \
            == sum(row["tiles"]) * len(rows)
        assert 0 < visited < 0.8 * positional
    assert tracing.step_load()["totals"][dsf.VISITED_TILES] == sum(
        step[dsf.VISITED_TILES] for step in load["last"])
    # data out of the step, beside its loss: no host callback in it
    assert "callback" not in engine.compile_train_step(batches[0]).as_text()
    tracing.reset_programs()


def test_a_step_with_no_segment_ids_counts_no_tiles(interpret_pallas):
    tracing.reset_programs()
    engine = _toy_engine()
    engine.train_batch(batch=_toy_batch(0, packed=False))
    load = engine.step_load()
    assert not any(name.startswith("flash/")
                   for step in load["last"] for name in step)
    assert not any(name.startswith("flash/") for name in load["totals"])
    row, = tracing.flash_calls()
    assert not row["packed"]
    tracing.reset_programs()


# ------------------------------------------------------- the dense call
def test_the_dense_calls_text_is_the_parents(interpret_pallas):
    """A call with no ``segment_ids`` — the two dense cells, the ring's
    chunks — lowers to the program it was at PR 71's parent commit: same
    grid spec, same operands (tests/data/flash_dense_call_digest.json,
    taken there by this function)."""
    with open(os.path.join(REPO, "tests", "data",
                           "flash_dense_call_digest.json")) as f:
        want = json.load(f)
    assert dense_call_digests() == want


def dense_call_digests():
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    q, kv = shape(2, 256, 4, 32), shape(2, 256, 2, 32)
    out = {}
    for name, kw in (("causal", {}), ("window", {"window": 96}),
                     ("not_causal", {"causal": False})):
        loss = lambda q, k, v: jnp.sum(dsf.ds_flash_attention(
            q, k, v, block_q=64, block_k=32, **kw))
        text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
            q, kv, kv).as_text()
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    return out
