"""The sparse layer's attend stage as kernels
(ops/pallas/selected_attention.py, interpret mode) against its XLA form
(``masked_chunks``) and against a float32 reference whose mask is made one
query at a time: values and the gradients of q, k and v, over packed rows
whose documents start anywhere, documents under and over ``dense_len``,
queries with fewer causal blocks than ``topk`` (``-1`` in ``blocks``), one
and sixteen query heads a key/value head, and tiles of several shapes; the
rule that chooses the lowering, and the account's row."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import sparse_attention as sa
from deepspeed_tpu.ops.pallas import selected_attention as kernels
from deepspeed_tpu.ops.sparse_attention import (BlockSelection,
                                                select_blocks,
                                                selected_attention)
from deepspeed_tpu.telemetry import tracing

SEL = BlockSelection(block_size=4, kernel_size=2, kernel_stride=1, topk=4,
                     init_blocks=1, window_size=8, dense_len=32)
S = 128

#: name -> (document starts after the first, query heads, key/value heads,
#: head width, (tile queries, tile keys), dtype)
CASES = {
    # documents of 5, 25, 69 and 29 tokens: starts off the blocks' grid, one
    # document over dense_len between three under it
    "odd_starts_r2": ((5, 30, 99), 4, 2, 16, (32, 16), "float32"),
    "odd_starts_wide_tiles": ((5, 30, 99), 4, 2, 16, (16, 32), "float32"),
    "odd_starts_one_tile": ((5, 30, 99), 4, 2, 16, (128, 128), "float32"),
    # one document: every query past block 4 chooses
    "one_document": ((), 4, 2, 16, (32, 32), "float32"),
    # two long documents that share a column at their boundary (50 = 12.5
    # blocks), the second's first queries short of topk causal blocks
    "shared_column": ((50,), 4, 2, 16, (32, 16), "float32"),
    # one-token and three-token documents: several share a column
    "tiny_documents": ((1, 2, 5, 40), 2, 2, 16, (16, 16), "float32"),
    "sixteen_heads_a_group": ((5, 30, 99), 16, 1, 8, (32, 16), "float32"),
    "one_head_a_group": ((7, 64), 2, 2, 16, (64, 32), "float32"),
    # the cell's dtype: operands rounded, sums and softmax float32
    "bfloat16": ((5, 30, 99), 4, 2, 16, (32, 16), "bfloat16"),
}
TOL = {"float32": 2e-5, "bfloat16": 3e-2}


def _segments(cuts):
    return jnp.asarray((np.arange(S)[:, None] >= np.asarray(
        cuts, np.int64).reshape(1, -1)).sum(-1).astype(np.int32)[None])


def _inputs(case):
    cuts, H, G, hd, _, dtype = CASES[case]
    key = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q = 2.0 * jax.random.normal(key[0], (1, S, H, hd))
    k, v, w = (jax.random.normal(key[i], (1, S, n, hd))
               for i, n in ((1, G), (2, G), (3, H)))
    seg = _segments(cuts)
    blocks, _ = select_blocks(q, k, seg, SEL)
    return tuple(t.astype(dtype) for t in (q, k, v)), w, blocks, seg


def _per_query_mask(blocks, seg):
    """[G, S, S] bool, one query at a time: the keys ``s <= t`` of its
    document's kept blocks, every causal key of a short document."""
    blocks, seg = np.asarray(blocks[0]), np.asarray(seg[0])
    G = blocks.shape[0]
    seen = np.zeros((G, S, S), bool)
    for t in range(S):
        mine = np.flatnonzero(seg == seg[t])
        start = mine[0]
        for g in range(G):
            if len(mine) < SEL.dense_len:
                seen[g, t, start:t + 1] = True
                continue
            for b in blocks[g, t]:
                if b >= 0:
                    lo = start + SEL.block_size * b
                    seen[g, t, lo:min(lo + SEL.block_size, t + 1)] = True
    return jnp.asarray(seen)


def _reference(q, k, v, seen):
    f32 = lambda t: t.astype(jnp.float32)
    R = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", f32(q), jnp.repeat(f32(k), R, 2),
                   precision="highest") * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(jnp.repeat(seen, R, 0)[None], s, -jnp.inf),
                       axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(f32(v), R, 2),
                      precision="highest")


def _value_and_grads(fn, qkv, w):
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o
    (_, o), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(*qkv)
    return (o,) + grads


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_xla_form_and_the_per_query_reference(
        case, monkeypatch):
    tiles, dtype = CASES[case][4:]
    monkeypatch.setattr(kernels, "TILES", tiles)
    qkv, w, blocks, seg = _inputs(case)
    seen = _per_query_mask(blocks, seg)
    # the case holds what its name says
    assert (np.asarray(blocks) < 0).any() and seen[:, :, 0].any()
    attend = lambda interpret: lambda q, k, v: selected_attention(
        q, k, v, blocks, seg, SEL, query_chunk=16, key_spans=2,
        interpret=interpret)
    with tracing.step_account("test/attend"):
        got = _value_and_grads(attend(True), qkv, w)
    row, = tracing.sparse_attention_calls("test/attend")
    assert row["lowering"] == "mosaic_tiles" and row["blocks"] == list(tiles)
    xla = _value_and_grads(attend(False), qkv, w)
    want = _value_and_grads(lambda *a: _reference(*a, seen), qkv, w)
    for name, a, b, c in zip(("o", "dq", "dk", "dv"), got, xla, want):
        assert a.dtype == b.dtype == jnp.dtype(dtype) and a.shape == c.shape
        scale = float(jnp.max(jnp.abs(c)))
        for other in (b, c):
            apart = float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - other.astype(jnp.float32))))
            assert apart <= TOL[dtype] * scale, (name, apart, scale)


def test_a_query_that_sees_nothing_reads_zeros_and_moves_nothing():
    """A row of ``blocks`` may keep nothing (not ``select_blocks``', which
    always keeps the query's own block): the kernels' output and every
    gradient are finite, and zero for that query."""
    qkv, w, blocks, seg = _inputs("one_document")
    blocks = blocks.at[:, :, 77].set(-1)
    tiles = kernels.Blocking(32, 16, 0)
    operands = sa.mask_operands(blocks, seg, SEL, jnp.float32)
    o, dq, dk, dv = _value_and_grads(
        lambda *a: kernels.selected_attention_kernels(
            *a, *operands, tiles, interpret=True), qkv, w)
    for t in (o, dq, dk, dv):
        assert bool(jnp.isfinite(t).all())
    assert float(jnp.abs(o[0, 77]).max()) == 0.0 \
        and float(jnp.abs(dq[0, 77]).max()) == 0.0
    assert float(jnp.abs(o[0, 76]).max()) > 0.0


@pytest.mark.parametrize("S_,tiles,pairs,keys", [
    (16384, (512, 512), 528, 8448.0),       # the cell: (S + 512) / 2
    (16384, (256, 512), 1056, 8448.0), (16384, (512, 256), 1056, 8448.0),
    (16384, (256, 256), 2080, 8320.0), (128, (32, 16), 20, 80.0)])
def test_visited_tiles_are_a_rule_of_shapes(S_, tiles, pairs, keys):
    assert kernels.visited_tiles(S_, *tiles) == pairs
    assert kernels.visited_keys_per_query(S_, *tiles) == keys


def test_two_selections_of_one_shape_visit_the_same_tiles(monkeypatch):
    """The device's work does not read the data: another selection and
    other documents, the same static ``tiles`` — and the same grid, the same
    block indices (the lowered text but for nothing)."""
    monkeypatch.setattr(kernels, "TILES", (32, 16))
    (q, k, v), _, blocks, seg = _inputs("odd_starts_r2")
    other, _ = select_blocks(-q, k, _segments((64,)), SEL)
    assert not np.array_equal(np.asarray(blocks), np.asarray(other))
    rows, texts = [], []
    for b, s in ((blocks, seg), (other, _segments((64,)))):
        fn = lambda q, k, v, b, s: selected_attention(
            q, k, v, b, s, SEL, interpret=True)
        with tracing.step_account("test/attend"):
            texts.append(jax.jit(fn).lower(q, k, v, b, s).as_text())
        rows += tracing.sparse_attention_calls("test/attend")
    assert rows[0] == rows[1] and rows[0]["tiles"] == 20
    assert rows[0]["sparse/visited_keys_per_query"] == 80.0
    assert texts[0] == texts[1]


@pytest.mark.parametrize("S_,why", [
    (100, "tiles of 4 tokens"), (126, "half a column left over")])
def test_a_sequence_the_tiles_do_not_divide_takes_the_xla_form(S_, why):
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (1, S_, 4, 16))
    k, v = (jax.random.normal(key[i], (1, S_, 2, 16)) for i in (1, 2))
    blocks = jnp.zeros((1, 2, S_, SEL.topk), jnp.int32)
    with tracing.step_account("test/attend"):
        out = jax.eval_shape(lambda *a: selected_attention(
            *a, blocks, None, SEL, interpret=True), q, k, v)
    row, = tracing.sparse_attention_calls("test/attend")
    assert row["lowering"] == "masked_chunks" and "tiles" not in row, why
    assert out.shape == q.shape


@pytest.mark.parametrize("case,lowering", [
    ("a_tpu", "mosaic_tiles"), ("not_a_tpu", "masked_chunks"),
    ("four_devices", "masked_chunks"), ("a_head_of_64", "masked_chunks"),
    ("s_1000", "masked_chunks"), ("asked_for_xla", "masked_chunks")])
def test_the_lowering_is_a_rule_of_what_the_call_observes(case, lowering,
                                                          monkeypatch):
    """The kernels on one TPU for lane-wide heads and an S the tiles
    divide; the XLA form everywhere else — no option chooses."""
    from deepspeed_tpu.ops import attention
    monkeypatch.setattr(attention, "_on_tpu", lambda: case != "not_a_tpu")
    monkeypatch.setattr(kernels.vmem, "device_kind", lambda: "tpu v5 lite")
    monkeypatch.setattr(jax, "device_count",
                        lambda: 4 if case == "four_devices" else 1)
    S_ = 1000 if case == "s_1000" else 1024
    hd = 64 if case == "a_head_of_64" else 128
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    blocks = jax.ShapeDtypeStruct((1, 2, S_, 64), jnp.int32)
    with tracing.step_account("test/attend"):
        jax.eval_shape(
            lambda q, k, v, b: selected_attention(
                q, k, v, b, None, BlockSelection(dense_len=512),
                interpret=False if case == "asked_for_xla" else None),
            arg(1, S_, 32, hd), arg(1, S_, 2, hd), arg(1, S_, 2, hd), blocks)
    row, = tracing.sparse_attention_calls("test/attend")
    assert row["lowering"] == lowering
    if lowering == "mosaic_tiles":
        assert row["blocks"] == [512, 512] and row["tiles"] == 3
        assert row["sparse/visited_keys_per_query"] == (S_ + 512) / 2
        # 16 heads' tiles of 512 pass what a call is granted unasked
        assert row["vmem_limit_bytes"] == 96 << 20
        assert "query_chunk" not in row
    else:
        assert (row["query_chunk"], row["key_spans"]) == (
            (1000, 1) if case == "s_1000" else (128, 4))


def test_the_mask_operands_are_zeros_and_ones_one_column_a_key():
    _, _, blocks, seg = _inputs("shared_column")
    incol, kept, start = sa.mask_operands(blocks, seg, SEL, jnp.bfloat16)
    C = S // SEL.block_size
    assert incol.shape == (1, S, C) and kept.shape == (1, 2, C, S)
    assert start.shape == (1, 1, S) and start.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(incol.sum(-1)), 1.0)
    assert set(np.unique(np.asarray(kept, np.float32))) == {0.0, 1.0}
    np.testing.assert_array_equal(np.asarray(start[0, 0]),
                                  np.where(np.arange(S) < 50, 0, 50))
    # token 50 starts block 0 of its document in the column of token 48
    assert int(jnp.argmax(incol[0, 50])) == int(jnp.argmax(incol[0, 49]))
