"""MiniCPM-SALA through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/minicpm_sala.py —
this file imports that same file): loss and every leaf's gradient with
packed documents whose boundaries fall inside a key block, a pooling
window and a chunk; the selection against a per-query loop written here
(the forced first and nearest blocks, the tie rule, a document under
``dense_len``, a query with fewer causal blocks than ``topk``); the attend
stage against dense attention where every block is kept; Lightning
attention chunked against the literal recurrence.  The engine's steps and
the scopes of a toy step are tests/test_minicpm_sala_engine.py.

Everything is float32 with seeded weights: the two sides differ only in
the order of summation."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.minicpm_sala import (LIGHTNING, MINICPM_SALA_SIZES,
                                               MIXER_TYPES, SPARSE,
                                               MiniCPMSALAConfig,
                                               count_params,
                                               minicpm_sala_model)
from deepspeed_tpu.ops.sparse_attention import (BlockSelection,
                                                select_blocks,
                                                selected_attention,
                                                selection_counts,
                                                visited_keys_per_query)
from deepspeed_tpu.ops.state_space import (lightning_attention,
                                           lightning_slopes, ssd_recurrent)
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "minicpm_sala_reference",
    os.path.join(REPO, "benchmarks", "references", "minicpm_sala.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 5e-7
GRAD_TOL = 2e-4         # max |a - b| / max |b| per leaf; measured <= 1e-6

GAS, B, S = 2, 2, 64
#: the toy selection: blocks of 4, a window of 2 every position, top-4 with
#: the first and the 2 nearest forced, selecting from 32 tokens on
SEL = BlockSelection(block_size=4, kernel_size=2, kernel_stride=1, topk=4,
                     init_blocks=1, window_size=8, dense_len=32)
#: a stride over one: a document's first slot and column are not its own
#: position's (blocks of 8, windows of 4 every 2)
SEL_STRIDED = BlockSelection(block_size=8, kernel_size=4, kernel_stride=2,
                             topk=4, init_blocks=1, window_size=16,
                             dense_len=48)


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return minicpm_sala_model(
        "tiny", **{"dtype": "float32", "remat": True, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: matrices several times
    their initial size (scores that decide the selection), norms off 1."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if "norm" in name:
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("w_q", "w_k"):
            return w * 25.0
        return w if name == "wte" else w * 5.0

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0, gas=GAS):
    """Rows of 64 tokens: a document of 41 or more (it selects: dense_len
    32) among short ones, boundaries inside a block of 4, a window of 2 and
    a chunk of 16."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 256, size=(gas, B, S), dtype=np.int32)
    cuts = np.array([[[5, 6, 47], [13, 14, 14]], [[41, 50, 63], [1, 9, 18]]])
    cuts = cuts[np.arange(gas) % 2]
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


# ------------------------------------------------- model against reference
def test_loss_and_every_gradient_leaf_match_the_reference():
    model = toy_model()
    params = seeded_params(model)
    batch = micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(model.loss))(params, batch)
        want, g_want = jax.jit(jax.value_and_grad(
            lambda p: reference.micro_batch_loss(
                p, batch["input_ids"], batch["segment_ids"],
                sizes_of(model))))(params)
    assert abs(float(got) - float(want)) < LOSS_TOL, (got, want)
    off = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30)),
        g_got, g_want)
    worst = max(jax.tree_util.tree_leaves_with_path(off), key=lambda t: t[1])
    assert worst[1] < GRAD_TOL, worst
    # every leaf has a gradient that is not zero: nothing is bypassed
    dead = [jax.tree_util.keystr(p) for p, g in
            jax.tree_util.tree_leaves_with_path(g_want)
            if float(jnp.abs(g).max()) == 0.0]
    assert not dead, dead


@pytest.mark.parametrize("change", [
    {"remat": False}, {"mlp_token_tile": None},
    {"attend_query_chunk": 64, "attend_key_spans": 1}, {"scan_chunk": 64}])
def test_how_a_step_is_cut_up_changes_no_number(change):
    """Remat, the feed-forward's tiles, the attend stage's chunks and spans
    and the scan's chunk are how, not what."""
    batch = micro(packed_batch(1), 1)
    base = toy_model()
    params = seeded_params(base)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(base.loss))(params, batch)
        got = jax.jit(jax.value_and_grad(toy_model(**change).loss))(
            params, batch)
    assert abs(float(got[0]) - float(want[0])) < LOSS_TOL
    off = jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30)),
        got[1], want[1])
    assert max(jax.tree.leaves(off)) < GRAD_TOL, off


def test_the_scalings_are_the_papers():
    model = toy_model()
    cfg = model.config
    assert cfg.residual_scale == 1.4 / math.sqrt(32)      # not sqrt(4)
    assert cfg.layer_kinds == "SLLL"
    params = seeded_params(model)
    batch = micro(packed_batch())
    # logits scale with 1 / (d_model / dim_model_base)
    wide = toy_model(dim_model_base=128)
    a, b = model.apply(params, batch), wide.apply(params, batch)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b) * 2.0, rtol=1e-5)


# --------------------------------------------- the selection, by brute force
def brute_select(q, k, seg, sel):
    """Steps 1-4 written as loops over documents, queries, heads and
    blocks, in float64: (blocks [G, S, topk], margin [G, S]: how far the
    last kept block's score is above the first one left out)."""
    S, H, hd = q.shape
    G = k.shape[1]
    R = H // G
    st, ks, bs = sel.kernel_stride, sel.kernel_size, sel.block_size
    per, reach, local = bs // st, ks // st - 1, sel.window_size // bs
    blocks = -np.ones((G, S, sel.topk), np.int64)
    margin = np.full((G, S), np.inf)
    starts = [0] + [t for t in range(1, S) if seg[t] != seg[t - 1]] + [S]
    for a, e in zip(starts[:-1], starts[1:]):
        n = e - a
        J = max(0, (n - ks) // st + 1)
        pooled = np.array([k[a + st * j:a + st * j + ks].mean(0)
                           for j in range(J)]).reshape(J, G, hd)
        for p in range(n):
            ended = [j for j in range(J) if st * j + ks - 1 <= p]
            A = np.zeros((G, J))
            for h in range(H):
                if ended:
                    logit = pooled[ended, h // R] @ q[a + p, h] / np.sqrt(hd)
                    w = np.exp(logit - logit.max())
                    A[h // R, ended] += w / w.sum()
            own = p // bs
            for g in range(G):
                score = []
                for b in range(own + 1):
                    touching = [j for j in range(per * b - reach,
                                                 per * b + per) if 0 <= j < J]
                    forced = b < sel.init_blocks or b > own - local
                    score.append(np.inf if forced else
                                 max([A[g, j] for j in touching], default=0.))
                order = sorted(range(own + 1), key=lambda b: (-score[b], b))
                kept = sorted(order[:sel.topk])
                blocks[g, a + p, :len(kept)] = kept
                if len(order) > sel.topk:
                    # an exact tie is common and real — one window touches
                    # two blocks and may be the maximum of both, and blocks
                    # no window of which has ended score 0 — and the index
                    # decides it on every side: only a near-tie is open
                    gap = score[order[sel.topk - 1]] - score[order[sel.topk]]
                    margin[g, a + p] = np.inf if gap == 0.0 else gap
    return blocks, margin


def _qk(seed, S, H=4, G=2, hd=16, scale=3.0):
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    return (scale * jax.random.normal(kq, (1, S, H, hd)),
            jax.random.normal(kk, (1, S, G, hd)))


def _segments(cuts, S):
    return (np.arange(S)[:, None] >= np.asarray(cuts)[None, :]).sum(-1) \
        .astype(np.int32)[None]


SELECTION_CASES = {
    # a long document between two short ones, boundaries inside blocks
    "blocks_of_4": (SEL, 64, (5, 52)),
    # one document: the row is the document
    "one_document": (SEL, 64, ()),
    # documents that start at odd positions: slots and columns shift
    "strided_odd_starts": (SEL_STRIDED, 128, (3, 110)),
    "strided_even_starts": (SEL_STRIDED, 128, (6, 70)),
    # a one-token document and a document shorter than a window
    "tiny_documents": (SEL_STRIDED, 128, (1, 2, 5, 100)),
}


@pytest.mark.parametrize("case", sorted(SELECTION_CASES))
def test_selection_is_the_per_query_loops(case):
    sel, S, cuts = SELECTION_CASES[case]
    q, k = _qk(3, S)
    seg = _segments(cuts, S)
    got, count = select_blocks(q, k, jnp.asarray(seg), sel, query_chunk=32)
    want, margin = brute_select(np.asarray(q[0], np.float64),
                                np.asarray(k[0], np.float64), seg[0], sel)
    got = np.asarray(got[0])
    decided = margin > 1e-5          # float32 against float64
    assert decided.mean() > 0.99
    np.testing.assert_array_equal(got[decided], want[decided])
    np.testing.assert_array_equal(np.asarray(count[0]), (want >= 0).sum(-1))
    # and the reference's own selection, written a third way
    sizes = {f: getattr(sel, f) for f in sel.__dataclass_fields__}
    ref, _ = reference.select(q[0], k[0], jnp.asarray(seg[0]), sizes,
                              q_block=32)
    np.testing.assert_array_equal(np.asarray(ref)[decided], want[decided])


def test_first_and_nearest_blocks_are_always_kept_and_ties_go_low():
    """With q = 0 every window scores alike: the forced blocks first (block
    0 and the two that end with the query's own), then the lowest
    indices."""
    S = 64
    _, k = _qk(5, S)
    q = jnp.zeros((1, S, 4, 16))
    blocks, count = select_blocks(q, k, None, SEL)
    blocks, count = np.asarray(blocks[0]), np.asarray(count[0])
    for t in (0, 3, 4, 15, 16, 40, 63):
        own = t // 4
        want = list(range(own + 1)) if own < 4 else [0, 1, own - 1, own]
        for g in range(2):
            assert list(blocks[g, t][blocks[g, t] >= 0]) == want, (t, g)
            assert count[g, t] == len(want)


def test_a_short_document_keeps_every_block_whatever_was_chosen():
    """Under ``dense_len`` the attention is dense inside the document, and
    at or over it a query sees only its kept blocks."""
    S = 64
    q, k = _qk(7, S)
    v = jax.random.normal(jax.random.PRNGKey(8), (1, S, 2, 16))
    seg = jnp.asarray(_segments((24,), S))      # 24 tokens, then 40
    blocks, count = select_blocks(q, k, seg, SEL)
    got = selected_attention(q, k, v, blocks, seg, SEL, query_chunk=16,
                             key_spans=2)
    dense = _dense_attention(q, k, v, seg)
    # the first document (24 < 32) and the second's first four blocks
    np.testing.assert_allclose(np.asarray(got[0, :24 + 16]),
                               np.asarray(dense[0, :24 + 16]), atol=2e-6)
    assert float(jnp.abs(got[0, 50:] - dense[0, 50:]).max()) > 1e-3
    counts = selection_counts(blocks, count, seg, SEL)
    assert float(counts["sparse/dense_documents"]) == 24 / 64
    # a query of the second document keeps at most 4 blocks of 4
    assert float(counts["sparse/selected_blocks_per_query"]) <= 6
    assert float(counts["sparse/required_keys_per_query"]) < 16


def _dense_attention(q, k, v, seg):
    R = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, R, axis=2), jnp.repeat(v, R, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(q.shape[-1])
    S = q.shape[1]
    seen = (seg[:, :, None] == seg[:, None, :]) \
        & (jnp.arange(S)[:, None] >= jnp.arange(S)[None, :])
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("spans", [(64, 1), (16, 2), (8, 4)])
def test_attend_is_dense_attention_where_every_block_is_kept(spans):
    S = 64
    sel = BlockSelection(block_size=4, kernel_size=2, kernel_stride=1,
                         topk=16, init_blocks=1, window_size=8, dense_len=0)
    q, k = _qk(11, S)
    v = jax.random.normal(jax.random.PRNGKey(12), (1, S, 2, 16))
    seg = jnp.asarray(_segments((9, 30), S))
    blocks, _ = select_blocks(q, k, seg, sel)

    def both(q, k, v):
        return (selected_attention(q, k, v, blocks, seg, sel,
                                   query_chunk=spans[0], key_spans=spans[1]),
                _dense_attention(q, k, v, seg))
    got, want = both(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    w = jax.random.normal(jax.random.PRNGKey(13), got.shape)
    grads = [jax.grad(lambda *a: jnp.sum(both(*a)[i] * w), (0, 1, 2))(q, k, v)
             for i in (0, 1)]
    for a, b in zip(*grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    assert visited_keys_per_query(S, *spans) == S * (spans[1] + 1) \
        / (2 * spans[1])


def test_attend_given_the_references_selection_is_the_references_step_5():
    S = 64
    q, k = _qk(17, S)
    v = jax.random.normal(jax.random.PRNGKey(18), (1, S, 2, 16))
    seg = jnp.asarray(_segments((7, 20), S))
    sizes = {f: getattr(SEL, f) for f in SEL.__dataclass_fields__}
    blocks, _ = reference.select(q[0], k[0], seg[0], sizes)
    want = reference.attend(q[0], k[0], v[0], blocks, seg[0], sizes)
    got = selected_attention(q, k, v, blocks[None], seg, SEL,
                             query_chunk=16, key_spans=2)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-6)


# ----------------------------------------------------- Lightning attention
def _literal_lightning(q, k, v, slopes, seg):
    """Token by token in float64."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    b, S, H, hd = q.shape
    out = np.zeros_like(v)
    for i in range(b):
        state = np.zeros((H, hd, hd))
        for t in range(S):
            if t == 0 or seg[i, t] != seg[i, t - 1]:
                state[:] = 0.0
            state = np.exp(-slopes)[:, None, None] * state \
                + k[i, t][:, :, None] * v[i, t][:, None, :]
            out[i, t] = np.einsum("hk,hkv->hv", q[i, t], state)
    return out


@pytest.mark.parametrize("chunk,interpret", [(16, False), (64, False),
                                             (128, True)])
def test_lightning_chunked_is_the_literal_recurrence(chunk, interpret):
    """The XLA chunked form at two chunks, and the state-space kernels
    interpreted at one head a group (the lowering the chip runs)."""
    H, hd = (4, 16) if not interpret else (2, 128)
    S = 64 if not interpret else 256
    keys = jax.random.split(jax.random.PRNGKey(21), 3)
    q, k, v = (0.3 * jax.random.normal(kk, (2, S, H, hd)) for kk in keys)
    seg = np.stack([_segments((15, 16, 40), S)[0], _segments((), S)[0]])
    slopes = lightning_slopes(H)
    np.testing.assert_allclose(
        np.asarray(slopes), [2.0 ** (-8.0 * h / H) for h in range(1, H + 1)])
    with tracing.step_account("test/lightning"):
        got = lightning_attention(q, k, v, slopes, jnp.asarray(seg),
                                  chunk=chunk, interpret=interpret)
    want = _literal_lightning(q, k, v, np.asarray(slopes, np.float64), seg)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    # ... and the state-space recurrence at a step of 1
    again = ssd_recurrent(v, jnp.ones((2, S, H)), -slopes, k, q, None,
                          jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(again), want, atol=3e-5)
    scan, = tracing.ssd_chunks("test/lightning")
    assert scan["groups"] == scan["heads"] == H and scan["path"] == (
        "kernel" if interpret else "xla")
    assert scan["chunks"] == -(-S // chunk)


# ------------------------------------------------------------- the family
def test_the_published_sizes_count_to_the_digit():
    whole = MiniCPMSALAConfig()
    assert whole.mixer_types == MIXER_TYPES and len(MIXER_TYPES) == 32
    assert MIXER_TYPES.count(SPARSE) == 8 \
        and MIXER_TYPES.count(LIGHTNING) == 24
    assert count_params(whole) == 9_477_206_016
    cut = MiniCPMSALAConfig(num_layers=4, vocab_size=9181)
    assert cut.layer_kinds == "SLLL"
    sparse = 3 * 4096 ** 2 + 2 * 4096 * 256 + 3 * 4096 * 16384 \
        + 2 * 4096 + 2 * 128
    lightning = 5 * 4096 ** 2 + 3 * 4096 * 16384 + 3 * 4096 + 2 * 128
    assert (sparse, lightning) == (253_763_840, 285_225_216)
    assert count_params(cut) == sparse + 3 * lightning \
        + 2 * 9181 * 4096 + 4096 == 1_184_654_336


@pytest.mark.parametrize("bad,words", [
    (dict(mixer_types=("minicpm4", "mamba")), "unknown"),
    (dict(num_layers=40), "names 32 layers for 40"),
    (dict(num_heads=6, num_kv_heads=4), "query heads over"),
    (dict(kernel_size=24), "multiples of kernel_stride"),
    (dict(topk=16), "always kept"),
])
def test_sizes_that_do_not_fit_are_refused_by_name(bad, words):
    with pytest.raises(ValueError, match=words):
        MiniCPMSALAConfig(**bad)


def test_serving_and_unknown_sizes_are_refused():
    model = toy_model()
    with pytest.raises(NotImplementedError, match="pooled keys"):
        model.decode_fn(None, None, None, None)
    with pytest.raises(ValueError, match="valid sizes"):
        minicpm_sala_model("10b")
    assert sorted(MINICPM_SALA_SIZES) == ["9b", "tiny"]
