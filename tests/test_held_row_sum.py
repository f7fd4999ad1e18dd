"""A held plan's rows are summed into their tokens by ``ds_rowsum``
(ISSUE 44): the kernel takes a block of tokens a grid step and fetches,
expert by expert, the run of the plan's rows that are theirs — whole tiles
of rows from HBM, their tokens and gates beside them — into one product
with the matrix of (row, token) and a float32 accumulator, one rounding.

The kernel runs in Pallas' interpreter here, at the held cells' widths,
with blocks small enough that a toy plan has several blocks of tokens and
several stages a block.  The oracle is the float32 scatter-add of every row
of the plan, rounded once: to 1e-5 in float32 (a token's rows add up in
another order), to one ulp in bfloat16.  The described-v5e compile of a
held layer lives with the others, in tests/test_chip_compile.py.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import grouped_gemm as gg
from deepspeed_tpu.telemetry import tracing
from tests.test_held_live_prefix import LOADS

BM = 16             # a multiple of a copy's rows, as the library's 128 is
T, E_ALL, OFF, HELD = 40, 32, 6, 3
#: (tokens a block, rows a stage): three blocks of tokens, of which the last
#: is half empty, and a stage of two copies of bfloat16 rows, four of float32
BLOCKS = (16, 32)


def _only_the_first_tokens(rng, R, E, off, held):
    """Held rows for the first 21 tokens' choices alone: the kept rows end
    inside the second block of tokens and the third has none."""
    eids = LOADS["no_held_row"](rng, R, E, off, held)
    mine = (np.arange(R) < 21 * (R // T)) & (rng.random(R) < 0.5)
    return np.where(mine, rng.integers(off, off + held, R), eids)


ALL_LOADS = dict(LOADS, the_prefix_ends_inside_a_block=_only_the_first_tokens)
#: (held_rows_factor, top_k, width): each of the cells' factors, choices and
#: widths once with each of the others' neighbours
SHAPES = {"factor2_top6_w2048": (2, 6, 2048),
          "factor4_top8_w2688": (4, 8, 2688),
          "factor16_top10_w3072": (16, 10, 3072),
          "factor2_top10_w2688": (2, 10, 2688),
          "factor4_top6_w3072": (4, 6, 3072),
          "factor16_top8_w2048": (16, 8, 2048)}


@pytest.fixture
def kernel(monkeypatch):
    """The kernel path, interpreted, at ``BLOCKS``."""
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    monkeypatch.setattr(gg, "_ROWSUM_BLOCKS", (("", BLOCKS),))


def _plan(load, factor, top_k, rng):
    R = T * top_k
    eids = jnp.asarray(ALL_LOADS[load](rng, R, E_ALL, OFF, HELD), jnp.int32)
    bound = gg.held_rows_bound(R, HELD, E_ALL, BM, factor=factor)
    plan, over = gg.make_held_group_plan(eids, OFF, HELD, bound, block_m=BM)
    return plan, int(over)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _scatter_add(y, gate_of_row, token_of_row, tokens, gated):
    """Every row of the plan into one float32 accumulator, rounded once."""
    rows = y.astype(jnp.float32)
    if gated:
        rows = gate_of_row.astype(y.dtype).astype(jnp.float32)[:, None] * rows
    return jnp.zeros((tokens, y.shape[1]), jnp.float32).at[
        token_of_row].add(rows, mode="drop").astype(y.dtype)


def _close(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:       # two float32 sums in two orders, each rounded once
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-5)


def sum_cases(dtype, name):
    """The 72 cases' parameters with the rows' type held: a file takes a
    half of them (``tests/test_held_row_sum_f32.py`` the other), so that
    ``--dist loadfile`` gives them to two workers."""
    def decorate(fn):
        for arg, values, ids in (("load", sorted(ALL_LOADS), None),
                                 ("shape", sorted(SHAPES), None),
                                 ("dtype", [dtype], [name])):
            fn = pytest.mark.parametrize(arg, values, ids=ids)(fn)
        return fn
    return decorate


@sum_cases(jnp.bfloat16, "bf16")
def test_the_kernel_sums_as_the_scatter_add(load, shape, dtype, kernel):
    the_kernel_sums_as_the_scatter_add(load, shape, dtype)


def the_kernel_sums_as_the_scatter_add(load, shape, dtype):
    factor, top_k, width = SHAPES[shape]
    rng = np.random.default_rng(11)
    plan, _ = _plan(load, factor, top_k, rng)
    Mp, R = plan.padded_rows, T * top_k
    no_element = np.asarray(plan.padded_to_row) == R
    if load == "no_held_row":
        assert no_element.all()
    if load == "the_prefix_ends_inside_a_block":
        tokens = np.asarray(plan.padded_to_row)[~no_element] // top_k
        assert 16 < tokens.max() < 32
    y = jnp.asarray(rng.standard_normal((Mp, width)), dtype)
    # a padding row inside the live prefix is exact zeros, as the kernels
    # that write ``y`` leave it; a row behind the prefix holds anything: it
    # is never read
    behind = np.arange(Mp) >= int(gg.live_rows(plan))
    assert no_element[behind].all()
    clean = jnp.where(no_element[:, None], 0, y)
    y = jnp.where(behind[:, None], jnp.nan, clean)
    gates = jnp.asarray(rng.uniform(0.1, 1, (R,)), jnp.float32)
    chunk = gg._live_chunk_rows(plan, width * y.dtype.itemsize)
    gate_of_row = jnp.take(gates, plan.padded_to_row, mode="fill",
                           fill_value=0)
    token_of_row = plan.padded_to_row // top_k
    for gated in (True, False):
        got = jax.jit(lambda y, gates: gg._sum_live_into_tokens(
            y, gates if gated else None, gg._way_back(plan), T, top_k,
            gg.live_rows(plan), chunk))(y, gates)
        assert got.shape == (T, width) and got.dtype == dtype
        _close(got, _scatter_add(clean, gate_of_row, token_of_row, T, gated),
               dtype)
        # a token with no row here: exact zeros
        held_tokens = np.unique(np.asarray(token_of_row)[~no_element])
        absent = np.setdiff1d(np.arange(T), held_tokens)
        assert not np.asarray(got, np.float32)[absent].any()


@pytest.mark.parametrize("top_k", [6, 8, 10])
@pytest.mark.parametrize("load", ["even_share", "one_expert_takes_all",
                                  "every_routed_row_held", "twice"])
def test_the_runs_of_a_block_of_tokens(load, top_k):
    """``_token_block_runs`` against NumPy over ``padded_to_row``: the rows
    of expert ``e`` whose tokens are block ``b``'s are exactly the padded
    rows ``[first[b, e], end[b, e])``, rows over the bound left out."""
    bt = 16
    plan, over = _plan(load, 2, top_k, np.random.default_rng(12))
    if load == "every_routed_row_held":
        assert over > 0
    R = T * top_k
    first, end = (np.asarray(a) for a in gg._token_block_runs(
        gg._way_back(plan), T, top_k, bt))
    assert first.shape == end.shape == (-(-T // bt), HELD)
    padded_to_row = np.asarray(plan.padded_to_row)
    group_start = np.cumsum(plan.group_sizes) - np.asarray(plan.group_sizes)
    group_of_row = np.searchsorted(np.cumsum(plan.group_sizes),
                                   np.arange(plan.padded_rows), side="right")
    block_of_row = np.where(padded_to_row < R, padded_to_row // top_k // bt,
                            -1)
    seen = 0
    for b in range(first.shape[0]):
        for e in range(HELD):
            rows = np.nonzero((block_of_row == b) & (group_of_row == e))[0]
            assert end[b, e] - first[b, e] == len(rows)
            assert group_start[e] <= first[b, e] <= end[b, e]
            if len(rows):
                np.testing.assert_array_equal(
                    rows, np.arange(first[b, e], end[b, e]))
            seen += len(rows)
    assert seen == (padded_to_row < R).sum() > 0
    np.testing.assert_array_equal(
        plan.group_of_element,
        np.where((np.asarray(_eids(load, top_k)) >= OFF)
                 & (np.asarray(_eids(load, top_k)) < OFF + HELD),
                 np.asarray(_eids(load, top_k)) - OFF, HELD))
    # a whole plan has none: its way back is a gather
    whole = gg.make_group_plan(jnp.zeros((16,), jnp.int32), 2, block_m=BM)
    assert whole.group_of_element is None


def _eids(load, top_k):
    return ALL_LOADS[load](np.random.default_rng(12), T * top_k, E_ALL, OFF,
                           HELD)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("load", ["even_share", "twice",
                                  "the_prefix_ends_inside_a_block"])
def test_gradients_through_dispatch_and_combine(load, dtype, kernel):
    """``dispatch_held_rows`` and ``combine_held_rows`` with the kernel
    behind them, values and all cotangents, against autodiff of the plain
    forms: one gather of every row, one float32 scatter-add of them."""
    top_k, width = 8, 256
    rng = np.random.default_rng(13)
    plan, _ = _plan(load, 4, top_k, rng)
    Mp, R = plan.padded_rows, T * top_k
    live = np.arange(Mp) < int(gg.live_rows(plan))
    token_of_row = plan.padded_to_row // top_k
    xt = jnp.asarray(rng.standard_normal((T, width)), dtype)
    y = jnp.asarray(rng.standard_normal((Mp, width)), dtype)
    gates = jnp.asarray(rng.uniform(0.1, 1, (R,)), jnp.float32)
    g_pad = jnp.asarray(rng.standard_normal((Mp, width)), dtype)
    g_tok = jnp.asarray(rng.standard_normal((T, width)), dtype)

    def dispatch_ref(xt):
        zero = jnp.zeros((1, width), xt.dtype)
        return jnp.concatenate([xt, zero])[token_of_row]

    def combine_ref(y, gates):
        gate = jnp.take(gates, plan.padded_to_row, mode="fill", fill_value=0)
        rows = gate.astype(y.dtype).astype(jnp.float32)[:, None] \
            * y.astype(jnp.float32)
        return jnp.zeros((T, width), jnp.float32).at[token_of_row].add(
            jnp.where(live[:, None], rows, 0), mode="drop").astype(y.dtype)

    x_pad, pull = jax.vjp(lambda x: gg.dispatch_held_rows(x, plan, top_k), xt)
    # (autodiff's transpose of a gather adds in the rows' dtype: the plain
    # form is differentiated in float32 and its cotangent rounded once)
    want, pull_ref = jax.vjp(dispatch_ref, xt.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(x_pad, np.float32)[live],
                                  np.asarray(want)[live])
    # (the cotangent behind the prefix is never read)
    dx, = pull(jnp.where(live[:, None], g_pad, jnp.nan))
    assert dx.dtype == dtype
    _close(dx, pull_ref(jnp.where(live[:, None], g_pad, 0).astype(
        jnp.float32))[0].astype(dtype), dtype)

    out, pull = jax.vjp(jax.jit(
        lambda y, gates: gg.combine_held_rows(y, gates, plan, top_k)),
        y, gates)
    want, pull_ref = jax.vjp(combine_ref, y, gates)
    _close(out, want, dtype)
    dy, dgates = pull(g_tok)
    dy_ref, dgates_ref = pull_ref(g_tok)
    np.testing.assert_allclose(np.asarray(dy, np.float32)[live],
                               np.asarray(dy_ref, np.float32)[live],
                               rtol=2.0 ** -7, atol=1e-6)
    np.testing.assert_allclose(dgates, dgates_ref,
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def _primitives_outside_kernels(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names |= _primitives_outside_kernels(sub)
    return names


def test_one_path_and_the_account_says_which(kernel, monkeypatch):
    """``held_row_sums()``: one row per shape of sum, ``path`` "kernel"
    where ``ds_rowsum`` ran and "xla" where the reference form did; no
    ``cond`` in either program, and no scatter of rows in the kernel's."""
    top_k, width = 6, 256
    rng = np.random.default_rng(14)
    plan, _ = _plan("even_share", 2, top_k, rng)
    y = jnp.asarray(rng.standard_normal((plan.padded_rows, width)),
                    jnp.bfloat16)
    gates = jnp.asarray(rng.uniform(0.1, 1, (T * top_k,)), jnp.float32)

    def text_and_rows():
        with tracing.step_account("a_sum"):
            text = jax.jit(lambda y, gates: gg.combine_held_rows(
                y, gates, plan, top_k)).lower(y, gates).as_text()
        return text, tracing.held_row_sums("a_sum")

    text, rows = text_and_rows()
    assert rows == [{"tokens": T, "width": width,
                     "plan_rows": plan.padded_rows, "blocks": BLOCKS,
                     "path": "kernel"}]
    # (the interpreter writes the kernel's own ``pl.when`` as a case: what
    # may not choose is the program around it)
    assert "cond" not in _primitives_outside_kernels(jax.make_jaxpr(
        lambda y, gates: gg.combine_held_rows(y, gates, plan, top_k))(
            y, gates).jaxpr)
    assert "scatter" not in text
    monkeypatch.delenv("DS_GGEMM_INTERPRET")
    text, rows = text_and_rows()
    assert [row["path"] for row in rows] == ["xla"] \
        and rows[0]["blocks"] is None
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert "stablehlo.scatter" in text
    assert tracing.held_row_sums("no_such_program") is None
    assert not hasattr(gg, "_ONE_PASS_SUM_EIGHTHS")
