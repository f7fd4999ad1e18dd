"""A held plan's consumers walk its live prefix (ISSUE 39): dispatch,
combine, the activation between the grouped calls and the grouped kernels'
output stop at ``used_blocks`` tiles, and what lies behind is nobody's.

The oracles are the one-pass forms the loops replaced, kept here: one
gather of all ``Mp`` rows, one float32 scatter-add of all of them, and
their hand-written backward halves.  Gathers are held bit for bit, the
float32 sums to one ulp of bfloat16; a poisoned run (every row behind the
prefix of every ``[Mp, ·]`` intermediate NaN) leaves the layer's output,
gradients and statistics as they were.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe import layer as moe
from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params, moe_layer
from deepspeed_tpu.ops.pallas import grouped_gemm as gg

BM = 8


# ------------------------------------------------- the one-pass oracles
def _token_rows(xt, token_of_row):
    zero = jnp.zeros((1,) + xt.shape[1:], xt.dtype)
    return jnp.concatenate([xt, zero])[token_of_row]


@functools.partial(jax.jit, static_argnums=2)
def _sum_into_tokens(rows, token_of_row, tokens):
    out = jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[
        token_of_row].add(rows.astype(jnp.float32), mode="drop")
    return out.astype(rows.dtype)


# jitted as the step is: XLA keeps a bfloat16 product's excess precision
# into the float32 sum where both are in one program
@functools.partial(jax.jit, static_argnums=3)
def _combine_one_pass(y, gate_of_row, token_of_row, tokens):
    return _sum_into_tokens(gate_of_row.astype(y.dtype)[:, None] * y,
                            token_of_row, tokens)


@jax.jit
def _combine_bwd_one_pass(y, gate_of_row, token_of_row, g):
    g_rows = _token_rows(g, token_of_row)
    dgate = jnp.sum(y.astype(jnp.float32) * g_rows.astype(jnp.float32),
                    axis=-1).astype(gate_of_row.dtype)
    return gate_of_row.astype(y.dtype)[:, None] * g_rows, dgate


# ------------------------------------------------------------- the loads
#: name -> the share of the routed rows that the held experts are sent, as
#: a function (rng, R, all experts, offset, held) -> expert ids [R]
def _none_held(rng, R, E, off, held):
    return np.where(rng.integers(0, 2, R) == 0, rng.integers(0, off, R),
                    rng.integers(off + held, E, R))


def _even(rng, R, E, off, held):
    return rng.integers(0, E, R)


def _twice(rng, R, E, off, held):
    mine = rng.random(R) < 2 * held / E
    return np.where(mine, rng.integers(off, off + held, R),
                    _none_held(rng, R, E, off, held))


def _one_expert(rng, R, E, off, held):
    mine = rng.random(R) < held / E
    return np.where(mine, off + held - 1, _none_held(rng, R, E, off, held))


def _the_bound_itself(rng, R, E, off, held):
    return rng.integers(off, off + held, R)


LOADS = {"no_held_row": _none_held, "even_share": _even, "twice": _twice,
         "one_expert_takes_all": _one_expert,
         "every_routed_row_held": _the_bound_itself}
#: name -> (held_rows_factor, bytes of a loop's chunk or None for the
#: module's own, under which every toy plan is shorter than one chunk)
SHAPES = {"factor2_chunk_of_3_tiles": (2, 3 * BM * 32),
          "factor4_chunk_of_4_tiles": (4, 4 * BM * 32),
          "factor16_chunk_of_7_tiles": (16, 7 * BM * 32),
          "factor16_plan_shorter_than_a_chunk": (16, None)}
T, K, E_ALL, OFF, HELD, D = 96, 4, 32, 6, 2, 16      # factor 16: bound = R


def _plan(load, factor, rng):
    R = T * K
    eids = jnp.asarray(LOADS[load](rng, R, E_ALL, OFF, HELD), jnp.int32)
    bound = gg.held_rows_bound(R, HELD, E_ALL, BM, factor=factor)
    plan, over = gg.make_held_group_plan(eids, OFF, HELD, bound, block_m=BM)
    return plan, int(over)


@pytest.fixture
def chunk_bytes(monkeypatch):
    def set_to(n):
        if n is not None:
            monkeypatch.setattr(gg, "_LIVE_CHUNK_BYTES", n)
    return set_to


def _bf16(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)


def _within_an_ulp_of_bf16(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=0)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("load", sorted(LOADS))
def test_rows_go_out_and_come_back_as_the_one_pass_forms(load, shape,
                                                         chunk_bytes):
    """dispatch and combine, values and the four cotangents."""
    factor, nbytes = SHAPES[shape]
    chunk_bytes(nbytes)
    rng = np.random.default_rng(7)
    plan, over = _plan(load, factor, rng)
    Mp, live = plan.padded_rows, int(gg.live_rows(plan))
    chunk = gg._live_chunk_rows(plan, D * 2)
    if nbytes is None:
        assert chunk == Mp
    else:
        assert chunk == nbytes // 32 and Mp % chunk and Mp > 2 * chunk
    if load == "every_routed_row_held" and factor == 16:
        # the bound holds every routed row: nothing over, the last chunk
        # is the one pulled back inside the plan
        assert over == 0 and live > Mp - chunk
    token_of_row = plan.padded_to_row // K
    xt, g_pad = _bf16(rng, T, D), _bf16(rng, Mp, D)
    gates = jnp.asarray(rng.uniform(0.1, 1, (T * K,)), jnp.float32)
    gate_of_row = jnp.take(gates, plan.padded_to_row, mode="fill",
                           fill_value=0)
    prefix = np.arange(Mp) < live

    # the gather out: bit for bit (behind the prefix both are zeros here:
    # the CPU's ``lax.empty``)
    x_pad, pull = jax.vjp(lambda x: gg.dispatch_held_rows(x, plan, K), xt)
    want = _token_rows(xt, token_of_row)
    np.testing.assert_array_equal(np.asarray(x_pad, np.float32),
                                  np.asarray(want, np.float32))
    assert not np.asarray(want, np.float32)[~prefix].any()
    # its backward: the float32 sum into tokens
    _within_an_ulp_of_bf16(pull(g_pad)[0],
                           _sum_into_tokens(g_pad, token_of_row, T))

    # the sum back, and its backward: a gather and a row reduction
    y, g_tok = _bf16(rng, Mp, D), _bf16(rng, T, D)
    # (jitted, as the oracle and the step are: a plan of one chunk is summed
    # in one pass, op by op if nothing compiles it as one program)
    out, pull = jax.vjp(jax.jit(
        lambda y, gates: gg.combine_held_rows(y, gates, plan, K)), y, gates)
    _within_an_ulp_of_bf16(
        out, _combine_one_pass(y, gate_of_row, token_of_row, T))
    dy, dgates = pull(g_tok)
    want_dy, want_dgate = _combine_bwd_one_pass(y, gate_of_row,
                                                token_of_row, g_tok)
    np.testing.assert_array_equal(
        np.asarray(dy, np.float32)[prefix],
        np.asarray(want_dy, np.float32)[prefix])
    want_dgates = jnp.zeros_like(gates).at[plan.padded_to_row].add(
        jnp.where(prefix, want_dgate, 0), mode="drop")
    np.testing.assert_array_equal(dgates, want_dgates)


@pytest.mark.parametrize("load", ["even_share", "every_routed_row_held"])
def test_the_activation_and_the_fan_out_over_the_prefix(load, chunk_bytes):
    chunk_bytes(3 * BM * 32)
    rng = np.random.default_rng(8)
    plan, _ = _plan(load, 16, rng)
    Mp, live = plan.padded_rows, int(gg.live_rows(plan))
    prefix = np.arange(Mp) < live
    a, b, g = (jnp.asarray(rng.standard_normal((Mp, D)), jnp.float32)
               for _ in range(3))

    def ours(a, b):
        a1, a2 = gg.fan_out_live_rows(a, plan, 2)
        return gg.map_live_rows(moe._silu_glu, plan, a1, b) \
            + gg.map_live_rows(jnp.sin, plan, a2)

    def one_pass(a, b):
        return moe._silu_glu(a, b) + jnp.sin(a)

    got, pull = jax.vjp(ours, a, b)
    want, pull_want = jax.vjp(one_pass, a, b)
    np.testing.assert_array_equal(np.asarray(got)[prefix],
                                  np.asarray(want)[prefix])
    # the cotangent behind the prefix is never read
    for d, d_want in zip(pull(jnp.where(prefix[:, None], g, jnp.nan)),
                         pull_want(g)):
        np.testing.assert_allclose(np.asarray(d)[prefix],
                                   np.asarray(d_want)[prefix], rtol=1e-6)


@pytest.mark.parametrize("load", sorted(LOADS))
def test_a_plan_asked_for_the_way_from_element_to_row(load):
    """``make_held_group_plan(..., row_to_padded=True)``: every other field
    is the plan's without it, ``row_to_padded`` is ``padded_to_row`` turned
    round — ``padded_rows``, out of range, for an element held elsewhere
    or over the bound — and a scalar a row goes out by
    ``scatter_to_groups`` (0 on a padding row) with a gather for its
    transpose."""
    rng = np.random.default_rng(5)
    R = T * K
    eids = jnp.asarray(LOADS[load](rng, R, E_ALL, OFF, HELD), jnp.int32)
    bound = gg.held_rows_bound(R, HELD, E_ALL, BM, factor=2)
    plain, over = gg.make_held_group_plan(eids, OFF, HELD, bound, block_m=BM)
    plan, over_too = gg.make_held_group_plan(eids, OFF, HELD, bound,
                                             block_m=BM, row_to_padded=True)
    assert plain.row_to_padded is None and int(over) == int(over_too)
    for name, field in plain._asdict().items():
        if name != "row_to_padded":
            np.testing.assert_array_equal(getattr(plan, name), field, name)
    to_row = np.asarray(plan.padded_to_row)
    want = np.full(R, plan.padded_rows)
    rows = np.flatnonzero(to_row < R)
    want[to_row[rows]] = rows
    np.testing.assert_array_equal(plan.row_to_padded, want)
    held = np.isin(np.asarray(eids), np.arange(OFF, OFF + HELD))
    assert (want < plan.padded_rows).sum() == held.sum() - int(over)
    if load == "no_held_row" or int(over):
        return
    gates = jnp.asarray(rng.random(R), jnp.float32)
    out, pull = jax.vjp(lambda g: gg.scatter_to_groups(g, plan), gates)
    np.testing.assert_array_equal(
        out, np.where(to_row < R, np.append(gates, 0)[to_row], 0))
    g = jnp.asarray(rng.random(plan.padded_rows), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pull(g)[0])[held],
        np.asarray(g)[np.minimum(want, plan.padded_rows - 1)][held])


@pytest.mark.parametrize("load", ["even_share", "twice",
                                  "every_routed_row_held"])
def test_the_sum_with_no_gates_and_its_transpose(load, chunk_bytes):
    """``sum_held_rows`` is ``combine_held_rows`` with every gate 1, and
    its transpose ``dispatch_held_rows``' forward over the tokens'
    cotangents — bit for bit, and no row of ``y`` is kept for it."""
    chunk_bytes(3 * BM * 32)
    rng = np.random.default_rng(9)
    plan, _ = _plan(load, 4, rng)
    Mp, live = plan.padded_rows, int(gg.live_rows(plan))
    prefix = (np.arange(Mp) < live)[:, None]
    y = jnp.where(prefix, _bf16(rng, Mp, D), jnp.nan)
    got, pull = jax.vjp(lambda y: gg.sum_held_rows(y, plan, K), y)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(gg.combine_held_rows(
            y, jnp.ones((T * K,), jnp.float32), plan, K), np.float32))
    g = _bf16(rng, T, D)
    np.testing.assert_array_equal(
        np.asarray(pull(g)[0], np.float32)[prefix[:, 0]],
        np.asarray(gg.dispatch_held_rows(g, plan, K),
                   np.float32)[prefix[:, 0]])
    residuals = jax.make_jaxpr(
        lambda y: jax.vjp(lambda y: gg.sum_held_rows(y, plan, K), y)[1])(y)
    assert not any(v.aval.shape == (Mp, D) for v in residuals.jaxpr.outvars)


@pytest.mark.parametrize("lanes", [1, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("load", ["even_share", "every_routed_row_held"])
def test_a_weight_a_row_beside_the_two_halves(load, dtype, lanes,
                                              chunk_bytes):
    """``map_live_rows`` over two ``[Mp, F]`` arrays and a ``[Mp, 1]``
    float32 one (and the same over 128 lanes, lane 0 the weight, as an
    exchange delivers it) — a routed row's gate where its expert is
    (moe/layer.py ``_glu``'s third operand) — against ``weight * silu(a) *
    b`` written plainly in float32 and rounded once: forward over the live
    prefix, and all three cotangents, the weight's — a row sum over ``F``,
    in lane 0 alone — among them.  With every routed row held the prefix is longer than sixteen
    chunks of three tiles and the plan shorter than seventeen: the last
    chunk is pulled back over rows the sixteenth has done."""
    chunk_bytes(3 * BM * max(D, lanes) * 4)
    rng = np.random.default_rng(12)
    plan, _ = _plan(load, 16, rng)
    Mp, live = plan.padded_rows, int(gg.live_rows(plan))
    chunk = 3 * BM
    assert (-(-live // chunk) * chunk > Mp) \
        == (load == "every_routed_row_held")
    prefix = np.arange(Mp) < live
    a, b, g = (jnp.asarray(rng.standard_normal((Mp, D)), dtype)
               for _ in range(3))
    w = jnp.asarray(rng.random((Mp, lanes)), jnp.float32)

    def ours(a, b, w):
        return gg.map_live_rows(moe._row_weighted(moe._silu_glu), plan,
                                a, b, w)

    def one_pass(a, b, w):
        f32 = lambda x: x.astype(jnp.float32)            # noqa: E731
        return (w[:, :1] * (jax.nn.silu(f32(a)) * f32(b))).astype(dtype)

    got, pull = jax.vjp(ours, a, b, w)
    want, pull_want = jax.vjp(one_pass, a, b, w)
    assert got.dtype == dtype and got.shape == (Mp, D)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[prefix],
                                  np.asarray(want, np.float32)[prefix])
    got_d = pull(jnp.where(prefix[:, None], g, jnp.nan))
    want_d = pull_want(g)
    assert [d.shape for d in got_d] == [(Mp, D), (Mp, D), (Mp, lanes)]
    assert [d.dtype for d in got_d] == [dtype, dtype, jnp.float32]
    for d, d_want in zip(got_d, want_d):
        d, d_want = (np.asarray(x, np.float32)[prefix] for x in (d, d_want))
        assert np.isfinite(d).all()
        np.testing.assert_allclose(
            d, d_want, rtol=1e-5 if dtype == jnp.float32 else 2.0 ** -7,
            atol=1e-6)


@pytest.mark.parametrize("blocks", [None, (8, 128)],
                         ids=["resident", "streamed"])
@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["fwd", "dx"])
def test_a_trailing_tile_is_not_written(transpose_rhs, blocks):
    """The kernels of a ``live_only`` plan: the prefix as the kernels that
    write zeros compute it, and behind it the result is what the buffer
    held (Pallas' interpreter hands out zeros; the chip, anything)."""
    rng = np.random.default_rng(9)
    plan, _ = _plan("even_share", 16, rng)
    live = int(gg.live_rows(plan))
    assert live < plan.padded_rows - 4 * BM
    Kd, N = 16, 24
    x = jnp.asarray(rng.standard_normal((plan.padded_rows, Kd)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(
        (HELD, N, Kd) if transpose_rhs else (HELD, Kd, N)), jnp.float32)
    x = x.at[live:].set(jnp.nan)                 # never fetched either
    kw = dict(interpret=True, transpose_rhs=transpose_rhs)
    if blocks:
        kw.update(block_k=blocks[0], block_n=blocks[1])
    got = gg.ds_ggemm(x, w, plan, **kw)
    want = gg.ds_ggemm(x, w, plan._replace(live_only=False), **kw)
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_array_equal(np.asarray(got)[:live],
                                  np.asarray(want)[:live])
    assert not np.asarray(want)[live:].any()


# --------------------------------------------------------- the layer whole
def _layer(activation, factor=16):
    config = MoEConfig(d_model=16, d_ff=24, num_experts=8, top_k=2,
                       experts_held=2, expert_offset=4,
                       held_rows_factor=factor, activation=activation,
                       dispatch_mode="grouped")
    params = init_moe_params(config, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 16), jnp.float32)
    return config, params, x


def _run(config, params, x):
    def loss(params, x):
        out, aux, stats = moe_layer(params, x, config, train=True,
                                    return_stats=True)
        return jnp.sum(jnp.sin(out)) + aux, (out, stats)

    (_, (out, stats)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(params, x)
    return out, stats, grads


@pytest.mark.parametrize("activation", ["silu_glu", "relu2"])
def test_poison_behind_the_prefix_changes_nothing(activation, monkeypatch):
    """Every ``[Mp, ·]`` array of the held layer — what the loops write
    into and what the kernels return, forward and backward — NaN behind
    ``used_blocks · bm``: output, gradients and statistics are the clean
    run's, and finite."""
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    monkeypatch.setenv("DS_GGEMM_BLOCKS", "8,128,128")
    monkeypatch.setattr(gg, "_LIVE_CHUNK_BYTES", 3 * 8 * 64)
    config, params, x = _layer(activation)
    clean = _run(config, params, x)
    assert int(clean[1]["dropped"]) == 0 and int(clean[1]["dispatched"]) > 0

    poisoned = []
    kernels = gg._pallas_ggemm

    def unwritten(shape, dtype, after, what):
        poisoned.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    def trailing_nan(x, w, tiles, block_m, **kw):
        out = kernels(x, w, tiles, block_m, **kw)
        assert kw["live_only"]
        poisoned.append(out.shape)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < tiles[1][0] * block_m, out, jnp.nan)

    monkeypatch.setattr(gg, "_unwritten", unwritten)
    monkeypatch.setattr(gg, "_pallas_ggemm", trailing_nan)
    dirty = _run(config, params, x)
    matrices = 3 if activation == "silu_glu" else 2
    # every matrix's forward and dx; x_pad, h, dy and the activation's
    # cotangents (the sum of x_pad's two is taken in place in the first);
    # the rows in token order of the two sums into tokens (since PR 44)
    assert len(poisoned) == 2 * matrices + 3 + (matrices - 1) + 2
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(dirty)):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_array_equal(a, b)


def test_a_row_over_the_bound_is_counted_as_before(monkeypatch):
    """factor 1 under a router that sends the held pair every row: the rows
    over the bound are the statistics', loop or no loop."""
    monkeypatch.setenv("DS_GGEMM_BLOCKS", "8,128,128")
    monkeypatch.setattr(gg, "_LIVE_CHUNK_BYTES", 2 * 8 * 64)
    config, params, x = _layer("silu_glu", factor=1)
    config = dataclasses.replace(config, router="sigmoid")
    params = dict(params, e_score_correction_bias=jnp.zeros((8,)).at[
        4:6].set(10.0))
    out, stats, grads = _run(config, params, x)
    bound = gg.held_rows_bound(160, 2, 8, 8, factor=1)
    assert bound == 40 and int(stats["dropped"]) >= 160 - bound - 2 * 8
    assert int(stats["dispatched"]) + int(stats["dropped"]) == 160
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves((out, grads)))


def test_live_and_plan_rows_reach_the_registry_tap_only():
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    config, params, x = _layer("silu_glu")
    reg = MetricsRegistry()
    moe.set_moe_metrics_registry(reg)
    try:
        text = jax.jit(lambda p, x: moe_layer(p, x, config)).lower(
            params, x).as_text()
        assert "callback" in text
        out = jax.jit(lambda p, x: moe_layer(p, x, config))(params, x)
        jax.block_until_ready(out)
        jax.effects_barrier()
    finally:
        moe.set_moe_metrics_registry(None)
    R = 80 * 2
    plan_rows = gg.held_rows_bound(R, 2, 8, factor=16) + 2 * 128
    assert reg.get_gauge(moe.HELD_PLAN_ROWS) == plan_rows
    live = reg.get_gauge(moe.HELD_LIVE_ROWS)
    assert live % 128 == 0 and 2 * 128 <= live <= plan_rows
    # with no tap the step's text holds no callback
    assert "callback" not in jax.jit(
        lambda p, x: moe_layer(p, x, config)).lower(params, x).as_text()


# ------------------------------------------- whose program this PR changes
def _parents_digests():
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "held_prefix_step_digests.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("grouped_kernels", [False, True],
                         ids=["ragged_dot", "ggemm_kernels"])
@pytest.mark.parametrize("family", ["gpt2", "olmoe"])
def test_a_step_with_no_held_plan_lowers_to_the_parents_text(
        family, grouped_kernels):
    """GPT-2 never reaches the expert layer and OLMoE's full plan
    (``_grouped_moe``: ``make_group_plan``, ``dispatch_rows``,
    ``sum_rows``, kernels that write their trailing tiles as zeros) is
    not this PR's: the toy step's lowered text — with the grouped kernels'
    bodies in it too — has the sha256 it had at PR 39's parent commit
    (tests/flash_step_texts.py; tests/data/held_prefix_step_digests.json),
    taken again at PR 49, which changed the flash kernels' tile body in it
    on purpose (tests/test_flash_tile_bodies.py holds the new one to the
    old one's results), and OLMoE's at PR 59, which moved the gate to the
    experts' activation in the full plan alone (tests/test_grouped_gemm.py
    holds it to the dense reference and counts its kernels; the held
    families' text, kernels interpreted or not, stood at that PR: the same
    digests at parent and change), and both again at PR 69, whose loss
    takes the hidden state and the head and forms no whole logits
    (``models/model.py head_token_loss``; tests/flash_step_texts.py says
    which digests and why), and at PR 71, whose packed flash calls bound
    their tile loops by the documents too
    (tests/test_flash_document_skip.py); the held families' entries stay
    PR 39's parent's,
    which the test below holds them to having left."""
    from tests import flash_step_texts
    want = _parents_digests()[
        "grouped_kernels" if grouped_kernels else "reference"][family]
    assert flash_step_texts.digest(family, grouped_kernels) == want


@pytest.mark.parametrize("family", ["joyai", "nemotron_h", "qwen3_next"])
def test_a_step_with_a_held_plan_left_the_parents_text(family):
    """The three families that hold a subset of their experts run
    ``_held_grouped_moe``, whose dispatch, activation, combine and kernels
    walk the plan's live prefix since PR 39: ``while`` loops with a traced
    trip count stand where one gather and one scatter-add of every padded
    row stood, so their text is NOT the parent's — by design; what they
    compute is held to the one-pass forms above and to the references by
    tests/test_joyai.py, test_nemotron_h.py and test_qwen3_next.py."""
    from tests import flash_step_texts
    for kernels in (False, True):
        parents = _parents_digests()[
            "grouped_kernels" if kernels else "reference"][family]
        assert flash_step_texts.digest(family, kernels) != parents
