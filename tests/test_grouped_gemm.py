"""Grouped-GEMM MoE dispatch (ISSUE 8): kernel-level parity for
ops/pallas/grouped_gemm.py (float + fused-dequant int8, forward and
custom-VJP backward, interpret mode so the real Pallas kernels run on
CPU), grouped-vs-einsum parity for moe/layer.py at matched drop-free
capacity (train fwd/bwd and eval exactness), the exchange on an EP mesh, and
the Mixtral serving compositions (cb greedy parity incl. int8 weights /
int8 KV, spec-decode rollback, prefix-cache COW).

The load-bearing contracts:
- grouped dispatch is DROP-FREE: every routed token computes regardless
  of capacity_factor, and the routing decision (topk_routing) is shared
  bitwise with the einsum formulation's topkgating;
- the padded group layout is lossless: scatter -> grouped GEMM ->
  gather equals a per-row dense matmul against each row's expert;
- int8 expert stacks ride the grouped kernel IN PLACE (no dequantized
  copy) and match the dequantize-then-matmul reference;
- serving: grouped and einsum dispatch produce token-identical greedy
  outputs (eval capacity is drop-free by MixtralConfig default).
"""
import functools
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.layer import MoEConfig, dispatch_scope, init_moe_params
from deepspeed_tpu.ops.pallas import grouped_gemm as gg
from tests.util import child_env


def _rand_eids(rng, R, E):
    return jnp.asarray(rng.integers(0, E, (R,)), jnp.int32)


def _dense_rowwise(x_rows, w, eids):
    """Per-row oracle: row r @ w[eids[r]] in fp32."""
    out = np.zeros((x_rows.shape[0], w.shape[2]), np.float32)
    xe = np.asarray(x_rows, np.float32)
    wf = np.asarray(w, np.float32)
    for r in range(x_rows.shape[0]):
        out[r] = xe[r] @ wf[int(eids[r])]
    return out


# ------------------------------------------------------------ group plan
def test_group_plan_layout_invariants():
    rng = np.random.default_rng(0)
    R, E, bm = 37, 5, 8
    eids = _rand_eids(rng, R, E)
    plan = gg.make_group_plan(eids, E, block_m=bm)
    assert plan.padded_rows == -(-R // bm) * bm + E * bm
    assert plan.num_blocks * bm == plan.padded_rows
    counts = np.asarray(plan.counts)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(eids), minlength=E))
    # row_to_padded lands each element inside its own expert's group,
    # injectively
    r2p = np.asarray(plan.row_to_padded)
    assert len(set(r2p.tolist())) == R
    gsz = np.asarray(plan.group_sizes)
    starts = np.concatenate([[0], np.cumsum(gsz)])
    for r in range(R):
        e = int(eids[r])
        assert starts[e] <= r2p[r] < starts[e + 1]
    # per-tile expert map is non-decreasing and consistent with offsets
    gids = np.asarray(plan.block_group_ids)
    assert (np.diff(gids) >= 0).all()
    for b in range(plan.num_blocks):
        row0 = b * bm
        owners = [e for e in range(E)
                  if starts[e] <= row0 < starts[e + 1]]
        if owners:                       # trailing tiles clamp to E-1
            assert gids[b] == owners[0]
    # used_blocks: the tiles that hold a group; the rest trail, unowned
    assert plan.used_blocks.shape == (1,)
    assert int(plan.used_blocks[0]) * bm == gsz.sum()
    # the two maps are inverses: padded_to_row names the element in each
    # row of a group and reads R, out of range, on every padding row
    p2r = np.asarray(plan.padded_to_row)
    assert p2r.shape == (plan.padded_rows,)
    np.testing.assert_array_equal(p2r[r2p], np.arange(R))
    others = np.setdiff1d(np.arange(plan.padded_rows), r2p)
    assert (p2r[others] == R).all()
    # stable: an expert's elements keep their flat order, from the first
    # row of its group, and its padding follows them
    for e in range(E):
        group = p2r[starts[e]:starts[e + 1]]
        np.testing.assert_array_equal(
            group[:counts[e]], np.flatnonzero(np.asarray(eids) == e))
        assert (group[counts[e]:] == R).all()
    # scatter/gather round-trips
    rows = jnp.asarray(rng.standard_normal((R, 4)), jnp.float32)
    padded = gg.scatter_to_groups(rows, plan)
    np.testing.assert_array_equal(
        np.asarray(gg.gather_from_groups(padded, plan)), np.asarray(rows))
    assert not np.asarray(padded)[others].any()


def _scatter_formulation(eids, E, bm):
    """The layout as it was built before the plan held both maps (kept
    here as the reference): argsort, counts and row_to_padded by scatter."""
    R = eids.shape[0]
    order = jnp.argsort(eids, stable=True)
    sorted_eids = jnp.take(eids, order)
    counts = jnp.zeros((E,), jnp.int32).at[eids].add(1)
    group_sizes = jnp.maximum(-(-counts // bm), 1) * bm
    zero = jnp.zeros((1,), jnp.int32)
    pstart = jnp.concatenate([zero, jnp.cumsum(group_sizes)])
    start = jnp.concatenate([zero, jnp.cumsum(counts)])
    rank = jnp.arange(R, dtype=jnp.int32) - jnp.take(start, sorted_eids)
    return jnp.zeros((R,), jnp.int32).at[order].set(
        jnp.take(pstart, sorted_eids) + rank)


def _scatter_dispatch(xt, r2p, k, padded_rows):
    """take -> zero-fill -> scatter, differentiated by plain autodiff."""
    rows = jnp.take(xt, jnp.arange(r2p.shape[0]) // k, axis=0)
    return jnp.zeros((padded_rows, xt.shape[1]), xt.dtype).at[r2p].set(rows)


def _take_sum(y, r2p, k):
    """take -> sum over a token's rows in float32, rounded once."""
    out_rows = jnp.take(y, r2p, axis=0).astype(jnp.float32)
    return jnp.sum(out_rows.reshape(-1, k, y.shape[1]), axis=1).astype(
        y.dtype)


def _experts_dense(xt, w1, w2, eids, gates, k):
    """The dense per-expert reference of a layer of two matrices: every
    routed element through its own expert's pair, weighted by its gate
    behind the activation, a token's ``k`` results summed."""
    rows = jnp.repeat(xt, k, axis=0)
    h = jnp.einsum("rd,rdf->rf", rows, w1[eids])
    y = jnp.einsum("rf,rfd->rd", gates[:, None] * jax.nn.gelu(h), w2[eids])
    return jnp.sum(y.reshape(-1, k, xt.shape[1]), axis=1)


ROW_MOVEMENT_CASES = {
    # name: (T, top_k, E, block_m, how the experts are chosen)
    "uniform": (16, 2, 4, 8, "uniform"),
    "empty_expert": (12, 2, 8, 8, "empty"),
    "one_expert": (10, 2, 4, 8, "one"),
    "ragged_rows": (13, 3, 5, 8, "random"),      # R = 39, not 8's multiple
    "top_1": (21, 1, 4, 8, "random"),
    "top_8": (9, 8, 16, 16, "random"),
}


@pytest.mark.parametrize("case", sorted(ROW_MOVEMENT_CASES))
def test_rows_move_by_gathers_as_the_scatter_formulation_did(case):
    """dispatch_rows / sum_rows (gathers through the plan's two maps, each
    the other's backward) against take -> scatter -> ... -> take under
    plain autodiff: the same layout, the same forward to the bit, the
    same gradients, and no scatter in the traced gradient; and, the gates
    multiplied into the activation between the two products in plan order
    (they ride the plan's sort), values and gradients — the gates' among
    them — of the dense per-expert reference."""
    T, k, E, bm, how = ROW_MOVEMENT_CASES[case]
    rng = np.random.default_rng(sorted(ROW_MOVEMENT_CASES).index(case))
    R, D = T * k, 6
    eids = {"uniform": np.arange(R) % E,
            "empty": rng.integers(0, E - 3, (R,)),
            "one": np.full((R,), 2),
            "random": rng.integers(0, E, (R,))}[how]
    eids = jnp.asarray(eids, jnp.int32)
    plan = gg.make_group_plan(eids, E, block_m=bm)
    r2p = _scatter_formulation(eids, E, bm)
    np.testing.assert_array_equal(plan.row_to_padded, r2p)

    xt = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((plan.padded_rows, D)), jnp.float32)
    gates = jnp.asarray(rng.random((R,)), jnp.float32)
    c_pad = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    c_out = jnp.asarray(rng.standard_normal(xt.shape), jnp.float32)
    for dt in (jnp.float32, jnp.bfloat16):
        np.testing.assert_array_equal(
            gg.dispatch_rows(xt.astype(dt), plan, k),
            _scatter_dispatch(xt.astype(dt), r2p, k, plan.padded_rows))
        np.testing.assert_array_equal(
            gg.sum_rows(y.astype(dt), plan, k),
            _take_sum(y.astype(dt), r2p, k))
    np.testing.assert_array_equal(
        gg.scatter_to_groups(jnp.repeat(xt, k, axis=0), plan),
        gg.dispatch_rows(xt, plan, k))

    def new(xt_, y_):
        return (jnp.sum(gg.dispatch_rows(xt_, plan, k) * c_pad)
                + jnp.sum(gg.sum_rows(y_, plan, k) * c_out))

    def old(xt_, y_):
        return (jnp.sum(_scatter_dispatch(xt_, r2p, k, plan.padded_rows)
                        * c_pad)
                + jnp.sum(_take_sum(y_, r2p, k) * c_out))

    got = jax.grad(new, argnums=(0, 1))(xt, y)
    want = jax.grad(old, argnums=(0, 1))(xt, y)
    for name, a, b in zip(("d xt", "d y"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    # a padding row's cotangent is an exact zero, as the scatter's was
    pad = np.setdiff1d(np.arange(plan.padded_rows), np.asarray(r2p))
    assert not np.asarray(got[1])[pad].any()

    # the layer's own order: out by dispatch_rows, the gate in the
    # activation where the expert is, back by the un-gated sum
    F = 5
    w1 = jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, F, D)), jnp.float32)

    def through_the_plan(xt_, w1_, w2_, gates_):
        gated = gg.make_group_plan(eids, E, block_m=bm, gates=gates_)
        mm = functools.partial(gg.ds_ggemm, plan=gated)
        h = mm(gg.dispatch_rows(xt_, gated, k), w1_)
        return gg.sum_rows(
            mm(gated.gates[:, None] * jax.nn.gelu(h), w2_), gated, k)

    def dense(xt_, w1_, w2_, gates_):
        return _experts_dense(xt_, w1_, w2_, eids, gates_, k)

    # the same plan, and the gates where a gather would have put them
    gated = gg.make_group_plan(eids, E, block_m=bm, gates=gates)
    for got, want in zip(gated[:-1], plan[:-1]):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(gated.gates,
                                  gg.scatter_to_groups(gates, plan))
    assert not np.asarray(gated.gates)[pad].any()
    np.testing.assert_allclose(through_the_plan(xt, w1, w2, gates),
                               dense(xt, w1, w2, gates), rtol=2e-5,
                               atol=2e-5)
    args = (xt, w1, w2, gates)
    got = jax.grad(lambda *a: jnp.sum(through_the_plan(*a) * c_out),
                   argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * c_out),
                    argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(("d xt", "d w1", "d w2", "d gates"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)

    def scatters(fn, *operands):
        from deepspeed_tpu.telemetry.costmodel import primitive_names
        return [n for n in primitive_names(jax.make_jaxpr(jax.grad(
            fn, argnums=tuple(range(len(operands)))))(*operands))
            if "scatter" in n]
    assert scatters(old, xt, y) and not scatters(new, xt, y)
    assert not scatters(
        lambda *a: jnp.sum(through_the_plan(*a) * c_out), *args)


def _accounted(fn, *args):
    """fn(*args) with the grouped calls it makes counted as a step would
    count them: (result, {kernel: its row of grouped_gemm_rows' calls})."""
    from deepspeed_tpu.telemetry import tracing
    with tracing.step_account("test/ggemm"):
        tracing.count_in_step(grouped_routed_rows=0, grouped_padded_rows=0)
        out = fn(*args)
    calls = tracing.grouped_gemm_rows("test/ggemm").get("calls", [])
    return out, {c["kernel"]: c for c in calls}


#: eid case -> (R, K, the regimes the library takes for fwd / dx / dw by
#: the shape alone on this CPU's VMEM budget).  ``streamed``: a contraction
#: so long that no [K, 128] panel fits, so forward and dw fall back to the
#: K-innermost tiling (dx contracts over N and stays resident)
GGEMM_CASES = {
    "mixed": (26, 16, "resident"),
    "empty_expert": (20, 16, "resident"),
    "one_expert": (20, 16, "resident"),
    "ragged_T": (13, 16, "resident"),
    "trailing_tiles": (24, 16, "resident"),
    "streamed": (26, 16384, "streamed"),
    "streamed_trailing_tiles": (24, 16384, "streamed"),
}


def _ggemm_case(case, E=4):
    R, K, regime = GGEMM_CASES[case]
    rng = np.random.default_rng(1)
    if case in ("mixed", "streamed"):
        eids = _rand_eids(np.random.default_rng(2), R, E)
    elif case == "empty_expert":
        eids = jnp.asarray(rng.integers(0, E - 2, (R,)), jnp.int32)
    elif case == "one_expert":
        eids = jnp.full((R,), 2, jnp.int32)
    elif case.endswith("trailing_tiles"):
        # whole tiles only: every expert's padding is spare and trails
        eids = jnp.asarray(np.repeat([0, 1, 3], 8), jnp.int32)
    else:                                # T not divisible by block_m
        eids = _rand_eids(np.random.default_rng(3), R, E)
    x = jnp.asarray(rng.standard_normal((R, K)) / np.sqrt(K / 16),
                    jnp.float32)
    return R, K, regime, eids, x


@pytest.mark.parametrize("eid_case", sorted(GGEMM_CASES))
def test_ds_ggemm_float_parity(eid_case):
    """Reference AND interpret-mode kernel vs the per-row dense oracle,
    across the ragged edge shapes the capacity formulation never sees and
    both regimes of the tiling (chosen by shape).  Tiles past the last
    routed row are written as exact zeros and never multiplied: whatever
    their input rows hold."""
    E, N, bm = 4, 24, 8
    R, K, regime, eids, x = _ggemm_case(eid_case)
    w = jnp.asarray(np.random.default_rng(7).standard_normal((E, K, N)),
                    jnp.float32)
    plan = gg.make_group_plan(eids, E, block_m=bm)
    used = int(plan.used_blocks[0])
    if eid_case.endswith("trailing_tiles"):
        assert used == 4 and plan.num_blocks == 7
    oracle = _dense_rowwise(x, w, eids)
    for interpret in (None, True):       # None -> jnp reference on CPU
        xp = gg.scatter_to_groups(x, plan)
        y, calls = _accounted(
            lambda: gg.ds_ggemm(xp, w, plan, interpret=interpret))
        got = np.asarray(gg.gather_from_groups(y, plan))
        np.testing.assert_allclose(got, oracle, rtol=2e-5, atol=2e-5)
        assert not np.asarray(y)[used * bm:].any()
        if interpret:
            assert calls["ds_ggemm_fwd"]["regime"] == regime, calls
    # the kernel does not read the trailing tiles at all
    poisoned = xp.at[used * bm:].set(jnp.nan)
    y = gg.ds_ggemm(poisoned, w, plan, interpret=True)
    np.testing.assert_allclose(np.asarray(gg.gather_from_groups(y, plan)),
                               oracle, rtol=2e-5, atol=2e-5)
    assert not np.asarray(y)[used * bm:].any()


def test_ds_ggemm_int8_parity_and_in_place():
    """Fused-dequant int8 grouped kernel (interpret) == dequantize-then-
    grouped-matmul, and the QuantizedTensor wrapper is consumed without
    materializing a float copy of the stack."""
    from deepspeed_tpu.models.model import QuantizedTensor
    from deepspeed_tpu.ops.pallas.quantization import (block_dequantize_int8,
                                                       block_quantize_int8)
    rng = np.random.default_rng(4)
    R, E, K, N = 21, 3, 16, 128
    eids = _rand_eids(rng, R, E)
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    q, s = block_quantize_int8(w)
    wd = block_dequantize_int8(q, s)
    plan = gg.make_group_plan(eids, E, block_m=8)
    xp = gg.scatter_to_groups(x, plan)
    ref = gg.gather_from_groups(gg.ds_ggemm(xp, wd, plan, interpret=True),
                                plan)
    for wq in ((q, s), QuantizedTensor(q, s, "float32")):
        got = gg.gather_from_groups(
            gg.ds_ggemm(xp, wq, plan, interpret=True), plan)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    # reference path (no interpret) agrees too
    got = gg.gather_from_groups(gg.ds_ggemm(xp, (q, s), plan), plan)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("eid_case", sorted(GGEMM_CASES))
def test_ds_ggemm_backward_kernel_matches_reference(eid_case):
    """Custom-VJP kernel backward (dx via transposed-RHS forward kernel,
    dw via the tgmm kernel; interpret mode) == ragged_dot autodiff, in
    both regimes; dw of the last expert, whose run the trailing tiles
    lengthen, included."""
    E, N = 4, 24
    R, K, regime, eids, x = _ggemm_case(eid_case)
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    plan = gg.make_group_plan(eids, E, block_m=8)
    cot = jnp.asarray(rng.standard_normal((R, N)), jnp.float32)

    def loss(x_, w_, interpret):
        xp = gg.scatter_to_groups(x_, plan)
        y = gg.gather_from_groups(
            gg.ds_ggemm(xp, w_, plan, interpret=interpret), plan)
        return jnp.sum(y * cot)

    gx_ref, gw_ref = jax.grad(lambda a, b: loss(a, b, None),
                              argnums=(0, 1))(x, w)
    (gx_k, gw_k), calls = _accounted(
        jax.grad(lambda a, b: loss(a, b, True), argnums=(0, 1)), x, w)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw_k), np.asarray(gw_ref),
                               rtol=2e-5, atol=2e-5)
    # dx contracts over N, so its panel is [N, K] whatever K is
    assert {k: c["regime"] for k, c in calls.items()} == {
        "ds_ggemm_fwd": regime, "ds_ggemm_dx": "resident",
        "ds_ggemm_dw": regime}


def _weight_fetches(tiles, K, N, E, bm, blocks, transpose_rhs=False):
    """Walks the kernel's grid (j, i, k) through its own weight index map:
    the number of times the block index changes = the weight blocks one
    call copies (a block whose index did not change is not copied again),
    beside the tiling the library chose."""
    gids, used = tiles
    rows = len(gids) * bm
    tiling = gg._choose_blocks(
        "ds_ggemm_dx" if transpose_rhs else "ds_ggemm_fwd", rows, K, N, E,
        bm, (2, 2, 2), blocks)
    index = gg._weight_block(transpose_rhs)
    fetched, last = 0, None
    for j in range(-(-N // tiling.bn)):
        for i in range(len(gids)):
            for k in range(-(-K // tiling.bk)):
                here = index(j, i, k, gids, used)
                fetched += here != last
                last = here
    return fetched, tiling


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["fwd", "dx"])
def test_weight_panel_is_fetched_once_per_expert(transpose_rhs):
    """The count behind the tentpole, on the OLMoE cell's plan (32,768
    routed rows over 64 experts, 320 M-tiles): with the library's own
    blocks the weight block's index changes at most E * N/bn times a call
    — once per expert and N block — where the K-innermost tiling it
    replaces changes it on every one of m_tiles * N/bn * K/bk steps."""
    E, bm, K, N = 64, 128, 2048, 1024
    eids = _rand_eids(np.random.default_rng(0), 4096 * 8, E)
    plan = gg.make_group_plan(eids, E, block_m=bm)
    tiles = (np.asarray(plan.block_group_ids), np.asarray(plan.used_blocks))
    m = plan.num_blocks
    assert m == 320 and int(tiles[1][0]) < m        # some tiles trail
    fetched, tiling = _weight_fetches(tiles, K, N, E, bm, None,
                                      transpose_rhs)
    n_n = N // tiling.bn
    assert tiling.regime == "resident" and tiling.bk == K
    assert fetched <= E * n_n
    assert tiling.weight_bytes == E * K * N * 2     # the account's bound
    streamed, old = _weight_fetches(tiles, K, N, E, bm, (512, 1024),
                                    transpose_rhs)
    assert old.regime == "streamed"
    assert streamed == m * (N // old.bn) * (K // old.bk)
    assert old.weight_bytes == m * K * N * 2


@pytest.mark.parametrize("shape,regimes", [
    # rows, K, N, E -> fwd / dx / dw on a v5e's budget
    ((40960, 2048, 1024, 64), ("resident", "resident", "resident")),
    ((40960, 1024, 2048, 64), ("resident", "resident", "resident")),
    # mixtral-8x7b's down projection: the [14336, bn] float32 accumulator
    # of dw is too wide to save bytes, and dw keeps the K-innermost tiling
    ((9216, 14336, 4096, 8), ("resident", "resident", "streamed")),
    # no panel at all: a contraction of 131,072
    ((9216, 131072, 4096, 8), ("streamed", "resident", "streamed")),
], ids=["olmoe_gate", "olmoe_down", "mixtral_down", "no_panel_fits"])
def test_blocks_are_chosen_by_shape(monkeypatch, shape, regimes):
    """One rule, two regimes, from shapes, dtype and the device kind: a
    panel over all of K beside the widest N block that fits the device's
    VMEM budget, else the K-innermost blocks; blocks given (the sweep's,
    DS_GGEMM_BLOCKS') are taken as given."""
    monkeypatch.setattr(gg.vmem, "device_kind", lambda: "tpu v5 lite")
    rows, K, N, E = shape
    sizes = (2, 2, 2)
    fwd = gg._choose_blocks("ds_ggemm_fwd", rows, K, N, E, 128, sizes)
    dx = gg._choose_blocks("ds_ggemm_dx", rows, N, K, E, 128, sizes)
    dw = gg._choose_blocks("ds_ggemm_dw", rows, K, N, E, 128, sizes)
    assert (fwd.regime, dx.regime, dw.regime) == regimes
    for t, (k, n) in ((fwd, (K, N)), (dx, (N, K)), (dw, (K, N))):
        assert n % t.bn == 0 and k % t.bk == 0
        assert t.vmem_bytes <= gg.vmem.budget()
        assert (t.bk == k) == (t.regime == "resident")
    given = gg._choose_blocks("ds_ggemm_fwd", rows, K, N, E, 128, sizes,
                              (512, 1024))
    assert (given.bk, given.bn, given.regime) == (512, 1024, "streamed")
    # a device the table does not know gets what fits unasked
    monkeypatch.setattr(gg.vmem, "device_kind", lambda: "cpu")
    small = gg._choose_blocks("ds_ggemm_fwd", rows, K, N, E, 128, sizes)
    assert small.vmem_bytes <= gg.vmem.UNASKED


def test_slot_kernel_parity_and_weight_stream_bound():
    """Decode-regime slot kernel (float + int8, interpret) == per-row
    oracle, and the scalar-prefetched weight-block schedule fetches each
    DISTINCT routed expert exactly once — the weights_floor_moe bound
    the ISSUE 8 acceptance names."""
    from deepspeed_tpu.ops.pallas.quantization import block_quantize_int8
    rng = np.random.default_rng(6)
    R, E, K, N = 6, 8, 16, 128
    eids = jnp.asarray([5, 1, 5, 1, 1, 3], jnp.int32)
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    q, s = block_quantize_int8(w)
    plan = gg.make_slot_plan(eids, E)
    assert plan.num_slots == min(R, E)
    active = np.asarray(plan.active)
    valid = np.asarray(plan.valid)
    # distinct experts, ascending, then the last id repeated: consecutive
    # equal block indices are not refetched, so the weight stream is
    # exactly the distinct set
    assert active[valid > 0].tolist() == [1, 3, 5]
    assert (active[valid == 0] == 5).all()
    oracle = _dense_rowwise(x, w, eids)
    got_f = gg.ds_ggemm_slots(x, w, plan, interpret=True)
    np.testing.assert_allclose(np.asarray(got_f), oracle,
                               rtol=2e-5, atol=2e-5)
    ref_q = gg.ds_ggemm_slots(x, (q, s), plan)          # jnp reference
    got_q = gg.ds_ggemm_slots(x, (q, s), plan, interpret=True)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(ref_q),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------- moe_layer parity
def _layer_setup(E=4, k=2, T=(2, 8), D=16, F=32, activation="silu_glu",
                 seed=0):
    cfg = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=k,
                    capacity_factor=float(E) / k,   # capacity = T: dropless
                    eval_capacity_factor=float(E) / k,
                    activation=activation)
    params = init_moe_params(cfg, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (*T, D))
    return cfg, params, x


def _equations(jaxpr):
    """Every equation of a traced program, sub-jaxprs included."""
    from deepspeed_tpu.telemetry.costmodel import _sub_jaxprs
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub)


@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
def test_a_rematerialised_full_plan_layer_multiplies_no_output_matrix_twice(
        monkeypatch, activation):
    """The gradient of two rematerialised full-plan layers, as traced with
    the real kernels: a row is weighted where its expert is, so the way
    back keeps no row of ``y`` — the recompute ends at the activation (a
    layer runs its first-half products twice and ``h @ w_out`` once: five
    ``ds_ggemm_fwd`` with a gate matrix, three without) and the backward
    gathers ``[R, D]`` rows out of a padded array once (the dispatch's
    cotangent), where it also gathered ``y`` for the gates' row-dot."""
    from collections import Counter
    from deepspeed_tpu.moe.layer import moe_layer
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    layers, k, D = 2, 2, 16
    cfg, params, x = _layer_setup(k=k, D=D, F=32, activation=activation)
    R = x.shape[0] * x.shape[1] * k

    def loss(params, x):
        for _ in range(layers):
            out, aux = jax.checkpoint(
                lambda p, h: moe_layer(p, h, cfg, train=True))(params, x)
            x = x + out
        return jnp.sum(x) + aux

    with dispatch_scope("grouped"):
        traced = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    kernels = Counter(
        e.params["name"]
        for e in _equations(traced) if e.primitive.name == "pallas_call")
    first_half = 2 if activation == "silu_glu" else 1
    assert kernels == {"ds_ggemm_fwd": layers * (2 * first_half + 1),
                       "ds_ggemm_dx": layers * (first_half + 1),
                       "ds_ggemm_dw": layers * (first_half + 1)}, kernels
    # rows gathered out of a padded [Mp, D] array into flat routed order:
    # the forward sum's and the dispatch's cotangent's, and no third
    row_gathers = [e for e in _equations(traced)
                   if e.primitive.name == "gather"
                   and e.outvars[0].aval.shape == (R, D)
                   and e.invars[0].aval.shape[0] > R]
    assert len(row_gathers) == 2 * layers, row_gathers
    assert not [e for e in _equations(traced)
                if "scatter" in e.primitive.name]


# ------------------------------------------------------- serving parity
@pytest.fixture(autouse=True)
def _debug_invariant(monkeypatch):
    monkeypatch.setenv("DS_SERVE_DEBUG", "1")


def _mixed_prompts(n=3, seed=0, lo=4, hi=12, V=200):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, (int(L),)).astype(np.int32)
            for L in rng.integers(lo, hi, n)]


def _run_cb(model, params, mode, prompts, max_new, cfg_kw=None,
            kv_cache_dtype=None):
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import (ContinuousBatchingScheduler,
                                       RequestState, SamplingParams)
    with dispatch_scope(mode):
        cfg = ServingConfig(**dict(dict(block_size=8, num_blocks=64,
                                        max_num_seqs=4,
                                        max_num_batched_tokens=256),
                                   **(cfg_kw or {})))
        sched = ContinuousBatchingScheduler(model, params, cfg,
                                            kv_cache_dtype=kv_cache_dtype)
        reqs = [sched.submit(p, SamplingParams(max_new_tokens=mn))
                for p, mn in zip(prompts, max_new)]
        sched.run_until_idle()
        assert all(r.state == RequestState.FINISHED for r in reqs)
        return [list(r.output_ids) for r in reqs], sched


# ------------------------------------------------- a held subset's plan
HELD_CASES = {
    # name: (R, all experts, offset, held, bound rows, bm)
    "inside_the_bound": (300, 16, 4, 4, 152, 8),
    "first_experts": (300, 16, 0, 4, 152, 8),
    "last_experts": (300, 16, 12, 4, 152, 8),
    "every_expert": (200, 4, 0, 4, 400, 8),
    "over_the_bound": (400, 8, 2, 2, 16, 8),
    "bound_of_nothing": (64, 8, 6, 2, 0, 8),
}


@pytest.mark.parametrize("case", sorted(HELD_CASES))
def test_held_plan_layout_and_rows_over_the_bound(case):
    R, E_all, off, held, bound, bm = HELD_CASES[case]
    eids = _rand_eids(np.random.default_rng(1), R, E_all)
    plan, over = gg.make_held_group_plan(eids, off, held, bound, block_m=bm)
    e = np.asarray(eids)
    mine = (e >= off) & (e < off + held)
    counts = np.asarray([(e == off + i).sum() for i in range(held)])
    assert plan.padded_rows == -(-bound // bm) * bm + held * bm
    assert plan.num_experts == held and plan.row_to_padded is None
    np.testing.assert_array_equal(plan.counts, counts)
    sizes = np.asarray(plan.group_sizes)
    assert (sizes >= bm).all() and (sizes % bm == 0).all()
    assert sizes.sum() <= plan.padded_rows
    assert int(plan.used_blocks[0]) * bm == sizes.sum()
    kept = np.minimum(counts, sizes)
    assert int(over) == mine.sum() - kept.sum()
    assert (int(over) > 0) == (case in ("over_the_bound",
                                        "bound_of_nothing"))
    # each group: its expert's first ``kept`` rows in token order, then R
    p2r = np.asarray(plan.padded_to_row)
    start = 0
    for i in range(held):
        want = np.flatnonzero(e == off + i)[:kept[i]]
        np.testing.assert_array_equal(p2r[start:start + kept[i]], want)
        assert (p2r[start + kept[i]:start + sizes[i]] == R).all()
        gids = np.asarray(plan.block_group_ids)
        assert (gids[start // bm:(start + sizes[i]) // bm] == i).all()
        start += sizes[i]
    assert (p2r[start:] == R).all()
    assert np.all(np.diff(np.asarray(plan.block_group_ids)) >= 0)


def test_held_rows_bound_is_twice_the_even_share():
    assert gg.held_rows_bound(16384 * 10, 32, 512, 128) == 20480
    assert gg.held_rows_bound(100, 3, 7, 8) == 88      # ceil(85.7) -> tile


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["ragged_dot", "kernels"])
def test_held_rows_go_out_and_come_back(interpret):
    """dispatch -> grouped GEMM -> combine over a held subset against a
    dense oracle (every token through every held expert, gate 0 where not
    chosen), forward and gradient in x, w and the gates."""
    rng = np.random.default_rng(2)
    T, k, E_all, off, held, D, N, bm = 40, 3, 8, 2, 3, 16, 24, 8
    eids = _rand_eids(rng, T * k, E_all)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((held, D, N)), jnp.float32)
    gates = jnp.asarray(rng.uniform(0.1, 1, (T * k,)), jnp.float32)
    bound = gg.held_rows_bound(T * k, held, E_all, bm)

    def ours(x, w, gates):
        plan, over = gg.make_held_group_plan(eids, off, held, bound,
                                             block_m=bm)
        y = gg.ds_ggemm(gg.dispatch_held_rows(x, plan, k), w, plan,
                        interpret=interpret)
        return gg.combine_held_rows(y, gates, plan, k), over

    def dense(x, w, gates):
        onehot = jax.nn.one_hot(eids - off, held)           # 0 rows: elsewhere
        per_expert = (gates[:, None] * onehot).reshape(T, k, held).sum(1)
        return jnp.einsum("te,td,edn->tn", per_expert, x, w)

    got, over = ours(x, w, gates)
    assert int(over) == 0
    np.testing.assert_allclose(got, dense(x, w, gates), atol=1e-4)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(
        fn(*a)[0] if fn is ours else fn(*a)))
    g_got = jax.grad(loss(ours), argnums=(0, 1, 2))(x, w, gates)
    g_want = jax.grad(loss(dense), argnums=(0, 1, 2))(x, w, gates)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-4)


# ------------------------------------------------------------- tooling
def test_ggemm_sweep_smoke():
    """scripts/ggemm_sweep.py runs the interpret-mode smoke and emits
    well-formed JSON rows for the float (fwd, dx, dw), int8, and slot
    kernels."""
    import json as _json
    env = child_env(GGEMM_SWEEP_SMOKE="1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "ggemm_sweep.py")],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [_json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    kinds = {r.get("kind") for r in rows}
    assert {"f", "dx", "dw", "int8", "int8_slots"} <= kinds, rows
    assert not any("error" in r for r in rows), rows
    # the three training kernels say what their tiling moves
    for r in rows:
        if r.get("kind") in ("f", "dx", "dw") and "winner" not in r:
            assert r["regime"] in ("resident", "streamed"), r
            # plumbing only, no timing: under six xdist workers the
            # slope of two tiny interpret-mode chains (2 and 10 steps,
            # ~0.2 ms apart) can come out <= 0, and the script then
            # reports no rate (GBs null) — what this test used to trip on
            assert r["bytes_per_call"] > 0 and "GBs" in r, r
            assert "pct_of_bf16_peak" in r and len(r["blocks"]) == 3, r
