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
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.moe.layer import (MoEConfig, dispatch_scope,
                                     init_moe_params, moe_layer,
                                     resolve_dispatch_mode,
                                     set_moe_metrics_registry)
from deepspeed_tpu.moe.sharded_moe import topk_routing, topkgating
from deepspeed_tpu.ops.pallas import grouped_gemm as gg


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


def _take_combine(y, gates, r2p, k):
    out_rows = jnp.take(y, r2p, axis=0)
    return jnp.sum((gates.astype(y.dtype)[:, None] * out_rows).reshape(
        -1, k, y.shape[1]), axis=1)


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
    """dispatch_rows / combine_rows (gathers through the plan's two maps,
    hand-written backward) against take -> scatter -> ... -> take under
    plain autodiff: the same layout, the same forward to the bit, the
    same gradients, and no scatter in the traced gradient."""
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
            gg.combine_rows(y.astype(dt), gates, plan, k),
            _take_combine(y.astype(dt), gates, r2p, k))
    np.testing.assert_array_equal(
        gg.scatter_to_groups(jnp.repeat(xt, k, axis=0), plan),
        gg.dispatch_rows(xt, plan, k))

    def new(xt_, y_, gates_):
        return (jnp.sum(gg.dispatch_rows(xt_, plan, k) * c_pad)
                + jnp.sum(gg.combine_rows(y_, gates_, plan, k) * c_out))

    def old(xt_, y_, gates_):
        return (jnp.sum(_scatter_dispatch(xt_, r2p, k, plan.padded_rows)
                        * c_pad)
                + jnp.sum(_take_combine(y_, gates_, r2p, k) * c_out))

    got = jax.grad(new, argnums=(0, 1, 2))(xt, y, gates)
    want = jax.grad(old, argnums=(0, 1, 2))(xt, y, gates)
    for name, a, b in zip(("d xt", "d y", "d gates"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)
    # a padding row's cotangent is an exact zero, as the scatter's was
    pad = np.setdiff1d(np.arange(plan.padded_rows), np.asarray(r2p))
    assert not np.asarray(got[1])[pad].any()

    def scatters(fn):
        from deepspeed_tpu.telemetry.costmodel import primitive_names
        return [n for n in primitive_names(jax.make_jaxpr(
            jax.grad(fn, argnums=(0, 1, 2)))(xt, y, gates))
            if "scatter" in n]
    assert scatters(old) and not scatters(new)


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


# ------------------------------------------------------- dispatch modes
def test_dispatch_mode_resolution_and_validation(monkeypatch):
    cfg = MoEConfig(d_model=8, d_ff=16, dispatch_mode="auto")
    assert resolve_dispatch_mode(cfg, train=True) == "einsum"
    # this host has 8 (virtual) devices and no real kernel: auto at eval
    # keeps the sharded einsum formulation; with the real kernel forced
    # (interpret) auto picks grouped
    assert resolve_dispatch_mode(cfg, train=False) == "einsum"
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    assert resolve_dispatch_mode(cfg, train=False) == "grouped"
    monkeypatch.delenv("DS_GGEMM_INTERPRET")
    with dispatch_scope("grouped"):
        assert resolve_dispatch_mode(cfg, train=True) == "grouped"
    assert resolve_dispatch_mode(cfg, train=True) == "einsum"
    with pytest.raises(ValueError, match="dispatch mode"):
        with dispatch_scope("bogus"):
            pass
    os.environ["DS_MOE_DISPATCH"] = "einsum"
    try:
        with dispatch_scope("grouped"):     # env wins over the override
            assert resolve_dispatch_mode(cfg, train=False) == "einsum"
    finally:
        del os.environ["DS_MOE_DISPATCH"]
    from deepspeed_tpu.runtime.config import ServingConfig
    with pytest.raises(ValueError, match="moe_dispatch"):
        ServingConfig(moe_dispatch="nope")
    assert ServingConfig(moe_dispatch="grouped").moe_dispatch == "grouped"


def test_serving_config_installs_dispatch_override(devices8):
    """An explicit serving.moe_dispatch reaches the layer-side resolver
    at scheduler construction (the quant_scan_threshold pattern)."""
    from deepspeed_tpu.moe.layer import set_dispatch_override
    from deepspeed_tpu.runtime.config import ServingConfig
    from deepspeed_tpu.serving import ContinuousBatchingScheduler
    from tests.util import tiny_gpt2
    m = tiny_gpt2()
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    cfg = ServingConfig(block_size=8, num_blocks=16, moe_dispatch="einsum")
    try:
        ContinuousBatchingScheduler(m, eng.params, cfg)
        mcfg = MoEConfig(d_model=8, d_ff=16, dispatch_mode="auto")
        assert resolve_dispatch_mode(mcfg, train=False) == "einsum"
    finally:
        set_dispatch_override(None)


def test_topk_routing_matches_topkgating():
    """The extracted routing decision is bitwise the gating half of
    topkgating — capacity is a property of the dispatch, not the
    router."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    r = topk_routing(logits, 2)
    g = topkgating(logits, 2, capacity_factor=2.0)
    assert float(r.l_aux) == float(g.l_aux)
    # each token's gate weights appear in the combine tensor exactly
    cw = np.asarray(g.combine_weights)      # [T, E, C]
    for t in range(8):
        for i in range(2):
            e = int(r.expert_idx[t, i])
            want = float(r.gate_weights[t, i])
            assert np.isclose(cw[t, e].max(), want, atol=1e-7)


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


@pytest.mark.parametrize("activation", ["silu_glu", "gelu"])
def test_grouped_matches_einsum_eval(activation):
    cfg, params, x = _layer_setup(activation=activation)
    with dispatch_scope("einsum"):
        ye, ae = moe_layer(params, x, cfg, train=False)
    with dispatch_scope("grouped"):
        yg, ag = moe_layer(params, x, cfg, train=False)
    np.testing.assert_allclose(np.asarray(yg), np.asarray(ye),
                               rtol=2e-5, atol=2e-5)
    assert float(ae) == pytest.approx(float(ag), rel=1e-6)


def test_grouped_matches_einsum_train_fwd_bwd():
    """Train-mode forward AND gradients agree at matched (drop-free)
    capacity — the formulations compute the same math."""
    cfg, params, x = _layer_setup()

    def loss(p, mode):
        with dispatch_scope(mode):
            out, aux = moe_layer(p, x, cfg, train=True)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    le, ge = jax.value_and_grad(loss)(params, "einsum")
    lg, gr = jax.value_and_grad(loss)(params, "grouped")
    assert float(le) == pytest.approx(float(lg), rel=1e-5)
    for key in ("router", "w_in", "w_out", "w_gate"):
        np.testing.assert_allclose(np.asarray(gr[key]), np.asarray(ge[key]),
                                   rtol=5e-5, atol=5e-5,
                                   err_msg=f"grad mismatch on {key}")


def test_grouped_is_dropless_when_einsum_drops():
    """Skewed routing at capacity_factor=1: einsum drops tokens (output
    loses their contribution), grouped computes every routed token."""
    E, k, D, F = 4, 1, 16, 32
    cfg = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=k,
                    capacity_factor=1.0, eval_capacity_factor=1.0,
                    min_capacity=1)
    params = init_moe_params(cfg, jax.random.PRNGKey(2))
    # force every token to expert 0: router bias via inputs aligned to
    # one direction -> capacity T/E drops 3/4 of tokens in einsum mode
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 1, D)),
                 (2, 8, 1))
    with dispatch_scope("einsum"):
        ye, _ = moe_layer(params, x, cfg, train=False)
    with dispatch_scope("grouped"):
        yg, _ = moe_layer(params, x, cfg, train=False)
    # identical rows: grouped computes ALL of them; einsum zeroes the
    # dropped ones -> rows differ
    assert not np.allclose(np.asarray(ye), np.asarray(yg))
    # grouped treats every row of the tiled batch identically (dropless)
    g = np.asarray(yg).reshape(-1, D)
    np.testing.assert_allclose(g, np.broadcast_to(g[0], g.shape),
                               rtol=1e-5, atol=1e-6)


def test_expert_ffn_gelu_ignores_gate_operand():
    """ISSUE 8 satellite: gelu-mode experts must not consume (nor
    require) a gate operand — outputs identical with and without the
    w_gate key present."""
    cfg, slim, x = _layer_setup(activation="gelu", seed=7)
    assert "w_gate" not in slim     # gelu init carries no gate weights
    # a spurious gate leaf (e.g. a checkpoint converted from a GLU
    # config) must be IGNORED, not vmapped as a phantom operand — the
    # old params.get("w_gate", params["w_in"]) default always vmapped
    # something
    params = dict(slim, w_gate=jnp.ones_like(slim["w_in"]) * 999.0)
    with dispatch_scope("einsum"):
        with_gate, _ = moe_layer(params, x, cfg, train=False)
    with dispatch_scope("einsum"):
        without_gate, _ = moe_layer(slim, x, cfg, train=False)
    np.testing.assert_array_equal(np.asarray(with_gate),
                                  np.asarray(without_gate))
    with dispatch_scope("grouped"):
        grouped, _ = moe_layer(slim, x, cfg, train=False)
    np.testing.assert_allclose(np.asarray(grouped),
                               np.asarray(without_gate),
                               rtol=2e-5, atol=2e-5)


def test_routing_telemetry_counters():
    """moe/dispatch_tokens + moe/dropped_tokens + moe_drop_fraction:
    einsum reports real capacity drops, grouped pins drops to 0."""
    from deepspeed_tpu.telemetry import MetricsRegistry
    E, k, D, F = 4, 1, 16, 32
    cfg = MoEConfig(d_model=D, d_ff=F, num_experts=E, top_k=k,
                    capacity_factor=1.0, eval_capacity_factor=1.0,
                    min_capacity=1)
    params = init_moe_params(cfg, jax.random.PRNGKey(2))
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(3), (1, 1, D)),
                 (2, 8, 1))                 # all 16 tokens -> one expert
    reg = MetricsRegistry()
    set_moe_metrics_registry(reg)
    try:
        with dispatch_scope("einsum"):
            moe_layer(params, x, cfg, train=False)
        jax.effects_barrier()
        dropped = reg.get_counter("moe/dropped_tokens")
        assert dropped == 12                # capacity 4 of 16 kept
        assert reg.get_counter("moe/dispatch_tokens") == 4
        assert reg.get_gauge("moe_drop_fraction") == pytest.approx(0.75)
        with dispatch_scope("grouped"):
            moe_layer(params, x, cfg, train=False)
        jax.effects_barrier()
        assert reg.get_counter("moe/dropped_tokens") == dropped  # +0
        assert reg.get_counter("moe/dispatch_tokens") == 4 + 16
        assert reg.get_gauge("moe_drop_fraction") == 0.0
    finally:
        set_moe_metrics_registry(None)


def test_grouped_gemm_span_on_eager_call(tmp_path, monkeypatch):
    """moe/grouped_gemm span lands on the Perfetto timeline for eager
    kernel invocations (the sweep/op-level surface)."""
    from deepspeed_tpu.telemetry import SpanTracer
    from deepspeed_tpu.telemetry import tracing as _tracing
    rng = np.random.default_rng(8)
    E, K, N, R = 3, 16, 24, 10
    eids = _rand_eids(rng, R, E)
    x = jnp.asarray(rng.standard_normal((R, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    plan = gg.make_group_plan(eids, E, block_m=8)
    tracer = SpanTracer(str(tmp_path / "trace.json"))
    monkeypatch.setattr(_tracing, "_ACTIVE", tracer)
    gg.ds_ggemm(gg.scatter_to_groups(x, plan), w, plan, interpret=True)
    names = [e.get("name") for e in tracer._events]
    assert "moe/grouped_gemm" in names


# ------------------------------------------------------- EP: the exchange
def test_grouped_request_on_ep_mesh_exchanges_and_matches(devices8):
    """A grouped request on a multi-device expert axis stays grouped: the
    layer exchanges its rows (moe/layer.py ``_exchanged_grouped_moe``; here
    expert 2 x data 4) and the math is unchanged vs the single-device
    grouped run.  Tokens the chips cannot split evenly (a generation's 14
    prompt tokens and 2 a decode step over 8 chips) are made up with rows
    of zero gate, so ``generate`` serves as it did through the einsum, and
    greedy tokens match exactly."""
    from deepspeed_tpu.models.mixtral import mixtral_model
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.comm import reset_topology
    m = mixtral_model("tiny", attention_impl="xla", dtype="float32",
                      max_seq_len=64, moe_dispatch="grouped")
    params = m.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    batch = {"input_ids": rng.integers(1, 200, (8, 16)).astype(np.int32)}
    ref_eng = InferenceEngine(m, DeepSpeedInferenceConfig(dtype="float32"),
                              model_parameters=params)
    ref = np.asarray(jax.jit(m.apply)(ref_eng.params, batch))
    prompts = rng.integers(1, 200, (2, 7)).astype(np.int32)
    ref_tokens = np.asarray(ref_eng.generate(prompts, max_new_tokens=8,
                                             do_sample=False))
    reset_topology()
    ep_eng = InferenceEngine(
        m, DeepSpeedInferenceConfig(dtype="float32", moe={"ep_size": 2}),
        model_parameters=params)
    assert dict(ep_eng.mesh.shape)["expert"] == 2
    with ep_eng.mesh:
        from deepspeed_tpu.comm.mesh import get_topology
        assert dict(get_topology().mesh.shape)["expert"] == 2
        assert resolve_dispatch_mode(m.config.moe, train=False) == "grouped"
        fn = jax.jit(m.apply)
        got = np.asarray(fn(ep_eng.params, batch))
        assert " all-to-all(" in fn.lower(
            ep_eng.params, batch).compile().as_text()
    np.testing.assert_allclose(got, ref, atol=2e-5)
    got_tokens = np.asarray(ep_eng.generate(prompts, max_new_tokens=8,
                                            do_sample=False))
    np.testing.assert_array_equal(got_tokens, ref_tokens)


# ------------------------------------------------------- serving parity
@pytest.fixture(autouse=True)
def _debug_invariant(monkeypatch):
    monkeypatch.setenv("DS_SERVE_DEBUG", "1")


@pytest.fixture(scope="module")
def mixtral_served():
    from deepspeed_tpu.models.mixtral import mixtral_model
    m = mixtral_model("tiny", attention_impl="xla", dtype="float32",
                      max_seq_len=128)
    eng = deepspeed_tpu.init_inference(model=m,
                                       config={"dtype": "float32"})
    return m, eng


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


def test_mixtral_cb_grouped_matches_einsum(mixtral_served):
    m, eng = mixtral_served
    prompts = _mixed_prompts(4, seed=1)
    max_new = [6, 4, 8, 5]
    outs_g, _ = _run_cb(m, eng.params, "grouped", prompts, max_new)
    outs_e, _ = _run_cb(m, eng.params, "einsum", prompts, max_new)
    assert outs_g == outs_e


def test_mixtral_cb_grouped_int8_kv(mixtral_served):
    m, _ = mixtral_served
    eng8 = deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "kv_cache_dtype": "int8"})
    prompts = _mixed_prompts(3, seed=2)
    max_new = [5, 5, 5]
    outs_g, _ = _run_cb(m, eng8.params, "grouped", prompts, max_new,
                        kv_cache_dtype="int8")
    outs_e, _ = _run_cb(m, eng8.params, "einsum", prompts, max_new,
                        kv_cache_dtype="int8")
    assert outs_g == outs_e


def test_mixtral_cb_grouped_int8_weights_interpret(mixtral_served,
                                                   monkeypatch):
    """int8 expert stacks through the REAL fused-dequant grouped kernels
    (interpret mode): cb greedy == static int8 generate, with the 4-D
    expert leaves staying quantized into the kernel (keep_moe_quantized)
    and the dense projections on the qgemm route."""
    m, _ = mixtral_served
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    from deepspeed_tpu.models.serving import (moe_dispatch_grouped,
                                              qgemm_scope)
    engq = deepspeed_tpu.init_inference(
        model=m, config={"dtype": "float32", "quant": {"enabled": True}})
    from deepspeed_tpu.models.model import QuantizedTensor
    is_q = lambda x: isinstance(x, QuantizedTensor)
    ndims = {l.q.ndim for l in jax.tree_util.tree_leaves(
        engq.params["blocks"], is_leaf=is_q) if is_q(l)}
    assert 4 in ndims                       # stacked experts quantized
    prompts = _mixed_prompts(3, seed=3)
    max_new = [5, 6, 4]
    with qgemm_scope(True):
        with dispatch_scope("grouped"):
            assert moe_dispatch_grouped(m.config.moe)
        outs_g, _ = _run_cb(m, engq.params, "grouped", prompts, max_new)
        refs = [list(np.asarray(engq.generate(
            p[None], max_new_tokens=mn, do_sample=False))[0, p.size:])
            for p, mn in zip(prompts, max_new)]
    assert outs_g == refs


def test_mixtral_spec_decode_grouped_parity(mixtral_served):
    """Speculative (ngram) decoding over grouped dispatch — verify
    windows ride the slot/grouped kernels and rollback keeps greedy
    outputs identical to plain grouped cb."""
    rng = np.random.default_rng(4)
    m, eng = mixtral_served
    motif = rng.integers(1, 200, (5,))
    prompts = [np.concatenate([rng.integers(1, 200, (2,)),
                               np.tile(motif, 4)]).astype(np.int32)
               for _ in range(3)]
    max_new = [8, 6, 8]
    spec_cfg = {"spec": {"mode": "ngram", "max_draft_tokens": 4}}
    outs_spec, sched = _run_cb(m, eng.params, "grouped", prompts, max_new,
                               cfg_kw=spec_cfg)
    assert sched.metrics.counters["spec_verify_steps"] > 0
    outs_plain, _ = _run_cb(m, eng.params, "grouped", prompts, max_new)
    assert outs_spec == outs_plain


def test_mixtral_prefix_cache_grouped_parity(mixtral_served):
    """Prefix-cache COW forks + suffix prefill through grouped dispatch:
    cache-on greedy outputs == cache-off (shared-prefix workload)."""
    rng = np.random.default_rng(5)
    m, eng = mixtral_served
    system = rng.integers(1, 200, (24,))
    prompts = [np.concatenate([system,
                               rng.integers(1, 200, (int(t),))]
                              ).astype(np.int32)
               for t in rng.integers(3, 8, 3)]
    max_new = [6, 6, 6]
    pc = {"prefix_cache": {"enabled": True}}
    outs_on, sched = _run_cb(m, eng.params, "grouped", prompts, max_new,
                             cfg_kw=pc)
    assert sched.metrics.counters["prefix_cache_hit"] > 0
    outs_off, _ = _run_cb(m, eng.params, "grouped", prompts, max_new)
    assert outs_on == outs_off


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
    env = dict(os.environ, GGEMM_SWEEP_SMOKE="1", JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "ggemm_sweep.py")],
        capture_output=True, text=True, timeout=560, env=env)
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
