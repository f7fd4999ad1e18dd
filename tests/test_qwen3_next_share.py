"""Qwen3-Next's toy model (tests/test_qwen3_next.py: the same sizes, seeded
weights, packed batch and reference) with each thing that makes the model
itself left out in turn, and the share of an expert-parallel layer: its
parts add up, a row over the bound is counted.  A file of its own so that
``--dist loadfile`` gives the family's tests to four workers."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params

from tests.test_qwen3_next import (  # noqa: F401 (the fixtures come by name)
    B, LOSS_TOL, micro, packed_batch, real_kernels,
    reference_loss_without_reset, reference_numbers, S, seeded_params, toy,
    toy_model)


#: what makes this model itself, each left out of one side in turn: the
#: loss then has to leave the tolerance
@pytest.mark.parametrize("left_out", ["document_reset", "shared_expert_gate",
                                      "held_subset"])
def test_a_departure_left_out_is_outside_the_tolerance(left_out):
    _, params, mb, loss_and_grads = toy()
    want = float(reference_numbers()[0])
    if left_out == "document_reset":
        # the model packed against the reference that never resets
        want = float(reference_loss_without_reset())
        got = float(loss_and_grads(params, mb)[0])
    elif left_out == "shared_expert_gate":
        off = jax.tree_util.tree_map_with_path(
            lambda path, w: w * 0 if path[-1].key == "shared_router" else w,
            params)
        got = float(loss_and_grads(off, mb)[0])
    else:
        other = toy_model(expert_offset=4)
        got = float(jax.jit(other.loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


# ----------------------------------------------------------- the share
SHARE = MoEConfig(d_model=32, d_ff=16, num_experts=16, top_k=4,
                  dispatch_mode="grouped", load_balance="all_choices",
                  aux_loss_coef=0.001, shared_expert_d_ff=16,
                  shared_expert_gate=True)


def _share_setup():
    params = jax.tree.map(lambda a: a * 20,
                          init_moe_params(SHARE, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    return params, x


def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out", "w_gate")
                else w) for k, w in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all four shares (4
    experts of 16 each) plus the shared expert counted once are the uncut
    layer's output; the router loss is the same on every share."""
    params, x = _share_setup()
    whole, aux = moe_layer.moe_layer(params, x, SHARE)
    routed_only = replace(SHARE, shared_expert_d_ff=0)
    shared = whole - moe_layer.moe_layer(params, x, routed_only)[0]
    total = shared
    for i in range(4):
        cfg = replace(routed_only, expert_offset=4 * i, experts_held=4)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(params, 4 * i, 4), x, cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-6)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


def test_a_share_allocates_its_own_experts_only():
    cfg = replace(SHARE, expert_offset=4, experts_held=4)
    shapes = jax.eval_shape(lambda k: init_moe_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert shapes["router"].shape == (32, 16)
    assert shapes["w_in"].shape == (4, 32, 16)
    assert shapes["w_out"].shape == (4, 16, 32)


def test_a_row_over_the_bound_is_counted(monkeypatch):
    """A plan too short for the rows the router sends here (tiles of 8
    and a bound of 16 rows where 144 are expected): the rest is counted,
    the statistics carry it, and the model's loss comes with the sum."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model = toy_model(remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    cfg = model.config.moe
    h = jax.random.normal(jax.random.PRNGKey(0), (B, S, 64))
    layer = jax.tree.map(lambda w: w[0, 0], params["blocks"]["full"]["moe"])
    _, _, stats = moe_layer.moe_layer(layer, h, cfg, return_stats=True)
    assert int(stats["dispatched"]) == 16 + 4 * 8       # the plan, full
    assert int(stats["dropped"]) > 0
    eids = moe_layer._route(layer, moe_layer._routing_logits(
        layer, h.reshape(-1, 64), cfg), cfg, True, None).expert_idx
    here = int(jnp.sum((eids >= 8) & (eids < 12)))
    assert int(stats["dropped"]) + int(stats["dispatched"]) == here
    # the model's loss comes with the count of all four layers
    _, counts = jax.jit(model.loss_with_counts_fn)(params, mb)
    over = int(counts["moe/rows_over_bound"])
    assert over > int(stats["dropped"])
    assert "callback" not in jax.jit(model.loss).lower(params, mb).as_text()


def test_a_share_runs_through_the_grouped_dispatch_only():
    params, x = _share_setup()
    cfg = replace(SHARE, expert_offset=4, experts_held=4,
                  dispatch_mode="einsum")
    with pytest.raises(ValueError, match="grouped dispatch only"):
        moe_layer.moe_layer(_held(params, 4, 4), x, cfg)
