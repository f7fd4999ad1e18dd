"""Driver ``train_steps_ep``: what it holds a run to in ``check_split``'s
place, each planted wrong in turn on a toy engine over a four-wide expert
axis (four virtual CPU devices), and its statements about the compiled
step on hand-made text."""
import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mellum import mellum_model
from drivers import train_steps_ep as ep

TOY = dict(num_layers=4, d_model=32, num_heads=4, num_kv_heads=2,
           head_dim=16, sliding_window=8, original_max_position_embeddings=16,
           d_ff=16, num_experts=8, top_k=2, held_rows_factor=4,
           vocab_size=256, max_seq_len=128, dtype="float32")


def engine_on(axes, shape, stage=2, **mesh):
    mesh_ = jax.sharding.Mesh(
        np.asarray(jax.devices()[:4]).reshape(shape), axes)
    engine, *_ = deepspeed_tpu.initialize(
        model=mellum_model("12b-a2.5b", **TOY), mesh=mesh_, config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1, "steps_per_print": 0,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage},
            **({"mesh": mesh} if mesh else {})})
    return engine


@pytest.fixture(autouse=True)
def _no_topology_left_behind():
    """benchmarks/tests has no fixture of tests/conftest.py's kind: the
    next file's model code, traced outside an engine, would read a
    four-wide expert axis."""
    yield
    from deepspeed_tpu.comm import reset_topology
    reset_topology()


@pytest.fixture
def small_leaves_count(monkeypatch):
    monkeypatch.setattr(ep, "LARGE", 1 << 10)


def test_the_deployment_passes_its_own_checks(small_leaves_count):
    problems = []
    ep.check_deployment(engine_on(("expert",), (4,), expert_parallel_size=4),
                        4, problems)
    assert problems == []


def test_experts_not_spread_fail(small_leaves_count, capsys):
    """Data parallel over the four chips, every chip holding every expert
    (ZeRO-2 splits their optimizer state, not the leaves)."""
    problems = []
    ep.check_deployment(engine_on(("data",), (4,)), 4, problems)
    assert any("expert leaf" in p and "not split 4 ways by expert" in p
               for p in problems), problems
    assert not any("optimizer state" in p for p in problems)


def test_optimizer_state_not_split_fails(small_leaves_count):
    """Stage 0 on the expert axis: the experts are spread, the dense
    optimizer state and accumulated gradients whole on every chip."""
    problems = []
    ep.check_deployment(
        engine_on(("expert",), (4,), stage=0, expert_parallel_size=4), 4,
        problems)
    assert any("optimizer state" in p for p in problems), problems
    assert any("accumulated gradient" in p for p in problems), problems
    assert not any("expert leaf" in p for p in problems)


EXCHANGE = [{"pairs": 4, "path": "all_to_all"}]
RAGGED = [{"pairs": 4, "path": "ragged_all_to_all"}]
GOOD = """
  %all-to-all.1 = bf16[131072,2304]{1,0} all-to-all(%x), replica_groups={{0,1,2,3}}
  %custom-call.2 = bf16[133120,896]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
"""
# what lax.ragged_all_to_all compiles to on the chip
GOOD_ON_CHIP = GOOD.replace("all-to-all", "ragged-all-to-all")


def problems_of(text, exchanges=EXCHANGE, on_chip=False):
    problems = []
    ep.check_program(text, exchanges, (8192, 32768), 64, problems, on_chip)
    return problems


def test_the_program_checks_on_hand_made_text():
    assert problems_of(GOOD) == []
    assert problems_of(GOOD.replace("all-to-all.1", "all-to-all-start.1")
                       .replace(" all-to-all(", " all-to-all-start(")) == []
    # the chip's collective and the layer's word for it, on and off the chip
    assert problems_of(GOOD_ON_CHIP, RAGGED) == []
    assert problems_of(GOOD_ON_CHIP, RAGGED, on_chip=True) == []


@pytest.mark.parametrize("text, exchanges, on_chip, word", [
    (GOOD.replace("all-to-all", "all-gather"), EXCHANGE, False,
     "no all-to-all"),
    (GOOD + "  %r = bf16[8,8]{1,0} ragged-dot(%a, %b, %g)\n", EXCHANGE, False,
     "ragged-dot"),
    (GOOD + "  %d = bf16[8192,64,1280]{2,1,0} convert(%m)\n", EXCHANGE,
     False, "capacity formulation"),
    (GOOD + "  %d = pred[32768,64,5120]{2,1,0} compare(%m, %n)\n", EXCHANGE,
     False, "capacity formulation"),
    (GOOD, None, False, "no exchange"),
    (GOOD, [{"pairs": 1, "path": "all_to_all"}], False, "no exchange"),
    # the account says ragged and the text holds the stand-in, or the other
    # way about: the two are told apart
    (GOOD, RAGGED, False, "no ragged-all-to-all"),
    (GOOD_ON_CHIP, EXCHANGE, False, "no all-to-all"),
    # on the chip the stand-in will not do, whatever the text holds
    (GOOD, EXCHANGE, True, "lax.ragged_all_to_all"),
    (GOOD, [{"pairs": 4, "path": "gathered"}], False, "no exchange"),
], ids=["no_all_to_all", "ragged_dot", "dispatch_einsum", "combine_mask",
        "no_account", "one_wide", "account_ragged_text_plain",
        "account_plain_text_ragged", "stand_in_on_the_chip",
        "unknown_path"])
def test_each_statement_planted_wrong_fails(text, exchanges, on_chip, word):
    problems = problems_of(text, exchanges, on_chip)
    assert len(problems) == 1 and word in problems[0], problems


def test_the_capacity_einsum_is_seen_in_a_real_program():
    """The einsum formulation on the same mesh leaves its [tokens, experts,
    capacity] arrays in the lowered text; the exchange leaves none."""
    import jax.numpy as jnp
    from dataclasses import replace
    from deepspeed_tpu.comm.mesh import MeshTopology, set_topology
    from deepspeed_tpu.moe.layer import (MoEConfig, init_moe_params,
                                         moe_layer)
    set_topology(MeshTopology(devices=jax.devices()[:4],
                              expert_parallel_size=4))
    config = MoEConfig(d_model=32, d_ff=16, num_experts=8, top_k=2,
                       dispatch_mode="grouped", held_rows_factor=4)
    params = init_moe_params(config, jax.random.PRNGKey(0))
    x = jnp.zeros((4, 16, 32))

    def text(config):
        return jax.jit(lambda p, x: moe_layer(p, x, config)[0]).lower(
            params, x).as_text(dialect="hlo")

    assert ep.capacity_arrays(text(config), (64, 16), 8) == []
    assert ep.capacity_arrays(
        text(replace(config, dispatch_mode="einsum")), (64, 16), 8)
