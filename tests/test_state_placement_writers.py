"""The step is built once a start, continued from
tests/test_state_placement.py: the four-device meshes (ZeRO-3 over
``data``, experts over ``expert``), a step that overflows under fp16, the
micro-step API and the host-offload tier — every writer of the counter and
the scaler leaves them on ``engine.state_shardings``, and the step after
finds the program the first one compiled."""
import jax
import numpy as np
import pytest

from deepspeed_tpu.models.mixtral import mixtral_model
from test_state_placement import (  # noqa: F401 — ``account`` is a fixture
    ENGINES, FP16, account, batch_of, built, built_once_and_nothing_later,
    recompiles, stage_config, start_of, toy_engine, unplaced)
from util import random_batch, tiny_gpt2

#: a scale no float16 gradient survives: every step overflows and rewrites
#: the scaler's leaves (the budget of one lets the first overflow shrink it)
OVERFLOWING = {"enabled": True, "loss_scale": 0, "initial_scale_power": 32,
               "hysteresis": 1}
OFFLOAD = {"stage": 2, "offload_optimizer": {"device": "cpu"}}


def four_wide(axis):
    return jax.sharding.Mesh(np.asarray(jax.devices()[:4]), (axis,))


def zero3_on_four():
    return toy_engine(on=four_wide("data"), **stage_config(3, False))


def experts_on_four():
    return toy_engine(
        model=mixtral_model("tiny", attention_impl="xla", dtype="float32",
                            capacity_factor=4.0),
        on=four_wide("expert"), mesh={"expert_parallel_size": 4},
        zero_optimization={"stage": 2})


ENGINES.update(zero3_data4=zero3_on_four, expert4=experts_on_four)
FOUR = ["zero3_data4", "expert4"]


# ------------------------------------------------- the four-device meshes
@pytest.mark.parametrize("name", FOUR)
def test_on_four_devices_the_state_is_born_placed(name, devices8):
    start = start_of(name)
    assert start.engine.mesh.size == 4
    assert start.at_init == []
    assert start.after_steps == []
    state = start.engine.state
    for leaf in (state["step"], *state["scaler"]):
        assert len(leaf.sharding.device_set) == 4
        assert leaf.sharding.is_fully_replicated


@pytest.mark.parametrize("name", FOUR)
def test_on_four_devices_later_steps_build_nothing(name, devices8):
    built_once_and_nothing_later(start_of(name))


# ------------------------------------------------ the writers of the two
@pytest.mark.parametrize("zero", [{"stage": 0}, OFFLOAD],
                         ids=["fused", "host_offload"])
def test_the_step_after_an_overflow_finds_its_program(zero, devices8):
    count0 = recompiles()
    engine = toy_engine(model=tiny_gpt2(dtype="float16"), fp16=OVERFLOWING,
                        zero_optimization=zero)
    batch = batch_of(engine)
    scales = []
    for _ in range(3):
        engine.train_batch(batch=batch)
        scales.append(engine.loss_scale)
        assert unplaced(engine) == []
    # each step overflowed: the scaler's leaves were rewritten, the
    # counter stood still
    assert scales == [2.0 ** 31, 2.0 ** 30, 2.0 ** 29]
    assert int(engine.state["step"]) == 0
    assert built(step_from=1) == []
    assert recompiles() == count0


@pytest.mark.parametrize("config", [
    {}, {"fp16": FP16}, {"zero_optimization": OFFLOAD},
    {"zero_optimization": OFFLOAD, "fp16": FP16}],
    ids=["micro", "micro_fp16", "micro_offload", "micro_offload_fp16"])
def test_the_micro_step_api_keeps_the_state_placed(config, devices8):
    """``forward`` / ``backward`` / ``step``: the ``grad`` and ``apply``
    programs, and under offload the host-side epilogue that advances the
    counter and the scaler outside any jit."""
    engine = toy_engine(**config)
    micro = random_batch(batch_size=engine.topology.dp_world_size,
                         seq_len=16)
    for step in range(3):
        engine.backward(engine.forward(micro))
        engine.step()
        assert unplaced(engine) == []
        assert int(engine.state["step"]) == step + 1
    assert built()                          # the path's programs are named
    assert built(step_from=1) == []


def test_host_offload_steps_keep_the_state_placed(devices8):
    engine = toy_engine(zero_optimization=OFFLOAD, fp16=FP16)
    batch = batch_of(engine)
    for step in range(3):
        engine.train_batch(batch=batch)
        assert unplaced(engine) == []
        assert int(engine.state["step"]) == step + 1
    assert {program for program, *_ in built()} == {"grad_step"}
    assert built(step_from=1) == []
