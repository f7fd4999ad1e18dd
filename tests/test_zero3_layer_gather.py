"""ZeRO-3 gathers one layer at a time (ISSUE 26): in the compiled step of
a toy GPT-2 on the virtual devices, the layer loops hold one all-gather per
ZeRO-sharded stacked leaf, of one layer's size, and no collective on
activations; the losses are those of plain data parallelism; a checkpoint
written under the old layout (ZeRO axes on the layer axis) loads."""
import re

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime import engine as engine_module
from deepspeed_tpu.runtime.zero.policy import ZeroShardingPolicy
from deepspeed_tpu.telemetry.tracing import (
    get_program_map, in_layer_loop, layer_loop_gathers, reset_programs)
from tests.test_step_program_map import fresh_compiles  # noqa: F401
from tests.util import base_config, random_batch, tiny_gpt2

LAYERS, D_MODEL, SEQ = 8, 32, 16
ZERO3 = {"stage": 3, "param_persistence_threshold": 0}


@pytest.fixture(autouse=True)
def _isolation():
    reset_programs()
    yield
    reset_programs()


def _engine(zero, **model_kw):
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_gpt2(remat=True, num_layers=LAYERS, **model_kw),
        config=base_config(zero_optimization=zero))
    return engine


def _batch(engine, seed):
    one = random_batch(batch_size=engine.topology.dp_world_size,
                       seq_len=SEQ, seed=seed)
    return {k: v[None] for k, v in one.items()}


def _train(engine, seeds):
    return [float(engine.train_batch(batch=_batch(engine, s)))
            for s in seeds]


def _result_dims(text):
    """{instruction name: [dims of each array of its result]}"""
    out = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ", line)
        if m:
            out[m.group(1)] = [
                tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"[a-z]+\d+\[([0-9,]*)\]", m.group(2))]
    return out


def test_layer_loops_gather_one_layer_per_leaf(devices8, fresh_compiles):
    engine = _engine(ZERO3)
    batch = _batch(engine, 0)
    engine.train_batch(batch=batch)
    blocks = engine.state["params"]["blocks"]
    specs = engine.param_specs["blocks"]
    sharded = [k for k in blocks if tuple(specs[k])]
    assert len(sharded) == len(blocks) == 12      # threshold 0: every leaf
    for key in blocks:
        assert tuple(specs[key])[0] is None, (key, specs[key])
        assert engine.grad_specs["blocks"][key] == specs[key]

    table = get_program_map("train/step")
    dims = _result_dims(engine.compile_train_step(batch).as_text())
    leaf_shapes = {tuple(v.shape[1:]) for v in blocks.values()}
    B = engine.topology.dp_world_size
    per_loop = {}
    for name, row in table.items():
        if not (in_layer_loop(row) and row["wire_bytes"] is not None):
            continue
        for shape in dims[name]:
            # nothing of an activation's shape crosses the devices inside a
            # layer loop: the partitioner gathers weights, as told
            assert shape[:2] not in ((B, SEQ), (1, SEQ)), (name, row, shape)
        if row["collective"] != "all-gather":
            continue
        # one layer's leaf, whole: no layer dim above 1
        (shape,) = dims[name]
        assert shape in leaf_shapes or (
            shape[0] == 1 and shape[1:] in leaf_shapes), (name, shape)
        per_loop[row["phase"]] = per_loop.get(row["phase"], 0) + 1
    # the forward loop gathers every sharded leaf once; so does the
    # backward loop, whose recompute shares its gather with the backward
    # proper — less the block's last bias, which no backward op reads
    assert per_loop == {"forward": len(sharded),
                        "recompute": len(sharded) - 1}

    counter = layer_loop_gathers("train/step")
    assert counter["rows"] == 2 * len(sharded) - 1
    assert {p: v["rows"] for p, v in counter["by_phase"].items()} == per_loop
    layer_bytes = sum(v.nbytes // LAYERS for v in blocks.values())
    largest = max(v.nbytes // LAYERS for v in blocks.values())
    assert counter["max_wire_bytes"] == largest * 7 // 8      # ring, n=8
    assert counter["by_phase"]["forward"]["wire_bytes"] \
        == layer_bytes * 7 // 8
    assert counter["wire_bytes_per_iteration"] == (
        2 * layer_bytes - blocks["mlp_out_b"].nbytes // LAYERS) * 7 // 8
    assert layer_loop_gathers("no/such/program") is None


def test_nothing_engages_without_a_zero_sharded_stack(devices8,
                                                      fresh_compiles):
    for zero in ({"stage": 2}, {"stage": 0}):
        engine = _engine(zero)
        engine.train_batch(batch=_batch(engine, 0))
        assert layer_loop_gathers("train/step")["rows"] == 0, zero
        reset_programs()


def test_losses_match_plain_data_parallelism(devices8):
    want = _train(_engine({"stage": 0}), seeds=(0, 1, 2))
    got = _train(_engine(ZERO3), seeds=(0, 1, 2))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_checkpoint_of_the_old_layout_loads(devices8, tmp_path, monkeypatch):
    """The storage sharding of stacked leaves changed, the global arrays
    did not: a checkpoint written with the ZeRO axes on the layer axis
    (the policy told of no stacked subtree) loads into today's layout and
    training continues with the same losses."""
    with monkeypatch.context() as m:
        m.setattr(engine_module, "ZeroShardingPolicy",
                  lambda **kw: ZeroShardingPolicy(
                      **{**kw, "stacked_key": None}))
        old = _engine(ZERO3)
    assert tuple(old.param_specs["blocks"]["qkv_w"])[0] is not None
    _train(old, seeds=(0, 1))
    old.save_checkpoint(str(tmp_path / "ck"))
    want = _train(old, seeds=(2, 3))

    new = _engine(ZERO3)
    assert tuple(new.param_specs["blocks"]["qkv_w"])[0] is None
    new.load_checkpoint(str(tmp_path / "ck"))
    got = _train(new, seeds=(2, 3))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stage", [1, 2])
def test_lower_stages_keep_state_off_the_layer_axis(stage, devices8):
    """One rule: under stage 1/2 the gradients and the optimizer state of
    the stack leave the layer axis too, and the losses stay those of
    stage 0."""
    engine = _engine({"stage": stage})
    if stage >= 2:
        for spec in jax.tree.leaves(engine.grad_specs["blocks"],
                                    is_leaf=lambda x: isinstance(x, P)):
            assert tuple(spec) and tuple(spec)[0] is None, spec
    moments = [leaf for leaf in jax.tree.leaves(engine.state["opt_state"])
               if leaf.ndim == 3 and leaf.shape[0] == LAYERS]
    assert moments
    for leaf in moments:
        assert tuple(leaf.sharding.spec)[0] is None, leaf.sharding
        assert len({str(s.index) for s in leaf.addressable_shards}) == 8
    got = _train(engine, seeds=(0, 1, 2))
    want = _train(_engine({"stage": 0}), seeds=(0, 1, 2))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
