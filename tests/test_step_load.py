"""What the router did to a step leaves the compiled train step beside
``moe/rows_over_bound``: ``engine.step_load()`` against the registry tap's
readings of a forward on the same weights (the two sinks of one record,
``moe/layer.py step_load``), a router bent on purpose, the compiled step's
text and collectives, and the benchmark's reader over a hand-made account.
Toy engines of the families' own test files only."""
import collections
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.moe import layer as moe
from deepspeed_tpu.telemetry import tracing
from tests import test_mellum as mellum_toy
from tests import test_nemotron_h as nemotron_toy
from tests.util import base_config

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")


def _from_file(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reader = _from_file("step_load_reader", "layer_metrics", "readers",
                    "step_load.py")
by_hand = _from_file("step_load_by_hand", "tests",
                     "test_step_load_readers.py")


@pytest.fixture(autouse=True)
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    tracing.reset_programs()
    yield
    moe.set_moe_metrics_registry(None)
    tracing.reset_programs()


class _Tap:
    """The registry tap of ``moe/layer.py`` as sums: every gauge a layer
    sets and every counter it adds to, added up by name."""

    def __init__(self):
        self.sums = collections.Counter()

    def set_gauge(self, name, value, **labels):
        self.sums[name] += value

    inc = set_gauge


def _tapped_forward(model, params, micro_batches):
    """{name: sum over the expert layer-calls of ``model.apply`` on each of
    ``micro_batches``} as the tap hears them, by callback."""
    tap = _Tap()
    moe.set_moe_metrics_registry(tap)
    try:
        forward = jax.jit(lambda p, b: model.apply(p, b))
        for mb in micro_batches:
            jax.block_until_ready(forward(params, mb))
        jax.effects_barrier()
    finally:
        moe.set_moe_metrics_registry(None)
    return tap.sums


def _nemotron_engine(start):
    engine, *_ = deepspeed_tpu.initialize(
        model=nemotron_toy.toy_model(), config=base_config(
            train_micro_batch_size_per_gpu=nemotron_toy.B,
            gradient_accumulation_steps=nemotron_toy.GAS, seed=3),
        mesh=nemotron_toy.one_device())
    # a copy: the step donates what it is given
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        jax.tree.map(jnp.copy, start), engine.state["params"])
    return engine


def _micros(toy, batch):
    return [toy.micro(batch, g) for g in range(toy.GAS)]


def test_the_steps_load_is_what_the_tap_reads_of_the_same_forward(
        monkeypatch):
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    engine = _nemotron_engine(nemotron_toy.toy()[1])
    tapped = []
    for step in range(2):
        batch = nemotron_toy.packed_batch(step)
        params = jax.tree.map(jnp.copy, engine.state["params"])
        engine.train_batch(batch=batch)
        tapped.append(_tapped_forward(engine.model, params,
                                      _micros(nemotron_toy, batch)))
    load = engine.step_load()
    assert load["steps"] == 2 and len(load["last"]) == 2
    for step, tap in zip(load["last"], tapped):
        assert step[moe.HELD_LIVE_ROWS] == tap[moe.HELD_LIVE_ROWS] > 0
        assert step[moe.HELD_PLAN_ROWS] == tap[moe.HELD_PLAN_ROWS]
        assert step[moe.ROUTED_ROWS] == tap["moe/dispatch_tokens"] \
            + tap["moe/dropped_tokens"] > 0
        # two expert layers, GAS micro-batches, 4 of 16 experts held
        routed = nemotron_toy.B * nemotron_toy.S * 4
        assert step[moe.EVEN_ROWS] == 2 * nemotron_toy.GAS * routed // 4
        assert step[moe.EVEN_EXPERT_ROWS] \
            == 2 * nemotron_toy.GAS * routed // 16
        assert step[moe.FULLEST_EXPERT_ROWS] * 4 >= step[moe.ROUTED_ROWS]
        # no exchange here: the path reports nothing it has not
        assert not [name for name in step if "exchange" in name
                    or "chip" in name]
    assert load["totals"] == {name: sum(step[name] for step in load["last"])
                              for name in load["last"][0]}
    # ... as the registry's gauges of the last resolved step
    assert engine.telemetry_registry.get_gauge(moe.HELD_LIVE_ROWS) \
        == load["last"][-1][moe.HELD_LIVE_ROWS]
    assert tracing.step_load("train/step") == load
    # never a count: the one key it had
    assert engine.step_counts() == {"moe/rows_over_bound": 0}


def test_a_bent_router_moves_the_data_and_leaves_the_shapes(monkeypatch):
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.utils.logging import logger
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    warnings = []
    monkeypatch.setattr(logger, "warning", warnings.append)
    start = nemotron_toy.toy()[1]
    bias = start["blocks"]["experts"]["moe"]["e_score_correction_bias"]
    # every token of every expert layer chooses expert 9, held here
    bent = jax.tree_util.tree_map_with_path(
        lambda path, w: w.at[..., 9].add(5.0)
        if path[-1].key == "e_score_correction_bias" else w, start)
    assert bias.shape[-1] == 16
    loads = []
    for params in (start, bent):
        engine = _nemotron_engine(params)
        engine.train_batch(batch=nemotron_toy.packed_batch())
        loads.append(engine.step_load()["last"][0])
    even, hot = loads
    tokens = 2 * nemotron_toy.GAS * nemotron_toy.B * nemotron_toy.S
    assert hot[moe.FULLEST_EXPERT_ROWS] == tokens \
        > even[moe.FULLEST_EXPERT_ROWS]
    assert hot[moe.HELD_LIVE_ROWS] > even[moe.HELD_LIVE_ROWS]
    assert hot[moe.ROUTED_ROWS] > even[moe.ROUTED_ROWS]
    for shape in (moe.EVEN_ROWS, moe.EVEN_EXPERT_ROWS, moe.HELD_PLAN_ROWS):
        assert hot[shape] == even[shape]
    assert not [w for w in warnings if any(n in w for n in moe.STEP_LOAD)]


@pytest.mark.parametrize("mode, has", [
    ("einsum", {moe.HELD_PLAN_ROWS}),
    ("grouped", {moe.HELD_PLAN_ROWS, moe.HELD_LIVE_ROWS})])
def test_a_model_that_bounds_nothing_reports_what_its_path_has(mode, has):
    """Mixtral's loss leaves nothing out (``step_counts()`` stays empty);
    its load is the einsum's — the routed rows, the fullest expert's, the
    capacity's slots — or the dropless plan's, and nothing they have not."""
    from deepspeed_tpu.models.mixtral import mixtral_model
    model = mixtral_model("custom", num_layers=2, d_model=64, num_heads=2,
                          num_kv_heads=2, d_ff=64, num_experts=4, top_k=2,
                          vocab_size=128, max_seq_len=32)
    with moe.dispatch_scope(mode):
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=base_config(train_micro_batch_size_per_gpu=2),
            mesh=nemotron_toy.one_device())
        ids = np.random.default_rng(0).integers(0, 128, (1, 2, 32),
                                                dtype=np.int32)
        engine.train_batch(batch={"input_ids": ids})
    (step,) = engine.step_load()["last"]
    routed = 2 * 2 * 32 * 2         # layers x tokens x top_k
    assert set(step) == has | {moe.ROUTED_ROWS, moe.EVEN_ROWS,
                               moe.EVEN_EXPERT_ROWS, moe.FULLEST_EXPERT_ROWS}
    assert step[moe.ROUTED_ROWS] == step[moe.EVEN_ROWS] == routed
    assert routed // 4 <= step[moe.FULLEST_EXPERT_ROWS] <= routed
    assert engine.step_counts() == {}


def _collectives(table):
    return collections.Counter(
        row["collective"] for row in table.values() if row["collective"])


def test_nothing_calls_the_host_and_the_exchange_keeps_its_collectives():
    """Four CPU devices, the experts spread over them: the compiled step
    has no host callback and the collectives it had (pinned here by kind:
    the parent's counts), and the fullest chip's rows are what numpy makes
    of the router's own table."""
    model = mellum_toy.toy_model()
    four = jax.sharding.Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=mellum_toy.B // 4,
            gradient_accumulation_steps=mellum_toy.GAS, seed=3,
            zero_optimization={"stage": 2},
            mesh={"expert_parallel_size": 4}), mesh=four)
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        mellum_toy.seeded_params(model), engine.state["params"])
    batch = mellum_toy.packed_batch()
    params = jax.tree.map(np.asarray, engine.state["params"])
    engine.train_batch(batch=batch)
    text = engine.compile_train_step(batch).as_text()
    kinds = _collectives(tracing.parse_program_text(text))
    assert "callback" not in text
    assert kinds == PARENT_COLLECTIVES, kinds
    load = engine.step_load()["last"][0]
    # [expert layers, experts] a micro-batch: chip c holds experts 2c, 2c+1
    routed = jax.jit(model.meta["routed_rows"])
    table = np.stack([np.asarray(routed(params, mb))
                      for mb in _micros(mellum_toy, batch)])
    chips = table.reshape(table.shape[:2] + (4, -1)).sum(-1)
    assert load[moe.FULLEST_CHIP_ROWS] == chips.max(-1).sum()
    assert load[moe.MEAN_CHIP_ROWS] == (chips.sum(-1) // 4).sum()
    assert load[moe.FULLEST_CHIP_ROWS] >= load[moe.MEAN_CHIP_ROWS]
    assert load[moe.ROUTED_ROWS] == table.sum() == load[moe.EVEN_ROWS]
    # each chip's fullest expert, added over the chips as a count is
    assert load[moe.FULLEST_EXPERT_ROWS] == table.reshape(
        table.shape[:2] + (4, -1)).max(-1).sum()
    assert 0 < load[moe.EXCHANGE_WIRE_ROWS] < load[moe.EXCHANGE_ROWS_SENT] \
        == load[moe.EXCHANGE_ROWS_RECEIVED]
    assert engine.step_counts() == {"moe/rows_over_bound": 0}


#: the collectives of the toy's compiled ``train/step`` on the four-wide
#: expert axis, by kind, as ``get_program_map`` counted them at the parent
#: of the PR that made the load leave the step (7ba1725): the facts ride a
#: sum that was there
PARENT_COLLECTIVES = {"all-reduce": 12, "all-gather": 32, "all-to-all": 56}


# ------------------------------------------------- the benchmark's reader
@pytest.mark.parametrize("name, account, steps, params, want", by_hand.CASES,
                         ids=[case[0] for case in by_hand.CASES])
def test_the_reader_over_a_hand_made_account(monkeypatch, name, account,
                                             steps, params, want):
    """benchmarks/tests/test_step_load_readers.py's cases (run by hand
    there), on the reader's file as the benchmark would import it."""
    by_hand.check(reader.read, monkeypatch, account, steps, params, want)
