"""The step is built once a start (ROADMAP S5 (d)): every leaf of
``engine.state`` is born committed to ``engine.state_shardings``, so the
first ``train_batch``, every later one and ``compile_train_step`` describe
one signature — one trace, one lowering, one executable.  Held by the
set-up account's rows (telemetry/tracing.py), per ZeRO stage, with and
without the dynamic loss scaler, after a restore and in the step described
from shapes; tests/test_state_placement_writers.py holds it on the
four-device meshes, after an overflow and through the host-side writers of
the counter and the scaler (two files: each stays under a minute of a
loaded worker).  Toy engines on the CPU: counts, never seconds."""
import collections
import functools

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.telemetry import get_registry, tracing
from util import base_config, random_batch, tiny_gpt2

FP16 = {"enabled": True, "initial_scale_power": 8}


@pytest.fixture(autouse=True)
def account():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_engine(model=None, on=None, **config):
    """``on``: the device mesh, where the default (all eight) is not
    wanted; ``config`` may hold the config's own ``mesh`` group."""
    engine, *_ = deepspeed_tpu.initialize(
        model=model or tiny_gpt2(remat=True), config=base_config(**config),
        mesh=on)
    return engine


def batch_of(engine):
    one = random_batch(batch_size=engine.topology.dp_world_size, seq_len=16)
    return {k: np.stack([v]) for k, v in one.items()}


def unplaced(engine):
    """The leaves of ``engine.state`` that are not committed arrays on the
    sharding ``engine.state_shardings`` names for them."""
    leaves = jax.tree_util.tree_leaves_with_path(engine.state)
    shardings = jax.tree.leaves(engine.state_shardings)
    assert len(leaves) == len(shardings)
    return [jax.tree_util.keystr(path)
            for (path, leaf), sharding in zip(leaves, shardings)
            if not (isinstance(leaf, jax.Array) and leaf.committed
                    and leaf.sharding == sharding)]


def built(step_from=0, **where):
    """Rows of the engine's own programs: what was traced, lowered,
    compiled or loaded at step ``step_from`` or later."""
    return [(r["program"], r["stage"], r["step"], r["cause"])
            for r in tracing.setup_account()["rows"]
            if r["program"] != "other" and r["step"] >= step_from
            and all(r[k] == v for k, v in where.items())]


def recompiles():
    return get_registry().get_counter("compile/recompiles")


def stage_config(stage, fp16):
    zero = {"stage": stage}
    if stage == 3:
        zero["param_persistence_threshold"] = 0
    return dict(zero_optimization=zero, **({"fp16": FP16} if fp16 else {}))


PAIRS = {f"zero{stage}_{'fp16' if fp16 else 'fp32'}": (stage, fp16)
         for stage in range(4) for fp16 in (False, True)}
#: name -> a toy engine: the eight (stage, scaler) pairs on all eight
#: devices (tests/test_state_placement_writers.py adds the two
#: four-device meshes)
ENGINES = {name: functools.partial(toy_engine, **stage_config(*pair))
           for name, pair in PAIRS.items()}
RESTORED = ["zero0_fp32", "zero2_fp16", "zero3_fp32"]

Start = collections.namedtuple(
    "Start", "engine batch at_init after_steps rows_after_first recompiled "
             "stages")


@functools.lru_cache(maxsize=None)
def start_of(name):
    """One engine a name: what it looked like after ``initialize`` and
    what three steps added to the account.  The tests of a name read one
    start (an engine and its three steps are seconds of a loaded worker),
    and the later sections step its engine on."""
    tracing.reset_programs()
    count0 = recompiles()
    engine = ENGINES[name]()
    at_init = unplaced(engine)
    batch = batch_of(engine)
    for _ in range(3):
        engine.train_batch(batch=batch)
    return Start(engine, batch, at_init, unplaced(engine),
                 built(step_from=1), recompiles() - count0,
                 [stage for _, stage, *_ in built(program="train_step")])


# ------------------------------------------------- born where it belongs
@pytest.mark.parametrize("name", list(PAIRS))
def test_every_leaf_of_the_state_is_born_placed(name, devices8):
    start = start_of(name)
    assert start.at_init == []
    assert start.after_steps == []


def built_once_and_nothing_later(start):
    # the first call's trace, lowering and executable, and nothing at
    # step 1 or later (the cost report's walk is its asker's)
    assert start.rows_after_first == []
    assert start.recompiled == 0
    assert start.stages.count("trace") == 1
    assert start.stages.count("lower") == 1
    assert sum(start.stages.count(s) for s in ("compile", "cache_load")) == 1


@pytest.mark.parametrize("name", list(PAIRS))
def test_later_steps_build_nothing(name, devices8):
    built_once_and_nothing_later(start_of(name))


# ------------------------------------------------------------ a restore
@pytest.mark.parametrize("name", RESTORED)
def test_a_restored_engine_compiles_its_step_once(name, tmp_path, devices8):
    saved = start_of(name)
    saved.engine.save_checkpoint(str(tmp_path))
    at = int(saved.engine.state["step"])
    tracing.reset_programs()
    engine = ENGINES[name]()
    path, _ = engine.load_checkpoint(str(tmp_path))
    assert path is not None
    assert unplaced(engine) == []
    assert int(engine.state["step"]) == at
    engine.train_batch(batch=saved.batch)
    engine.train_batch(batch=saved.batch)
    assert unplaced(engine) == []
    # the restored engine's first call is the run's step ``at``
    stages = [stage for _, stage, *_ in built(program="train_step")]
    assert stages.count("lower") == 1
    assert sum(stages.count(s) for s in ("compile", "cache_load")) == 1
    assert {step for *_, step, _ in built()} == {at}


# ------------------------------------------- the step described from shapes
@pytest.mark.parametrize("name", RESTORED)
def test_compile_train_step_describes_the_call_that_ran(name, devices8):
    """The abstract state ``compile_train_step`` describes is the
    signature of the live calls: jit's caches serve it (this jax reports
    the cached trace as an event of microseconds; nothing is lowered,
    compiled or loaded for it), and its text is the text of the
    executable the next ``train_batch`` runs."""
    engine, batch = start_of(name)[:2]
    tracing.reset_programs()
    described = engine.compile_train_step(batch).as_text()
    step = engine._compiled["train_step"]
    ran = []

    def spy(state, sharded, rng):
        # lowered from the live arguments, before the call donates them
        ran.append(step.lower(state, sharded, rng).compile().as_text())
        return step(state, sharded, rng)
    engine._compiled["train_step"] = spy
    try:
        engine.train_batch(batch=batch)
    finally:
        engine._compiled["train_step"] = step
    assert len(ran) == 1
    assert ran[0].splitlines() == described.splitlines()
    # neither the description nor the live arguments built anything (the
    # reset took the engine's names with it: the rows are ``other``'s)
    rows = tracing.setup_account()["rows"]
    assert {r["cause"] for r in rows} \
        == {tracing.SPAN_COMPILE_AOT, tracing.SPAN_FUSED_STEP}
    assert {r["stage"] for r in rows} == {"trace"}
