"""Ouro's toy model (tests/test_ouro.py: the same sizes, seeded weights,
packed batch and reference) through the engine: ``initialize`` ->
``train_batch`` with the first step's loss against the plain reference and
the loss falling over three steps, the four exit masses that leave the
compiled step beside the loss, the scopes and accounts of a toy step.  A
file of its own so that ``--dist loadfile`` gives the family's tests to two
workers."""
import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import ouro
from deepspeed_tpu.telemetry import tracing
from tests.test_ouro import (B, DOCS, LOSS_TOL, S, packed_batch, reference,
                             sizes_of, toy, toy_model)
from tests.util import base_config, scope_parts

GAS = 2


def _engine(model, **config):
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3, **config),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",)))
    return engine


def _start_at(engine, params):
    # a copy: the step donates what it is given, and the weights are
    # every test's
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        jax.tree.map(jnp.copy, params), engine.state["params"])


def _batch():
    """Two micro-batches [GAS, B, S]."""
    return {k: np.stack([np.asarray(packed_batch(seed)[k])
                         for seed in range(GAS)])
            for k in ("input_ids", "segment_ids")}


def test_three_engine_steps_from_the_references_loss_downwards():
    model, start, _, _, _ = toy()
    engine = _engine(model, zero_optimization={"stage": 2}, optimizer={
        "type": "AdamW", "params": {"lr": 3e-3}})
    _start_at(engine, start)
    batch = _batch()
    want = reference.step_loss(start, batch, sizes_of(model), chunk=1)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    assert abs(losses[0] - want) < LOSS_TOL, (losses[0], want)
    assert losses[2] < losses[1] < losses[0] - 0.02, losses
    # nothing is left out of the loss: no count, and no warning to give
    assert engine.step_counts() == {}
    # ... and where the tokens leave left the step beside it
    load = engine.step_load()
    assert load["steps"] == 3 and len(load["last"]) == 3
    scored = GAS * (B * S - B * DOCS)
    for step in load["last"]:
        masses = [step[ouro.exit_mass_name(t)] for t in (1, 2, 3, 4)]
        assert min(masses) > 0
        # each mass is rounded on its own, a micro-batch at a time
        assert abs(sum(masses) - scored) <= 4 * GAS
        assert step[ouro.SCORED_TOKENS] == scored
        expected_pass = step[ouro.EXIT_PASS_TOKENS] / step[ouro.SCORED_TOKENS]
        assert 1.0 < expected_pass < 4.0
        assert set(step) == set(ouro.STEP_LOAD)
    # the first step's are the reference's exit distribution, summed
    want_mass = np.zeros(4)
    for g in range(GAS):
        micro = {k: v[g] for k, v in batch.items()}
        _, p = reference.micro_batch_loss(
            start, jnp.asarray(micro["input_ids"]),
            jnp.asarray(micro["segment_ids"]), sizes_of(model), block=36,
            output="exits")
        _, scored_at = reference.token_losses(start, micro, sizes_of(model),
                                              chunk=1)
        want_mass += np.asarray(p)[:, scored_at].sum(-1)
    got = [load["last"][0][ouro.exit_mass_name(t)] for t in (1, 2, 3, 4)]
    assert np.abs(np.asarray(got) - want_mass).max() <= GAS
    # the registry gauges the last step's under the same names
    assert tracing.step_load("train/step")["totals"][ouro.SCORED_TOKENS] \
        == 3 * scored


def test_scopes_and_accounts_of_a_toy_step():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine = _engine(toy_model())
        engine.train_batch(batch=_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for name in ("ds.embed", "ds.head_loss", "ds.exit_gate", "ds.block/attn",
                 "ds.block/attn/rope", "ds.block/attn/scores",
                 "ds.block/attn/out_proj", "ds.block/mlp"):
        assert any(name in s for s in scopes), name
    for phase in ("forward", "recompute", "backward"):
        for part in ("/attn/scores", "/mlp/"):
            assert any(row["phase"] == phase and part in row["scope"]
                       for row in table.values() if row["scope"]), \
                (phase, part)
    # the gate and the heads are computed once: nothing of them is
    # recomputed
    for row in table.values():
        if any(s in (row["scope"] or "")
               for s in ("ds.exit_gate", "ds.head_loss")):
            assert row["phase"] in ("forward", "backward"), row
    assert scope_parts(scopes) >= {"attn", "rope", "scores", "out_proj",
                                   "mlp"}
    (loop,) = tracing.layer_loops("train/step")
    assert (loop["passes"], loop["layers"], loop["applications"]) \
        == (4, 3, 12)
    assert loop["saved_carry_bytes"] == 12 * B * S * 64 * 4     # float32
    (head,) = tracing.head_chunks("train/step")
    assert (head["name"], head["tokens"]) == ("exits", 4 * B * S)
