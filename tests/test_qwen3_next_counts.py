"""Qwen3-Next's toy engine (tests/test_qwen3_next.py: the same sizes, seeded
weights and packed batch) beside the loss: the count of rows over a
share's bound leaves the fused step with it, and the scopes and accounts
of a toy step.  The first step's loss under the three ZeRO stages is
tests/test_qwen3_next_engine.py; a file of its own so that ``--dist
loadfile`` gives the family's engines to two workers."""
import jax

import deepspeed_tpu
from deepspeed_tpu.telemetry import tracing

from tests.test_qwen3_next import (  # noqa: F401 (the fixtures come by name)
    B, GAS, S, TOY, micro, one_device, packed_batch, real_kernels,
    toy_model)
from tests.util import base_config, scope_parts


def _counting_engine():
    engine, *_ = deepspeed_tpu.initialize(
        model=toy_model(), config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3), mesh=one_device())
    return engine


def test_the_engine_counts_and_warns_of_rows_over_the_bound(monkeypatch):
    """The count leaves the fused step beside the loss, summed over the
    micro-batches, with no host callback; the engine adds it up, counts it
    in its registry and warns — and says nothing of a plan long enough."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    from deepspeed_tpu.utils.logging import logger
    warnings = []
    monkeypatch.setattr(logger, "warning", warnings.append)
    batch = packed_batch()
    engine = _counting_engine()
    engine.train_batch(batch=batch)
    assert engine.step_counts() == {"moe/rows_over_bound": 0}
    # ... and the router's load left the step beside it
    load = engine.step_load()["totals"]
    assert load["moe/routed_rows"] > 0 and load["moe/even_rows"] > 0
    assert not [w for w in warnings if "rows_over_bound" in w]

    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    engine = _counting_engine()
    model, params = engine.model, engine.state["params"]
    counted = jax.jit(model.loss_with_counts_fn)    # one trace, GAS calls
    want = sum(int(counted(params, micro(batch, g))[1]["moe/rows_over_bound"])
               for g in range(GAS))
    counter = lambda: engine.telemetry_registry.get_counter(
        "train/step_counts", count="moe/rows_over_bound")
    before = counter()
    assert "callback" not in engine.compile_train_step(batch).as_text()
    engine.train_batch(batch=batch)
    assert int(engine.last_metrics["counts"]["moe/rows_over_bound"]) == want
    assert engine.step_counts() == {"moe/rows_over_bound": want} and want > 0
    assert counter() == before + want
    said = [w for w in warnings if "rows_over_bound" in w]
    assert len(said) == 1 and f"= {want}" in said[0] \
        and "held_rows_bound" in said[0] and "train step 1" in said[0]
    # the micro-step API does not carry the count, and says so
    engine.forward(micro(batch))
    assert any("only the fused train step" in w for w in warnings)


def test_scopes_and_counts_of_a_toy_step():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=toy_model(), config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=GAS), mesh=one_device())
        engine.train_batch(batch=packed_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for name in ("ds.embed", "ds.head_loss", "ds.block/attn",
                 "ds.block/linear_attn/in_proj", "ds.block/linear_attn/conv",
                 "ds.block/linear_attn/delta_rule",
                 "ds.block/linear_attn/gate_norm",
                 "ds.block/linear_attn/out_proj", "ds.block/mlp/router",
                 "ds.block/mlp/dispatch", "ds.block/mlp/experts",
                 "ds.block/mlp/combine", "ds.block/mlp/shared_expert",
                 "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    for phase in ("forward", "recompute", "backward"):
        assert any(row["phase"] == phase
                   and "/linear_attn/delta_rule/" in row["scope"]
                   for row in table.values() if row["scope"]), phase
    assert scope_parts(scopes) >= {
        "linear_attn", "in_proj", "conv", "delta_rule", "gate_norm",
        "out_proj", "shared_expert"}
    rows = tracing.grouped_gemm_rows("train/step")
    T, k = B * S, TOY["top_k"]
    bound = -(-(2 * T * k * 4 // 16) // 128) * 128
    assert rows["held_rows_bound"] == bound
    assert rows["padded_rows_per_call"] == bound + 4 * 128
    assert rows["routed_rows_per_call"] == T * k * 4 // 16
    assert (rows["experts_held"], rows["experts_routed"]) == (4, 16)
    assert tracing.delta_rule_chunks("train/step") == [
        {"chunks": -(-S // 16), "chunk_len": 16, "batch": B, "heads": 4,
         "dk": 16, "dv": 16, "decay": "head", "path": "xla"}]
