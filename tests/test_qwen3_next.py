"""Qwen3-Next through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/qwen3_next.py —
this file imports that same file, there is no second copy): loss and
gradients with packed documents, the share of an expert-parallel layer
(its parts add up; a row over the bound is counted), and what stays as it
was for the families whose expert layers hold every expert.

``DS_GGEMM_INTERPRET=1`` runs the real grouped GEMM kernels in Pallas'
interpreter.  Everything is float32 with seeded weights: the two sides
differ only in the order of summation and in the form of the delta rule
(chunked here, per token there)."""
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mixtral import mixtral_model
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig, count_params,
                                             qwen3_next_model)
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params
from deepspeed_tpu.telemetry import tracing
from tests.util import base_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "qwen3_next_reference",
    os.path.join(REPO, "benchmarks", "references", "qwen3_next.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 2e-6
GRAD_TOL = 5e-5         # max |a - b| / max |b| per leaf; measured <= 6e-6

TOY = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
           head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16, d_ff=32,
           shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
           expert_offset=8, vocab_size=512, max_seq_len=128,
           delta_rule_chunk=16, dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 72, 4


@pytest.fixture(autouse=True)
def _real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    monkeypatch.setattr(moe_layer, "_metrics_registry", None)
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return qwen3_next_model("80b-a3b", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits wide, gates and decays off their centre."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if name.endswith("norm"):
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("router", "shared_router", "w_ba"):
            return w * 20.0
        if name in ("w_gate", "w_in", "w_out", "shared_gate", "shared_in",
                    "shared_out", "conv_w"):
            return w * 4.0
        return w

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0, gas=GAS):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(gas, B, S),
                       dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(gas, B, DOCS - 1)), axis=-1)
    cuts[0, 0] = (15, 16, 48)     # a one-token document at a chunk's edge
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


def reference_loss(params, mb, sizes):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=36)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@pytest.mark.parametrize("stage", [0, 1, 2])
def test_engine_first_step_loss_matches_the_reference(stage):
    model = toy_model()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3,
            zero_optimization={"stage": stage}), mesh=one_device())
    start = seeded_params(model)
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    batch = packed_batch()
    want = reference.step_loss(start, batch, sizes_of(model), chunk=1)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held):
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(model.loss)(params, mb)
        want, want_grads = jax.value_and_grad(reference_loss)(
            params, mb, sizes_of(model))
    assert abs(float(loss) - float(want)) < LOSS_TOL
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    # every leaf learns, the ones the issue names among them
    lin = grads["blocks"]["linear"]
    for leaf in (lin["A_log"], lin["dt_bias"], lin["w_ba"], lin["conv_w"],
                 lin["moe"]["shared_router"], grads["blocks"]["full"]["wq"]):
        assert float(jnp.abs(leaf).max()) > 0


#: what makes this model itself, each left out of one side in turn: the
#: loss then has to leave the tolerance
@pytest.mark.parametrize("left_out", ["document_reset", "shared_expert_gate",
                                      "held_subset"])
def test_a_departure_left_out_is_outside_the_tolerance(left_out):
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    want = float(reference_loss(params, mb, sizes_of(model)))
    if left_out == "document_reset":
        # the model packed against the reference that never resets
        want = float(reference.micro_batch_loss(
            params, mb["input_ids"], None, sizes_of(model), block=36))
        got = float(model.loss(params, mb))
    elif left_out == "shared_expert_gate":
        off = jax.tree_util.tree_map_with_path(
            lambda path, w: w * 0 if path[-1].key == "shared_router" else w,
            params)
        got = float(model.loss(off, mb))
    else:
        other = toy_model(expert_offset=4)
        got = float(other.loss(params, mb))
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
    assert not [w for w in warnings if "rows_over_bound" in w]

    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    engine = _counting_engine()
    model, params = engine.model, engine.state["params"]
    want = sum(int(jax.jit(model.loss_with_counts_fn)(
        params, micro(batch, g))[1]["moe/rows_over_bound"])
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


def test_a_share_runs_through_the_grouped_dispatch_only():
    params, x = _share_setup()
    cfg = replace(SHARE, expert_offset=4, experts_held=4,
                  dispatch_mode="einsum")
    with pytest.raises(ValueError, match="grouped dispatch only"):
        moe_layer.moe_layer(_held(params, 4, 4), x, cfg)


def _lowered(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.zeros((2, 64), jnp.int32),
             "segment_ids": jnp.zeros((2, 64), jnp.int32)}
    return jax.jit(jax.value_and_grad(model.loss)).lower(
        shapes, batch).as_text()


@pytest.mark.parametrize("size,dispatch", [
    ("tiny", "einsum"), ("tiny", "grouped"), ("olmoe", "grouped")])
def test_holding_every_expert_is_the_program_it_was(size, dispatch,
                                                    monkeypatch):
    """Default MoEConfig: the Mixtral / OLMoE programs are untouched by
    the held-subset and shared-expert fields — saying "all experts, none
    shared" out loud lowers to the same text, and that text has nothing of
    the new path in it (PERF.md section 6, PR 32, has the byte comparison
    with the parent commit)."""
    kw = dict(size="tiny") if size == "tiny" else dict(
        size="olmoe-1b-7b", num_layers=2, d_model=64, num_heads=2,
        num_kv_heads=2, d_ff=64, num_experts=4, top_k=2, vocab_size=512,
        max_seq_len=128)
    model = mixtral_model(moe_dispatch=dispatch, remat=True, **kw)
    text = _lowered(model)
    explicit = type(model.config).moe.fget

    def said_out_loud(self):
        return replace(explicit(self), expert_offset=0,
                       experts_held=self.num_experts, shared_expert_d_ff=0,
                       shared_expert_gate=False)

    monkeypatch.setattr(type(model.config), "moe", property(said_out_loud))
    assert _lowered(mixtral_model(moe_dispatch=dispatch, remat=True,
                                  **kw)) == text
    assert "shared_expert" not in text


# ------------------------------------------------------- the rest of it
def test_zero3_and_streaming_refuse_clearly():
    model = toy_model()
    params, mb = model.init(jax.random.PRNGKey(0)), micro(packed_batch())
    with param_stream_scope(True, mode="gather"):
        with pytest.raises(NotImplementedError, match="ZeRO stage 0-2"):
            model.loss(params, mb)


@pytest.mark.parametrize("entry", ["init_cache_fn", "prefill_fn",
                                   "decode_fn", "verify_fn"])
def test_serving_entry_points_name_the_missing_piece(entry):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        getattr(toy_model(), entry)(None, None, None)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    whole = Qwen3NextConfig()
    assert count_params(whole) == config["published"]["n_params"]
    assert whole.pattern == ("linear", "linear", "linear", "full")
    assert whole.rotary_ndims == 64 and whole.num_periods == 12
    model = qwen3_next_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"]["linear"]["moe"]
    assert moe["router"].shape == (1, 3, 2048, 512)
    assert moe["w_in"].shape == (1, 3, 32, 2048, 512)
    with pytest.raises(ValueError, match="whole"):
        Qwen3NextConfig(num_layers=6).num_periods


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
    assert set(tracing.STEP_SCOPES) >= {
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
         "dk": 16, "dv": 16, "path": "xla"}]
