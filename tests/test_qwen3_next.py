"""Qwen3-Next through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/qwen3_next.py —
this file imports that same file, there is no second copy): loss and
gradients with packed documents, the share of an expert-parallel layer
(its parts add up; a row over the bound is counted), and what stays as it
was for the families whose expert layers hold every expert.

``DS_GGEMM_INTERPRET=1`` runs the real grouped GEMM kernels in Pallas'
interpreter.  Everything is float32 with seeded weights: the two sides
differ only in the order of summation and in the form of the delta rule
(chunked here, per token there).

The toy, its seeded weights, the reference's loss and gradients and the
model's own are made once a process (``functools.lru_cache``) and every
test reads them; a departure runs only the departed side.  The tests that
build an engine are ``tests/test_qwen3_next_engine.py``, so that ``--dist
loadfile`` gives the family's tests to two workers."""
import functools
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.mixtral import mixtral_model
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig, count_params,
                                             qwen3_next_model)
from deepspeed_tpu.telemetry import tracing

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
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
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


@functools.lru_cache(maxsize=None)
def toy(held="a_share"):
    """(model, seeded weights, first micro-batch, the model's jitted loss
    and gradients) of the toy that holds a share or every expert."""
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    return (model, seeded_params(model), micro(packed_batch()),
            jax.jit(jax.value_and_grad(model.loss)))


@functools.lru_cache(maxsize=None)
def reference_numbers(held="a_share"):
    """The reference's loss and gradients at :func:`toy`'s weights and
    batch."""
    model, params, mb, _ = toy(held)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, sizes=sizes_of(model))))(params, mb)


def reference_loss_without_reset():
    """The loss of the reference that never resets at a document's start,
    at the same weights and batch."""
    model, params, mb, _ = toy()
    return jax.jit(functools.partial(reference_loss, sizes=sizes_of(model)))(
        params, {"input_ids": mb["input_ids"]})


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held):
    _, params, mb, loss_and_grads = toy(held)
    with jax.default_matmul_precision("highest"):
        loss, grads = loss_and_grads(params, mb)
    want, want_grads = reference_numbers(held)
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
