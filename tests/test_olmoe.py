"""OLMoE through the normal path at toy size on the CPU, against the plain
reference the benchmark uses (benchmarks/references/olmoe.py — this file
imports that same file, there is no second copy).

``DS_GGEMM_INTERPRET=1`` runs the real grouped GEMM kernels (forward, dx,
dw) in Pallas' interpreter, so the dropless training path is the one
compared.  Everything is float32 with seeded weights: the two sides differ
only in the order of summation, so the tolerances are tight enough that
leaving out any one of OLMoE's departures from Mixtral — or computing in a
lower precision — lands far outside.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mixtral import MIXTRAL_SIZES, mixtral_model
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.telemetry import tracing
from tests.util import base_config, scope_parts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "olmoe_reference",
    os.path.join(REPO, "benchmarks", "references", "olmoe.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

#: float32 on both sides, same weights, same batch: only the order of
#: summation differs (kernel tiles, sorted rows, blocks of the reference).
#: Measured 0 to 5e-7; each departure left out moves the loss by 1e-3 or
#: more, a bf16 product by 5e-5.
LOSS_TOL = 2e-5
#: a gradient leaf against the reference's, as max |a - b| / max |b|
#: (measured 2e-7 to 8e-7)
GRAD_TOL = 2e-5

TOY = dict(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=64,
           num_experts=4, top_k=2, vocab_size=512, max_seq_len=128,
           dtype="float32", moe_dispatch="grouped", remat=True)
GAS, B, S, DOCS = 2, 2, 64, 3


@pytest.fixture(autouse=True)
def _real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return mixtral_model("olmoe-1b-7b", **{**TOY, **overrides})


def sizes_of(model):
    c = model.config
    return {k: getattr(c, k) for k in (
        "num_heads", "num_kv_heads", "head_dim", "num_experts", "top_k",
        "rms_norm_eps", "rope_theta", "aux_loss_coef",
        "router_z_loss_coef")}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm scales away from
    one, router logits wide and expert weights large enough that
    renormalising the chosen gates, or not, shows in the loss."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    blocks = dict(params["blocks"])
    for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
        key, sub = jax.random.split(key)
        blocks[name] = blocks[name] + 0.3 * jax.random.normal(
            sub, blocks[name].shape)
    blocks["moe"] = {name: w * (20.0 if name == "router" else 4.0)
                     for name, w in blocks["moe"].items()}
    return {**params, "blocks": blocks}


def packed_batch(seed=0, gas=GAS):
    """[gas, B, S] token ids with DOCS documents in every sequence."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(gas, B, S),
                       dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(gas, B, DOCS - 1)), axis=-1)
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


def reference_loss(params, mb, sizes):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=32)


def test_engine_first_step_loss_matches_the_reference():
    model = toy_model()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3),
        mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",)))
    # the engine's own (seeded) weights, pushed to where every part counts
    start = seeded_params(model)
    engine.state["params"] = jax.tree.map(
        lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding),
        start, engine.state["params"])
    batch = packed_batch()
    want = reference.step_loss(start, batch, sizes_of(model), chunk=1)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)


def test_gradients_match_the_reference():
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        # one compile a side where the eager form dispatches op by op
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, sizes=sizes_of(model))))(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst


#: OLMoE's departures from Mixtral, each left out of the model in turn
#: (the reference keeps all five): the loss then has to leave the tolerance
DEPARTURES = {
    "qk_norm": dict(qk_norm=False),
    "unnormalised_gates": dict(norm_topk_prob=True),
    "all_choice_load_balance": dict(load_balance="first_choice"),
    "z_loss": dict(router_z_loss_coef=0.0),
    "document_mask": {},
}


@pytest.mark.parametrize("left_out", sorted(DEPARTURES))
def test_a_departure_left_out_is_outside_the_tolerance(left_out):
    full = toy_model()
    params, mb = seeded_params(full), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        want = float(reference_loss(params, mb, sizes_of(full)))
        assert abs(float(full.loss(params, mb)) - want) < LOSS_TOL
        if left_out == "document_mask":
            mb = {"input_ids": mb["input_ids"]}
        got = float(toy_model(**DEPARTURES[left_out]).loss(params, mb))
    assert abs(got - want) > 10 * LOSS_TOL, (left_out, got, want)


def test_a_lower_precision_is_outside_the_tolerance():
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        want = float(reference_loss(params, mb, sizes_of(model)))
        low = float(reference.micro_batch_loss(
            params, mb["input_ids"], mb["segment_ids"], sizes_of(model),
            block=32, matmul_dtype=jnp.bfloat16))
    assert abs(low - want) > LOSS_TOL, (low, want)


def test_skewed_routing_einsum_drops_tokens_grouped_does_not():
    """Every token's first two choices are experts 0 and 1: the capacity
    formulation (capacity 1.25 x tokens x 2 / 4) keeps five eighths of
    each expert's rows and drops the rest; the grouped path computes them
    all and matches the reference, which has no capacity."""
    model = toy_model()
    params = seeded_params(model)
    # one input feature is the same large number in every token, and the
    # router reads the choice off it
    params["wte"] = params["wte"].at[:, 0].set(4.0)
    router = params["blocks"]["moe"]["router"] * 0.05
    params["blocks"]["moe"]["router"] = \
        router.at[:, 0, 0].set(3.0).at[:, 0, 1].set(2.0)
    mb = micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        want = float(reference_loss(params, mb, sizes_of(model)))
        grouped = float(model.loss(params, mb))
        einsum = float(toy_model(moe_dispatch="einsum").loss(params, mb))
    assert abs(grouped - want) < LOSS_TOL, (grouped, want)
    assert abs(einsum - want) > 10 * LOSS_TOL, (einsum, want)


def _toy_step(gas=GAS):
    from jax.experimental.compilation_cache import compilation_cache
    # scopes are debug info, which the compile cache's key leaves out: an
    # executable an older tree cached would come back with that tree's
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=toy_model(), config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=gas),
            mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]),
                                   ("data",)))
        engine.train_batch(batch=packed_batch(gas=gas))
        return tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_scopes_kernel_names_and_row_counts_of_a_toy_step():
    table = _toy_step()
    scopes = [row["scope"] or "" for row in table.values()]
    # off the TPU a kernel is not a custom call, but its name= is still a
    # scope of the instructions the interpreter makes of it
    for name in ("ds.embed", "ds.head_loss", "ds.block/attn",
                 "ds.block/mlp/router", "ds.block/mlp/dispatch",
                 "ds.block/mlp/experts", "ds.block/mlp/combine",
                 "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    assert {"router", "dispatch", "experts", "combine", "ds_ggemm_fwd",
            "ds_ggemm_dx", "ds_ggemm_dw"} <= scope_parts(scopes)
    for phase in ("forward", "recompute", "backward"):
        assert any(row["phase"] == phase and "/experts/" in row["scope"]
                   for row in table.values() if row["scope"]), phase
    # the rows' movement, hand-written backward included, stays under the
    # scopes that moe.dispatch_ms_per_step reads (the recompute needs no
    # combined output, only what its backward gathers from) ...
    for part, phases in (("dispatch", ("forward", "recompute", "backward")),
                         ("combine", ("forward", "backward"))):
        for phase in phases:
            assert any(row["phase"] == phase
                       and f"/mlp/{part}/" in row["scope"]
                       for row in table.values() if row["scope"]), \
                (part, phase)
    # ... and none of it is a scatter
    assert not any("scatter" in s for s in scopes
                   if "/mlp/dispatch/" in s or "/mlp/combine/" in s)
    # the step's own account: B*S tokens x 2 choices, padded per expert
    rows = tracing.grouped_gemm_rows("train/step")
    bm = 128
    assert rows["routed_rows_per_call"] == B * S * 2
    assert rows["padded_rows_per_call"] == -(-B * S * 2 // bm) * bm + 4 * bm
    # ... and how its grouped kernels are tiled: forward, dx and dw (here
    # gate / up and down have one shape), an expert's weight panel
    # fetched once per call
    D = TOY["d_model"]
    assert sorted((c["kernel"], c["k"], c["n"]) for c in rows["calls"]) \
        == [(name, D, D) for name in
            ("ds_ggemm_dw", "ds_ggemm_dx", "ds_ggemm_fwd")]
    for call in rows["calls"]:
        assert call["regime"] == "resident"
        assert call["weight_bytes_per_call"] in (4 * D * D * 2,
                                                 4 * D * D * 4)
        assert call["operand_bytes_per_call"] > 0
    # no host callback anywhere in the step
    assert not any("callback" in s for s in scopes)


@pytest.mark.parametrize("experts", ["kernels", "ragged_dot"])
def test_no_scatter_in_the_gradient_of_the_grouped_layer(experts,
                                                         monkeypatch):
    """The counter of the gathers-only row movement, read off the jaxpr:
    jax.grad through the routed-expert layer in grouped mode — router,
    plan, dispatch, experts (interpreted kernels or the reference),
    combine — holds no scatter primitive of any kind."""
    from deepspeed_tpu.telemetry.costmodel import primitive_names
    monkeypatch.setenv("DS_GGEMM_INTERPRET",
                       "1" if experts == "kernels" else "0")
    config = moe_layer.MoEConfig(
        d_model=16, d_ff=32, num_experts=8, top_k=2,
        dispatch_mode="grouped", norm_topk_prob=False,
        load_balance="all_choices", z_loss_coef=0.001)
    params = moe_layer.init_moe_params(config, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))

    def loss(params, x):
        out, aux = moe_layer.moe_layer(params, x, config, train=True)
        return jnp.sum(out ** 2) + aux

    names = primitive_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))
    assert ("pallas_call" in names) == (experts == "kernels")
    assert "gather" in names and "sort" in names
    assert not [n for n in names if "scatter" in n]


ROUTING_CASES = {
    "reference_form": dict(),
    "unnormalised": dict(normalize=False),
    "all_choices": dict(load_balance="all_choices"),
    "olmoe": dict(normalize=False, load_balance="all_choices"),
}


@pytest.mark.parametrize("case", sorted(ROUTING_CASES))
def test_topk_routing_options(case):
    options = ROUTING_CASES[case]
    T, E, k = 32, 8, 3
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E)) * 3.0
    probs = jax.nn.softmax(logits, axis=-1)
    routing = topk_routing(logits, k, **options)
    base = topk_routing(logits, k)
    np.testing.assert_array_equal(routing.expert_idx, base.expert_idx)
    chosen = jnp.take_along_axis(probs, routing.expert_idx, axis=1)
    if options.get("normalize", True):
        want = chosen / chosen.sum(-1, keepdims=True)
    else:
        want = chosen
    np.testing.assert_allclose(routing.gate_weights, want, rtol=1e-6)
    counts = jax.nn.one_hot(routing.expert_idx, E).sum((0, 1))
    if options.get("load_balance") == "all_choices":
        f = counts / T                        # sums to k
    else:
        f = jax.nn.one_hot(routing.expert_idx[:, 0], E).mean(0)
    np.testing.assert_allclose(
        routing.l_aux, E * jnp.sum(f * probs.mean(0)), rtol=1e-6)


def test_the_size_is_the_published_one():
    size = MIXTRAL_SIZES["olmoe-1b-7b"]
    assert (size["num_layers"], size["d_model"], size["num_heads"],
            size["num_kv_heads"], size["d_ff"], size["num_experts"],
            size["top_k"], size["vocab_size"], size["max_seq_len"]) == \
        (16, 2048, 16, 16, 1024, 64, 8, 50304, 4096)
    # what one chip trains: 2 of the 16 layers (benchmarks/configs)
    assert mixtral_model("olmoe-1b-7b", num_layers=2).meta["n_params"] \
        == 1_045_186_560
    with pytest.raises(ValueError):
        topk_routing(jnp.zeros((4, 4)), 2, load_balance="every_other")
