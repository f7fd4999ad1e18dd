"""JoyAI-LLM-Flash through the normal path at toy size on the CPU, against
the plain reference the benchmark uses (benchmarks/references/joyai.py —
this file imports that same file, there is no second copy): loss and
gradients with packed documents (the embedding and the head carrying both
of their uses), each thing that makes the model itself left out in turn,
the share of an expert-parallel layer (its parts add up; a row over the
bound is counted), what it refuses by name, its sizes, and the scopes and
accounts of a toy step.

Where a test asks for ``real_kernels``, ``DS_GGEMM_INTERPRET=1`` runs the
real grouped GEMM kernels in Pallas' interpreter (elsewhere their jnp
form stands in: the same plan, faster to compile).  Everything is float32
with seeded weights: the two sides differ only in the order of
summation."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import joyai
from deepspeed_tpu.models.joyai import JoyAIConfig, count_params, joyai_model
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.layer import MoEConfig, init_moe_params
from deepspeed_tpu.telemetry import tracing
from tests.util import base_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "joyai_reference",
    os.path.join(REPO, "benchmarks", "references", "joyai.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 4e-6
GRAD_TOL = 1e-4         # max |a - b| / max |b| per leaf; measured <= 6e-6

TOY = dict(num_layers=3, d_model=64, num_heads=4, q_lora_rank=48,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, rope_theta=10000.0, d_ff_dense=96, d_ff=32,
           shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
           expert_offset=8, vocab_size=512, max_seq_len=128,
           dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 48, 4


@pytest.fixture(autouse=True)
def _isolation(monkeypatch):
    monkeypatch.setattr(moe_layer, "_metrics_registry", None)
    tracing.reset_programs()
    yield
    tracing.reset_programs()


@pytest.fixture
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")


def toy_model(**overrides):
    return joyai_model("llm-flash", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits and attention scores wide, the selection
    bias off its start."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if "norm" in name:
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name in ("router", "lm_head"):
            return w * 20.0
        if name == "e_score_correction_bias":
            return 0.2 * jax.random.normal(sub, w.shape)
        if name in ("w_uq", "w_ukv", "w_dkv", "w_dq"):
            return w * 12.0
        if name == "wte":
            return w
        return w * 5.0

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0, gas=GAS, docs=DOCS):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(gas, B, S),
                       dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(gas, B, docs - 1)), axis=-1)
    cuts[0, 0, :3] = (15, 16, 30)     # a one-token document
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


def reference_loss(params, mb, sizes):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=24)


def jitted_reference_loss(model, grad=False):
    """One compile where the eager form dispatches op by op."""
    fn = functools.partial(reference_loss, sizes=sizes_of(model))
    return jax.jit(jax.value_and_grad(fn) if grad else fn)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@pytest.mark.parametrize("stage", [0, 2])
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
    bias = lambda p: np.asarray(
        p["blocks"]["moe"]["e_score_correction_bias"])
    bias_was = bias(start)
    got = float(engine.train_batch(batch=batch))
    assert abs(got - want) < LOSS_TOL, (got, want)
    if stage == 2:      # a second step on the state the first one left
        assert np.isfinite(float(engine.train_batch(batch=packed_batch(1))))
    # the selection bias is a leaf the loss does not train
    assert np.abs(bias_was).max() > 0
    np.testing.assert_array_equal(bias(engine.state["params"]), bias_was)
    assert engine.step_counts() == {"moe/rows_over_bound": 0}


def _pop_biases(grads):
    return [grads["blocks"]["moe"].pop("e_score_correction_bias"),
            grads["mtp"]["block"]["moe"].pop("e_score_correction_bias")]


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held, real_kernels):
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jitted_reference_loss(model, grad=True)(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    for bias in _pop_biases(grads) + _pop_biases(want_grads):
        assert float(jnp.abs(bias).max()) == 0
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    # every other leaf learns
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)


def test_the_embedding_and_the_head_carry_both_uses():
    """One leaf each, two uses: the gradient of ``wte`` and of ``lm_head``
    is the main model's plus the module's, and neither alone."""
    model, alone = toy_model(), toy_model(num_mtp_layers=0)
    params, mb = seeded_params(model), micro(packed_batch())
    assert set(params["mtp"]) == {"norm_h", "norm_e", "w_eh", "block",
                                  "final_norm"}
    main = {k: v for k, v in params.items() if k != "mtp"}
    with jax.default_matmul_precision("highest"):
        both = jax.jit(jax.grad(model.loss))(params, mb)
        first = jax.jit(jax.grad(alone.loss))(main, mb)
        _, want = jitted_reference_loss(model, grad=True)(params, mb)
    for leaf in ("wte", "lm_head"):
        scale = float(jnp.abs(want[leaf]).max())
        assert float(jnp.abs(both[leaf] - want[leaf]).max()) \
            < GRAD_TOL * scale
        second = both[leaf] - first[leaf]
        assert float(jnp.abs(second).max()) > 0.05 * scale, leaf
        assert float(jnp.abs(first[leaf]).max()) > 0.05 * scale, leaf


# ----------------------------------------------- what makes it this model
def _with_moe(monkeypatch, **changes):
    explicit = JoyAIConfig.moe.fget
    monkeypatch.setattr(JoyAIConfig, "moe", property(
        lambda self: replace(explicit(self), **changes)))


def _attention_patched(monkeypatch, change):
    """``change(q, k, v) -> (q, k, v, kwargs)`` just before the product."""
    attend = joyai.causal_attention

    def patched(q, k, v, **kw):
        q, k, v, more = change(q, k, v)
        if "sm_scale" in more:
            q = q * (more["sm_scale"] * q.shape[-1] ** 0.5)
        return attend(q, k, v, **kw)

    monkeypatch.setattr(joyai, "causal_attention", patched)


def _scale_of_the_nope_width(monkeypatch):
    nope = TOY["qk_nope_head_dim"]
    _attention_patched(monkeypatch, lambda q, k, v: (
        q, k, v, {"sm_scale": nope ** -0.5}))


def _rotary_on_the_whole_head(monkeypatch):
    turn = joyai.rope
    nope = TOY["qk_nope_head_dim"]

    def whole(q, k, v):
        # the rotary parts are turned already: turn the rest too
        return (jnp.concatenate([turn(q[..., :nope], TOY["rope_theta"],
                                      interleaved=True), q[..., nope:]], -1),
                jnp.concatenate([turn(k[..., :nope], TOY["rope_theta"],
                                      interleaved=True), k[..., nope:]], -1),
                v, {})
    _attention_patched(monkeypatch, whole)


def _no_rotary(monkeypatch):
    monkeypatch.setattr(joyai, "rope", lambda x, *a, **kw: x)


def _a_rotary_key_per_head(monkeypatch):
    """Each head's copy of the shared key scaled by its own factor: what a
    key per head would be, where the weights give only one."""
    H = TOY["num_heads"]
    nope = TOY["qk_nope_head_dim"]
    factor = (1.0 + 0.5 * jnp.arange(H))[None, None, :, None]
    _attention_patched(monkeypatch, lambda q, k, v: (
        q, jnp.concatenate([k[..., :nope], k[..., nope:] * factor], -1), v,
        {}))


def _norm_dropped(which):
    def patch(monkeypatch):
        norm = joyai._rms_norm

        def keep_others(x, w, eps):
            width = TOY["q_lora_rank" if which == "q" else "kv_lora_rank"]
            return x if x.shape[-1] == width else norm(x, w, eps)
        monkeypatch.setattr(joyai, "_rms_norm", keep_others)
    return patch


def _bias_in_the_weights(monkeypatch):
    route = sharded_moe.topk_routing

    def biased(logits, k, *args, selection_bias=None, scale=1.0, **kw):
        routing = route(logits, k, *args, selection_bias=selection_bias,
                        scale=scale, **kw)
        picked = jnp.take_along_axis(
            jax.nn.sigmoid(logits) + selection_bias, routing.expert_idx, 1)
        return routing._replace(gate_weights=picked / jnp.sum(
            picked, axis=1, keepdims=True) * scale)

    monkeypatch.setattr(moe_layer, "topk_routing", biased)


def _dense_layer_as_an_expert_layer(monkeypatch):
    """The leading block built as the expert blocks are, with the first
    expert layer's own experts behind the leading layer's attention."""
    def hidden(params, batch, config, train=True, rng=None):
        dense = params["dense"]
        lead = {**{k: w for k, w in dense.items()
                   if k not in ("w_gate", "w_up", "w_down")},
                "moe": jax.tree.map(lambda a: a[0], params["blocks"]["moe"])}
        seg = batch.get("segment_ids")
        x = params["wte"].astype(jnp.dtype(config.dtype))[batch["input_ids"]]
        fn = joyai._expert_block_fn(config, train, rng, seg)
        x, _ = fn(x, lead)
        x, (aux, over) = jax.lax.scan(fn, x, params["blocks"])
        return x, jnp.sum(aux), jnp.sum(over)

    monkeypatch.setattr(joyai, "hidden_with_aux", hidden)


def _module_scored_against_the_next_token(monkeypatch):
    real = joyai.mtp_targets

    def next_token(batch):
        _, scored = real(batch)
        return jnp.roll(batch["input_ids"], -1, axis=1), scored
    monkeypatch.setattr(joyai, "mtp_targets", next_token)


def _module_loss_crossing_documents(monkeypatch):
    real = joyai.mtp_targets

    def crossing(batch):
        return real({"input_ids": batch["input_ids"]})
    monkeypatch.setattr(joyai, "mtp_targets", crossing)


def _module_with_its_own_embedding(monkeypatch):
    """The module reads another table than ``wte`` (the same shape, other
    numbers) for token t+1."""
    real = joyai.mtp_hidden_with_aux

    def own(params, x, batch, config, train=True, rng=None):
        other = jnp.roll(params["wte"], 7, axis=0)
        return real({**params, "wte": other}, x, batch, config, train, rng)
    monkeypatch.setattr(joyai, "mtp_hidden_with_aux", own)


#: name -> (what it does to the MODEL's side: a patch, overrides of the
#: builder).  The reference keeps the equations; the loss then has to
#: leave the tolerance.
DEPARTURES = {
    "scale_of_the_nope_width": (_scale_of_the_nope_width, {}),
    "rotary_on_the_whole_head": (_rotary_on_the_whole_head, {}),
    "no_rotary": (_no_rotary, {}),
    "a_rotary_key_per_head": (_a_rotary_key_per_head, {}),
    "no_q_latent_norm": (_norm_dropped("q"), {}),
    "no_kv_latent_norm": (_norm_dropped("kv"), {}),
    "softmax_for_sigmoid": (
        lambda mp: _with_moe(mp, router="softmax"), {}),
    "bias_added_to_the_weights": (_bias_in_the_weights, {}),
    "no_scaling_factor": (None, dict(routed_scaling_factor=1.0)),
    "no_shared_expert": (
        lambda mp: _with_moe(mp, shared_expert_d_ff=0), {}),
    "dense_layer_as_an_expert_layer": (_dense_layer_as_an_expert_layer, {}),
    "module_off": (None, dict(num_mtp_layers=0)),
    "module_scored_against_the_next_token": (
        _module_scored_against_the_next_token, {}),
    "module_loss_crossing_documents": (_module_loss_crossing_documents, {}),
    "module_with_its_own_embedding": (_module_with_its_own_embedding, {}),
}


@pytest.mark.parametrize("left_out", sorted(DEPARTURES))
def test_a_departure_left_out_is_outside_the_tolerance(left_out,
                                                       monkeypatch):
    patch, overrides = DEPARTURES[left_out]
    right = toy_model()
    # many short documents where the departure is at their boundaries
    docs = 14 if left_out == "module_loss_crossing_documents" else DOCS
    params, mb = seeded_params(right), micro(packed_batch(docs=docs))
    want = float(jitted_reference_loss(right)(params, mb))
    if patch:
        patch(monkeypatch)
    model = toy_model(**overrides)
    if left_out == "module_off":
        params = {k: v for k, v in params.items() if k != "mtp"}
    got = float(jax.jit(model.loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_with_nothing_left_out_the_same_comparison_holds():
    """The control of the test above: the same parameters and batch, no
    departure, inside the tolerance — and with the module off on both
    sides, the 40-layer kind of stack alone."""
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    want = float(jitted_reference_loss(model)(params, mb))
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL
    alone = toy_model(num_mtp_layers=0)
    main = {k: v for k, v in params.items() if k != "mtp"}
    want = float(jitted_reference_loss(alone)(main, mb))
    assert abs(float(jax.jit(alone.loss)(main, mb)) - want) < LOSS_TOL


def test_the_modules_token_losses_match_the_reference():
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    got, scored = jax.jit(model.meta["mtp_token_losses"])(params, mb)
    want, want_scored = reference.mtp_token_losses(
        params, mb, sizes_of(model), chunk=1)
    np.testing.assert_array_equal(scored, want_scored)
    seg = np.asarray(mb["segment_ids"])
    # t, t+1 and t+2 of one document, inside the sequence
    by_hand = np.zeros_like(want_scored)
    by_hand[:, :-2] = (seg[:, :-2] == seg[:, 1:-1]) & (seg[:, :-2]
                                                       == seg[:, 2:])
    np.testing.assert_array_equal(want_scored, by_hand)
    assert 0 < want_scored.sum() < want_scored.size
    np.testing.assert_allclose(np.asarray(got)[want_scored],
                               want[want_scored], atol=2e-4)
    main, main_scored = reference.token_losses(params, mb, sizes_of(model),
                                               chunk=1)
    logits = jax.jit(model.apply)(params, mb)
    nll = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.roll(mb["input_ids"], -1, 1)[..., None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(nll)[main_scored],
                               main[main_scored], atol=2e-4)


# ------------------------------------------------------- the share's sums
SHARE = MoEConfig(d_model=32, d_ff=16, num_experts=16, top_k=4,
                  dispatch_mode="grouped", load_balance="all_choices",
                  aux_loss_coef=1e-4, router="sigmoid",
                  routed_scaling_factor=2.5, activation="silu_glu",
                  shared_expert_d_ff=16)


def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out", "w_gate")
                else w) for k, w in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test on a whole expert block: the routed parts of
    all four shares (4 experts of 16 each) plus the shared expert and the
    attention counted once are the uncut block's output; the router loss
    is the same on every share."""
    cfg = JoyAIConfig(**{**{k: v for k, v in TOY.items()
                            if k not in ("experts_held", "expert_offset")}})
    layer = jax.tree.map(lambda a: a[0], seeded_params(
        toy_model(experts_held=None, expert_offset=0))["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    whole, (aux, _) = jax.jit(lambda x, layer: joyai._expert_block(
        x, layer, cfg, train=True))(x, layer)
    attended = jax.jit(lambda x, layer: joyai._latent_attention(
        x, layer, cfg, None))(x, layer)
    no_shared = JoyAIConfig.moe.fget
    h = joyai._rms_norm(attended, layer["mlp_norm"], cfg.norm_eps)
    routed_only = replace(no_shared(cfg), shared_expert_d_ff=0)
    shared = moe_layer.moe_layer(layer["moe"], h, no_shared(cfg))[0] \
        - moe_layer.moe_layer(layer["moe"], h, routed_only)[0]
    total = attended + shared
    for i in range(4):
        part_cfg = replace(routed_only, expert_offset=4 * i, experts_held=4)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(layer["moe"], 4 * i, 4), h, part_cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


def test_a_share_allocates_its_own_experts_only():
    cfg = replace(SHARE, expert_offset=4, experts_held=4)
    shapes = jax.eval_shape(lambda k: init_moe_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"router", "w_in", "w_out", "w_gate", "shared_in",
                           "shared_out", "shared_gate",
                           "e_score_correction_bias"}
    assert shapes["router"].shape == (32, 16)
    assert shapes["e_score_correction_bias"].shape == (16,)
    assert shapes["w_gate"].shape == shapes["w_in"].shape == (4, 32, 16)
    assert shapes["w_out"].shape == (4, 16, 32)
    model = toy_model()
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert tree["blocks"]["moe"]["w_in"].shape == (2, 4, 64, 32)
    assert tree["blocks"]["moe"]["router"].shape == (2, 64, 16)
    assert tree["mtp"]["block"]["moe"]["w_in"].shape == (4, 64, 32)
    assert "wte" not in tree["mtp"] and "lm_head" not in tree["mtp"]


def test_a_row_over_the_bound_is_counted(monkeypatch):
    """A plan too short for the rows the router sends here: the rest is
    counted, the model's loss comes with the sum over its expert layers,
    the module's among them, and the engine adds it up."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setattr(gg, "default_block_m", lambda: 8)
    monkeypatch.setattr(gg, "held_rows_bound", lambda *a, **k: 16)
    model = toy_model(remat=False)
    params, mb = seeded_params(model), micro(packed_batch())
    _, counts = jax.jit(model.loss_with_counts_fn)(params, mb)
    main_only = toy_model(remat=False, num_mtp_layers=0)
    _, fewer = jax.jit(main_only.loss_with_counts_fn)(
        {k: v for k, v in params.items() if k != "mtp"}, mb)
    assert int(counts["moe/rows_over_bound"]) \
        > int(fewer["moe/rows_over_bound"]) > 0
    assert "callback" not in jax.jit(model.loss).lower(params, mb).as_text()
    engine, *_ = deepspeed_tpu.initialize(
        model=toy_model(), config=base_config(
            train_micro_batch_size_per_gpu=B,
            gradient_accumulation_steps=GAS, seed=3), mesh=one_device())
    engine.train_batch(batch=packed_batch())
    assert engine.step_counts()["moe/rows_over_bound"] > 0


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
    with pytest.raises(NotImplementedError, match="absorbed form"):
        getattr(toy_model(), entry)(None, None, None)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    whole = JoyAIConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 50_190_491_648
    assert count_params(replace(whole, num_mtp_layers=0)) \
        == config["published"]["n_params_main"] == 48_942_542_592
    assert (whole.qk_head_dim, whole.v_head_dim, whole.expert_layers) \
        == (192, 128, 39)
    model = joyai_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    assert model.meta["n_params"] == 680_441_088
    # every width under the source's own key
    cut = model.config
    assert (cut.d_model, cut.num_heads, cut.q_lora_rank, cut.kv_lora_rank,
            cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim,
            cut.d_ff_dense, cut.d_ff, cut.top_k, cut.num_experts) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok")) + (
                config["published"]["n_routed_experts"],)
    assert (cut.rope_theta, cut.norm_eps, cut.routed_scaling_factor,
            cut.num_mtp_layers) == (
        config["rope_theta"], config["rms_norm_eps"],
        config["routed_scaling_factor"], config["num_nextn_predict_layers"])
    assert config["first_k_dense_replace"] == 1     # the one leading block
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"]["moe"]
    assert moe["router"].shape == (4, 2048, 256)
    assert moe["w_gate"].shape == moe["w_in"].shape == (4, 16, 2048, 768)
    assert moe["w_out"].shape == (4, 16, 768, 2048)
    assert shapes["dense"]["w_gate"].shape == (2048, 7168)
    assert shapes["blocks"]["w_uq"].shape == (4, 1536, 32 * 192)
    assert shapes["blocks"]["w_dkv"].shape == (4, 2048, 512 + 64)
    assert shapes["blocks"]["w_ukv"].shape == (4, 512, 32 * 256)
    assert shapes["mtp"]["w_eh"].shape == (4096, 2048)
    assert shapes["wte"].shape == (16160, 2048)
    with pytest.raises(ValueError, match="one leading dense layer"):
        JoyAIConfig(num_layers=1)
    with pytest.raises(ValueError, match="0 or 1"):
        JoyAIConfig(num_mtp_layers=2)


def test_no_stride_and_no_join_of_q_in_the_lowered_toy_step():
    """The lowered text of the toy step's forward and backward (tests/
    flash_step_texts.py: ``value_and_grad(loss)``, remat on), by the scope
    of each instruction: under ``ds.block/attn/rope`` and
    ``.../q_latent`` no slice has a stride other than 1, nothing gathers
    or scatters (what ``x[..., 0::2]`` is before XLA sees it, and what its
    transpose is), and no concatenate under ``q_latent`` builds a
    ``[B, S, H, nope + rot]`` array — ``q`` leaves its up-projection whole
    and turns in place (PR 45).  Every pass of the block is looked at: the
    main stack's and the module's, forward, recompute and backward."""
    import re
    model = toy_model()
    H, wide = TOY["num_heads"], (TOY["qk_nope_head_dim"]
                                 + TOY["qk_rope_head_dim"])
    mb = micro(packed_batch())
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), mb).as_text(
            debug_info=True)
    scope_of = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    seen = {"rope": 0, "q_latent": 0}
    for line in text.splitlines():
        m = re.search(r'= "?(?:stablehlo|chlo)\.(\w+).* loc\((#loc\d+)\)$',
                      line)
        scope = m and re.search(r"ds\.block/attn/(rope|q_latent)/",
                                scope_of.get(m.group(2), ""))
        if not scope:
            continue
        op, where = m.group(1), scope.group(1)
        seen[where] += 1
        assert op not in ("gather", "scatter", "dynamic_slice"), line
        if op == "slice":
            # [a:b:stride, ...]: the stride is printed where it is not 1
            assert not re.search(r"\d+:\d+:\d+", line), line
        if op == "concatenate" and where == "q_latent":
            assert f"x{H}x{wide}x" not in line.split("->")[-1], line
    # both scopes were read, each in forward, recompute and backward
    assert seen["rope"] > 30 and seen["q_latent"] > 30, seen


def test_scopes_and_accounts_of_a_toy_step(interpret_pallas, real_kernels):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=toy_model(attention_impl="flash"), config=base_config(
                train_micro_batch_size_per_gpu=B,
                gradient_accumulation_steps=GAS), mesh=one_device())
        engine.train_batch(batch=packed_batch())
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    scopes = [row["scope"] or "" for row in table.values()]
    for name in ("ds.embed", "ds.head_loss", "ds.block/attn/q_latent",
                 "ds.block/attn/kv_latent", "ds.block/attn/rope",
                 "ds.block/attn/scores", "ds.block/attn/out_proj",
                 "ds.block/mlp/router", "ds.block/mlp/dispatch",
                 "ds.block/mlp/experts", "ds.block/mlp/combine",
                 "ds.block/mlp/shared_expert", "ds.mtp", "ds_ggemm_fwd",
                 "ds_ggemm_dx", "ds_ggemm_dw"):
        assert any(name in s for s in scopes), name
    # the module's own embedding lookup, head pass and loss are its scope's
    for inner in ("ds.embed", "ds.head_loss"):
        assert any("ds.mtp" in s and inner in s for s in scopes), inner
    # the module's block is under ds.mtp, with ds.block's own scopes
    assert any("ds.mtp" in s and "ds.block/attn/scores" in s for s in scopes)
    assert any("ds.mtp" in s and "ds.block/mlp/experts" in s for s in scopes)
    for phase in ("forward", "recompute", "backward"):
        assert any(row["phase"] == phase and "/attn/scores/" in row["scope"]
                   for row in table.values() if row["scope"]), phase
    # an instruction of a block is under one of the block's own scopes: a
    # family that writes none reads ``other`` in every step.* metric
    for row in table.values():
        if "ds.block" in (row["scope"] or "") or "ds.mtp" in (
                row["scope"] or ""):
            assert row["phase"] != "other", row
        if "ds.block" in (row["scope"] or ""):
            assert any(part in row["scope"]
                       for part in ("/attn/", "/mlp/")), row
    assert set(tracing.STEP_SCOPES) >= {"q_latent", "kv_latent", "rope",
                                        "scores", "out_proj", "ds.mtp"}
    rows = tracing.grouped_gemm_rows("train/step")
    T, k = B * S, TOY["top_k"]
    bound = -(-(2 * T * k * 4 // 16) // 128) * 128
    assert rows["held_rows_bound"] == bound
    assert (rows["experts_held"], rows["experts_routed"]) == (4, 16)
    assert {c["kernel"] for c in rows["calls"]} == {
        "ds_ggemm_fwd", "ds_ggemm_dx", "ds_ggemm_dw"}
    flash = tracing.flash_calls("train/step")
    assert [(c["dk"], c["dv"], c["heads"], c["kv_heads"], c["seq_len"],
             c["packed"]) for c in flash] == [(24, 16, 4, 4, S, True)]
