"""Kimi-Linear through the normal path at toy size on the CPU, against the
plain reference the benchmark uses (benchmarks/references/kimi_linear.py —
this file imports that same file): loss and every leaf's gradient with
packed documents, each thing that makes the model itself left out in turn,
and the layer kinds a depth cut keeps.  The share of an expert-parallel
layer, what the family refuses by name and its sizes are
tests/test_kimi_linear_share.py, the engine's steps and the scopes of a toy
step tests/test_kimi_linear_engine.py, both on this file's toy model.

Everything is float32 with seeded weights: the two sides differ only in
the order of summation."""
import functools
import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import joyai, kimi_linear
from deepspeed_tpu.models.kimi_linear import (KDA, MLA, KimiLinearConfig,
                                              kimi_linear_model)
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "kimi_linear_reference",
    os.path.join(REPO, "benchmarks", "references", "kimi_linear.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 2e-6
GRAD_TOL = 2e-4         # max |a - b| / max |b| per leaf; measured <= 2e-5

TOY = dict(num_layers=8, d_model=64, kda_num_heads=2, kda_head_dim=16,
           kda_gate_rank=8, delta_rule_chunk=16, num_heads=4,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, d_ff_dense=96, d_ff=32, shared_expert_d_ff=32,
           num_experts=16, top_k=4, experts_held=4, expert_offset=8,
           vocab_size=512, max_seq_len=128, dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 48, 4


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return kimi_linear_model("48b-a3b", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: matrices several times
    their initial size, norms off 1, a router whose choices are decided,
    a selection bias that changes them, decays from nearly none to e^-5 a
    token."""
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
        if name == "A_log":
            return jnp.log(jax.random.uniform(sub, w.shape, minval=0.5,
                                              maxval=8.0))
        if name == "dt_bias":
            return jax.random.normal(sub, w.shape)
        if name in ("w_f_up", "w_g_up", "w_f_down", "w_g_down", "w_ukv",
                    "w_dkv", "w_q", "w_beta"):
            return w * 12.0
        if name == "conv_w":
            return w * 25.0
        if name == "wte":
            return w
        return w * 5.0

    return jax.tree_util.tree_map_with_path(push, params)


def packed_batch(seed=0, gas=GAS, docs=DOCS):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TOY["vocab_size"], size=(gas, B, S),
                       dtype=np.int32)
    cuts = np.sort(rng.integers(1, S, size=(gas, B, docs - 1)), axis=-1)
    cuts[0, 0, :3] = (15, 16, 30)     # a one-token document at a chunk's edge
    segments = (np.arange(S)[None, None, :, None]
                >= cuts[:, :, None, :]).sum(-1).astype(np.int32)
    return {"input_ids": ids, "segment_ids": segments}


def micro(batch, g=0):
    return {k: jnp.asarray(v[g]) for k, v in batch.items()}


def reference_loss(params, mb, sizes):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=24)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@functools.lru_cache(maxsize=None)
def seeded_toy():
    """(model, seeded weights, first micro-batch), made once a process."""
    model = toy_model()
    return model, seeded_params(model), micro(packed_batch())


@functools.lru_cache(maxsize=None)
def both_sides():
    """((loss, gradients) of the model, of the reference) at the seeded
    toy, once a process: the right side of every planted fault."""
    model, params, mb = seeded_toy()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, sizes=sizes_of(model))))(params, mb)
    return got, want


def _leaves(tree):
    return {"/".join(p.key for p in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


LEAVES = sorted(_leaves(jax.eval_shape(toy_model().init,
                                       jax.random.PRNGKey(0))))


def test_the_loss_is_the_references():
    (loss, _), (want, _) = both_sides()
    assert abs(float(loss) - float(want)) < LOSS_TOL, (loss, want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_gradient_is_the_references(leaf):
    (_, grads), (_, want) = both_sides()
    got, b = _leaves(grads)[leaf], _leaves(want)[leaf]
    if leaf.endswith("e_score_correction_bias"):
        # in the choice alone: the loss does not train it
        assert float(jnp.abs(got).max()) == float(jnp.abs(b).max()) == 0
        return
    assert float(jnp.abs(got - b).max()) \
        < GRAD_TOL * float(jnp.abs(b).max()), leaf
    assert float(jnp.abs(got).max()) > 0, leaf      # every leaf learns


def test_the_tree_is_the_leads_and_the_runs():
    assert {leaf.split("/")[0] for leaf in LEAVES} == {
        "wte", "lead", "blocks", "final_norm", "lm_head"}
    tree = jax.eval_shape(toy_model().init, jax.random.PRNGKey(0))
    blocks = tree["blocks"]
    assert set(blocks) == {"run0", "run1"}
    # K K M, then K K K M: a share's own experts, the router whole
    assert blocks["run0"][KDA]["w_qkv"].shape == (1, 2, 64, 3 * 32)
    assert blocks["run1"][KDA]["w_qkv"].shape == (1, 3, 64, 3 * 32)
    assert blocks["run0"][MLA]["w_q"].shape == (1, 1, 64, 4 * 24)
    assert blocks["run1"][KDA]["moe"]["w_in"].shape == (1, 3, 4, 64, 32)
    assert blocks["run1"][MLA]["moe"]["router"].shape == (1, 1, 64, 16)
    assert "w_dq" not in blocks["run0"][MLA]        # no query latent
    assert tree["lead"]["w_gate"].shape == (64, 96)
    assert tree["lead"]["dt_bias"].shape == (32,)   # a key channel each
    assert tree["lead"]["A_log"].shape == (2,)      # a head each
    specs = kimi_linear.logical_specs(toy_model().config)
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))


# ------------------------------------------- what makes it this model
def _patched_rule(monkeypatch, change):
    real = kimi_linear.gated_delta_rule
    monkeypatch.setattr(kimi_linear, "gated_delta_rule",
                        lambda q, k, v, g, *a, **kw: change(
                            real, q, k, v, g, *a, **kw))


def _decay_after_the_write(monkeypatch):
    """``S_t = Diag(exp(g_t)) (S_{t-1} + k beta (v - S_{t-1}^T k))``."""
    from deepspeed_tpu.ops.linear_attention import l2norm

    def rule(real, q, k, v, g, beta, seg, chunk, l2norm_scales):
        q, k = (l2norm(t) * s for t, s in zip((q, k), l2norm_scales))
        first = jnp.concatenate([jnp.ones((q.shape[0], 1), bool),
                                 seg[:, 1:] != seg[:, :-1]], axis=1)

        def token(state, xs):
            q_t, k_t, v_t, g_t, b_t, first_t = xs
            state = jnp.where(first_t[:, None, None, None], 0.0, state)
            read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
            state = (state + k_t[..., None] * (
                b_t[..., None] * (v_t - read))[..., None, :]) \
                * jnp.exp(g_t)[..., None]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

        by_token = lambda a: jnp.moveaxis(a, 1, 0)
        B_, _, H, dk = q.shape
        _, o = jax.lax.scan(
            token, jnp.zeros((B_, H, dk, v.shape[-1]), jnp.float32),
            tuple(by_token(a) for a in (q, k, v, g, beta, first)))
        return jnp.moveaxis(o, 0, 1)

    _patched_rule(monkeypatch, rule)


def _the_channels_mean_decay(monkeypatch):
    _patched_rule(monkeypatch, lambda real, q, k, v, g, *a, **kw: real(
        q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape),
        *a, **kw))


def _rotary_left_on(monkeypatch):
    turned = lambda q, k_r, config: joyai._rotary(q, k_r, SimpleNamespace(
        rope_theta=10000.0, qk_nope_head_dim=config.qk_nope_head_dim))
    monkeypatch.setattr(
        kimi_linear, "latent_attention",
        lambda x, layer, config, seg, rotary=None: joyai.latent_attention(
            x, layer, config, seg, rotary=turned))


def _a_convolution_that_crosses_documents(monkeypatch):
    real = kimi_linear.causal_conv
    monkeypatch.setattr(kimi_linear, "causal_conv",
                        lambda x, w, seg, **kw: real(x, w, None, **kw))


def _the_gate_before_the_norm(monkeypatch):
    monkeypatch.setattr(
        kimi_linear, "_gated_norm", lambda o, gate, w, eps:
        kimi_linear._rms_norm(o * jax.nn.sigmoid(gate), w, eps))


#: name -> what it does to the MODEL's side.  The reference keeps the
#: equations; the loss then has to leave the tolerance.
DEPARTURES = {
    "the_decay_after_the_write": _decay_after_the_write,
    "the_channels_mean_in_place_of_the_vector": _the_channels_mean_decay,
    "rotary_left_on_in_the_mla_layers": _rotary_left_on,
    "a_convolution_not_reset_at_a_document":
        _a_convolution_that_crosses_documents,
    "the_output_gate_before_the_norm": _the_gate_before_the_norm,
}


@pytest.mark.parametrize("name", list(DEPARTURES))
def test_each_departure_leaves_the_tolerance(name, monkeypatch):
    model, params, mb = seeded_toy()
    want = float(both_sides()[1][0])
    DEPARTURES[name](monkeypatch)
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(toy_model().loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_the_lead_is_a_kda_layer_on_both_sides():
    """The sixth departure cannot be planted: a lead with latent attention
    is another parameter tree, and both sides refuse it by name."""
    with pytest.raises(ValueError, match="leading dense layer's mixer"):
        toy_model(kda_layers=(2, 3, 5, 6, 7), full_attn_layers=(1, 4, 8))
    model, params, mb = seeded_toy()
    with pytest.raises(ValueError, match="layer 1's mixer is KDA"):
        reference_loss(params, mb, {**sizes_of(model),
                                    "layer_kinds": "MKKMKKKM"})


@pytest.mark.parametrize("depth, kinds, runs", [
    (8, "KKKMKKKM", (((KDA, KDA, MLA), 1), ((KDA, KDA, KDA, MLA), 1))),
    (5, "KKKMK", (((KDA, KDA, MLA), 1), ((KDA,), 1))),
    (27, "KKKM" * 6 + "KKM", (((KDA, KDA, MLA), 1),
                              ((KDA, KDA, KDA, MLA), 5),
                              ((KDA, KDA, MLA), 1))),
])
def test_a_depth_cut_keeps_the_first_layers_kinds(depth, kinds, runs):
    config = KimiLinearConfig(num_layers=depth)
    assert config.layer_kinds == kinds
    assert config.runs == runs
    assert sum(len(p) * n for p, n in runs) == depth - 1
    with pytest.raises(ValueError, match="do not name each"):
        KimiLinearConfig(num_layers=4, kda_layers=(1, 2),
                         full_attn_layers=(4,))
