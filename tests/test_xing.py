"""Xing4.0 through the normal path at toy size on the CPU, against the plain
reference the benchmark uses (benchmarks/references/xing.py — this file
imports that same file): loss and every leaf's gradient with packed
documents and hyper-connection leaves drawn at order 1, the block with one
stream against JoyAI's block, the share of an expert-parallel layer (its
parts add up), each thing that makes the model itself left out in turn,
what it refuses by name, and its sizes.  The engine's steps and the scopes
of a toy step are tests/test_xing_engine.py, on this file's toy model.

Everything is float32 with seeded weights: the two sides differ only in
the order of summation."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import joyai, xing
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.models.xing import XingConfig, count_params, xing_model
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.ops import hyper_connection
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "xing_reference",
    os.path.join(REPO, "benchmarks", "references", "xing.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured 0 to 3e-6
GRAD_TOL = 2e-4         # max |a - b| / max |b| per leaf; measured <= 3e-5

TOY = dict(num_layers=3, num_dense_layers=1, d_model=64, num_heads=4,
           q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
           # S 48 is three original contexts: YaRN's ramp is in play
           rope_factor=8.0, original_max_position_embeddings=16,
           d_ff_dense=96, d_ff=32, shared_expert_d_ff=32, num_experts=16,
           top_k=4, experts_held=4, expert_offset=8, vocab_size=512,
           max_seq_len=128, dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 48, 4


@pytest.fixture(autouse=True)
def _isolation():
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return xing_model("4.0-29b-a4b", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: as tests/test_joyai.py,
    and the hyper-connections' scalars and biases at order 1 with
    projections of order 1, so that ``H_res`` is far from the identity and
    from the uniform matrix and ``H_pre``, ``H_post`` far from their
    start."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if name in ("alpha", "b_pre", "b_post", "b_res"):
            return jax.random.normal(sub, w.shape)
        if name == "phi":
            return jax.random.normal(sub, w.shape) / np.sqrt(w.shape[-2])
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
    fn = functools.partial(reference_loss, sizes=sizes_of(model))
    return jax.jit(jax.value_and_grad(fn) if grad else fn)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@functools.lru_cache(maxsize=None)
def seeded_toy():
    """(model, seeded weights, first micro-batch, the reference's loss
    there), made once a process: the right side of every planted fault."""
    model = toy_model()
    params, mb = seeded_params(model), micro(packed_batch())
    return model, params, mb, float(jitted_reference_loss(model)(params, mb))


def _pop_biases(grads):
    return [grads["blocks"]["moe"].pop("e_score_correction_bias"),
            grads["mtp"]["block"]["moe"].pop("e_score_correction_bias")]


#: where every stream is still a copy of one row, a sublayer that starts
#: with a norm does not see H_pre's scale nor H_res's mixing: the first
#: attention's b_pre and b_res (the main stack's and the module's) have
#: gradients of rounding's size on both sides
BLIND = {("dense", "hc_attn", "b_pre"), ("dense", "hc_attn", "b_res"),
         ("mtp", "block", "hc_attn", "b_pre"),
         ("mtp", "block", "hc_attn", "b_res")}


@pytest.mark.parametrize("held", ["a_share", "every_expert"])
def test_gradients_match_the_reference(held):
    model = toy_model(**({} if held == "a_share" else
                         dict(experts_held=None, expert_offset=0)))
    params, mb = seeded_params(model), micro(packed_batch())
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jitted_reference_loss(model, grad=True)(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    for bias in _pop_biases(grads) + _pop_biases(want_grads):
        assert float(jnp.abs(bias).max()) == 0
    everywhere = max(float(jnp.abs(g).max())
                     for g in jax.tree.leaves(want_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, got in jax.tree_util.tree_leaves_with_path(grads):
        names = tuple(p.key for p in path)
        b = flat_want[path]
        if names in BLIND:
            assert float(jnp.abs(b).max()) < 1e-4 * everywhere, names
            assert float(jnp.abs(got).max()) < 1e-4 * everywhere, names
            continue
        assert float(jnp.abs(got - b).max()) \
            < GRAD_TOL * float(jnp.abs(b).max()), names
        # every other leaf learns
        assert float(jnp.abs(got).max()) > 0, names
    hc = grads["blocks"]["hc_mlp"]
    assert set(hc) == {"phi", "alpha", "b_pre", "b_post", "b_res"}


def test_the_mixing_matrices_are_far_from_both_trivial_ones():
    """What the gradient test stands on: at the seeded weights ``H_res`` of
    a main block is neither the identity nor uniform, and doubly
    stochastic."""
    model, params, mb, _ = seeded_toy()
    cfg = model.config
    x = jax.random.normal(jax.random.PRNGKey(5), (B, S, cfg.hc_mult
                                                  * cfg.d_model))
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["hc_mlp"])
    pre, post, res = hyper_connection.hc_coefficients(x, layer, cfg.hc)
    assert float(jnp.abs(res - jnp.eye(4)).mean()) > 0.1
    assert float(jnp.abs(res - 0.25).mean()) > 0.05
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-4)
    assert float(jnp.abs(pre - 0.25).mean()) > 0.05
    assert float(jnp.abs(post - 1.0).mean()) > 0.1


def test_the_modules_token_losses_match_the_reference():
    model, params, mb, _ = seeded_toy()
    got, scored = jax.jit(model.meta["mtp_token_losses"])(params, mb)
    want, want_scored = reference.mtp_token_losses(
        params, mb, sizes_of(model), chunk=1)
    np.testing.assert_array_equal(scored, want_scored)
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


# ------------------------------------------------- one stream is JoyAI's
def _one_stream_params(cfg, joyai_layer, key):
    """JoyAI's layer with hyper-connection leaves whose coefficients are
    all 1 (``alpha`` 0, ``b_post`` 0, one stream)."""
    make = functools.partial(hyper_connection.init_hc_params, cfg.hc,
                             cfg.d_model, alpha=0.0)
    k1, k2 = jax.random.split(key)
    return {**joyai_layer, "hc_attn": make(k1), "hc_mlp": make(k2)}


@pytest.mark.parametrize("kind", ["dense", "expert"])
def test_with_one_stream_the_block_is_joyais_block(kind):
    """n = 1 and ``H_pre = H_post = H_res = 1``: ``X' = X + F(N(X))``.
    Plain rotary on both sides (``rope_factor`` 1: YaRN is this family's
    own)."""
    sizes = {k: v for k, v in TOY.items()
             if k not in ("num_dense_layers", "rope_factor",
                          "original_max_position_embeddings")}
    theirs = joyai.JoyAIConfig(**sizes, routed_scaling_factor=2.0)
    ours = XingConfig(**{**TOY, "hc_mult": 1, "rope_factor": 1.0})
    params = joyai.init_params(theirs, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, theirs.d_model))
    seg = micro(packed_batch())["segment_ids"]
    if kind == "dense":
        layer = jax.tree.map(lambda a: 5.0 * a, params["dense"])
        want = joyai._dense_block(x, layer, theirs, segment_ids=seg)
        got = xing._dense_block(
            x,
            _one_stream_params(ours, layer, jax.random.PRNGKey(2)), ours,
            segment_ids=seg)
    else:
        layer = jax.tree.map(lambda a: 5.0 * a[0], params["blocks"])
        want, (want_aux, _) = joyai._expert_block(
            x, layer, theirs, train=True, segment_ids=seg)
        got, (aux, over) = xing._expert_block(
            x,
            _one_stream_params(ours, layer, jax.random.PRNGKey(2)), ours,
            train=True, segment_ids=seg)
        assert float(aux) == pytest.approx(float(want_aux), rel=1e-6)
        assert int(over[0]) == 0    # the rows over; then the load
    assert got.shape == (B, S, theirs.d_model)      # one stream: the row
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))
    assert xing.softmax_factor(ours) == 1.0


# ------------------------------------------------------- the share's sums
def _held(params, offset, n):
    return {k: (w[offset:offset + n] if k in ("w_in", "w_out", "w_gate")
                else w) for k, w in params.items()}


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's share test on a whole hyper-connected expert sublayer:
    the write is linear in the branch, so the routed parts of all four
    shares (4 experts of 16 each) plus the shared expert once, written
    into the streams once, are the uncut block's output — which the
    ``every_expert`` case above holds to the uncut reference; the router
    loss is the same on every share."""
    whole_model = toy_model(experts_held=None, expert_offset=0)
    cfg = whole_model.config
    layer = jax.tree.map(lambda a: a[0],
                         seeded_params(whole_model)["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 4 * 64))
    whole, (aux, _) = jax.jit(lambda x, layer: xing._expert_block(
        x, layer, cfg, train=True))(x, layer)
    attended = jax.jit(lambda x, layer: xing._attention_sublayer(
        x, layer, cfg, None, "probe", 1))(x, layer)
    pre, post, res = hyper_connection.hc_coefficients(
        attended, layer["hc_mlp"], cfg.hc)
    h = xing._rms_norm(hyper_connection.hc_read(attended, pre),
                       layer["mlp_norm"], cfg.norm_eps)
    routed_only = replace(cfg.moe, shared_expert_d_ff=0)
    total = moe_layer.moe_layer(layer["moe"], h, cfg.moe)[0] \
        - moe_layer.moe_layer(layer["moe"], h, routed_only)[0]   # shared
    for i in range(4):
        part_cfg = replace(routed_only, expert_offset=4 * i, experts_held=4)
        part, aux_i, stats = moe_layer.moe_layer(
            _held(layer["moe"], 4 * i, 4), h, part_cfg, return_stats=True)
        assert int(stats["dropped"]) == 0
        assert float(aux_i) == pytest.approx(float(aux), rel=1e-5)
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    summed = hyper_connection.hc_write(attended, total, post, res)
    np.testing.assert_allclose(summed, whole, atol=1e-5 * float(
        jnp.abs(whole).max()))


def test_a_share_allocates_its_own_experts_and_every_sublayers_streams():
    tree = jax.eval_shape(toy_model().init, jax.random.PRNGKey(0))
    assert tree["blocks"]["moe"]["w_in"].shape == (2, 4, 64, 32)
    assert tree["blocks"]["moe"]["router"].shape == (2, 64, 16)
    assert tree["mtp"]["block"]["moe"]["w_in"].shape == (4, 64, 32)
    assert "wte" not in tree["mtp"] and "lm_head" not in tree["mtp"]
    for block, lead in ((tree["dense"], (1,)), (tree["blocks"], (2,)),
                        (tree["mtp"]["block"], ())):
        for name in ("hc_attn", "hc_mlp"):
            assert {k: v.shape for k, v in block[name].items()} == {
                "phi": lead + (4 * 64, 24), "alpha": lead + (3,),
                "b_pre": lead + (4,), "b_post": lead + (4,),
                "b_res": lead + (4, 4)}
    specs = xing.logical_specs(toy_model().config)
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))


# ------------------------------------------- what makes it this model
def _with_hc(monkeypatch, **changes):
    explicit = XingConfig.hc.fget
    monkeypatch.setattr(XingConfig, "hc", property(
        lambda self: replace(explicit(self), **changes)))


def _patched_coefficients(monkeypatch, change):
    real = xing.hc_coefficients

    def patched(*args, **kwargs):
        return change(*real(*args, **kwargs))
    monkeypatch.setattr(xing, "hc_coefficients", patched)


def _entry_in_one_stream(monkeypatch):
    def first_only(x, n):
        return jnp.concatenate([x] + [jnp.zeros_like(x)] * (n - 1), axis=-1)
    monkeypatch.setattr(xing, "_replicate", first_only)


def _plain_rotary(monkeypatch):
    monkeypatch.setattr(xing, "yarn_inv_freq", lambda config: np.asarray(
        config.rope_theta ** (-np.arange(0, config.qk_rope_head_dim, 2)
                              / config.qk_rope_head_dim)))


def _module_reads_after_the_final_norm(monkeypatch):
    real = xing.mtp_input

    def normed(params, x, batch, config):
        return real(params, xing._rms_norm(x, params["final_norm"],
                                           config.norm_eps), batch, config)
    monkeypatch.setattr(xing, "mtp_input", normed)


#: name -> (what it does to the MODEL's side: a patch, overrides of the
#: builder).  The reference keeps the equations; the loss then has to
#: leave the tolerance.  Two of the configuration's assumed choices are not
#: here because no loss can tell them apart: rows before columns (20 sweeps
#: reach the same matrix: 2e-6 on this loss; one sweep shows it,
#: tests/test_hyper_connection.py) and exit by mean (a norm follows every
#: exit: 2e-4, through its epsilon alone).
DEPARTURES = {
    "one_sweep": (lambda mp: _with_hc(mp, sweeps=1), {}),
    "a_clamp_at_a_half": (None, dict(hc_clamp_min=-0.5, hc_clamp_max=0.5)),
    "h_post_without_its_2": (lambda mp: _patched_coefficients(
        mp, lambda pre, post, res: (pre, post / 2, res)), {}),
    "h_res_transposed": (lambda mp: _patched_coefficients(
        mp, lambda pre, post, res: (pre, post, jnp.swapaxes(res, -1, -2))),
        {}),
    "no_mixing": (lambda mp: _patched_coefficients(
        mp, lambda pre, post, res: (pre, post, jnp.broadcast_to(
            jnp.eye(res.shape[-1]), res.shape))), {}),
    "norm_eps_of_the_flattened_norm": (
        lambda mp: _with_hc(mp, norm_eps=1.0), {}),
    "entry_in_one_stream": (_entry_in_one_stream, {}),
    "plain_rotary": (_plain_rotary, {}),
    "no_softmax_factor": (None, dict(mscale_all_dim=0.0)),
    "mscale_on_the_tables": (None, dict(mscale=3.0)),
    "module_reads_after_the_final_norm": (
        _module_reads_after_the_final_norm, {}),
}


@pytest.mark.parametrize("name", list(DEPARTURES))
def test_each_departure_leaves_the_tolerance(name, monkeypatch):
    patch, overrides = DEPARTURES[name]
    _, params, mb, want = seeded_toy()
    if patch:
        patch(monkeypatch)
    model = toy_model(**overrides)
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(model.loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_the_control_stays_inside_the_tolerance():
    model, params, mb, want = seeded_toy()
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(model.loss)(params, mb))
    assert abs(got - want) < LOSS_TOL, (got, want)


def test_another_number_of_streams_is_another_tree():
    two = jax.eval_shape(toy_model(hc_mult=2).init, jax.random.PRNGKey(0))
    assert two["blocks"]["hc_attn"]["phi"].shape == (2, 2 * 64, 8)
    with pytest.raises(ValueError, match="hc_mult"):
        toy_model(hc_mult=0)


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
    with pytest.raises(NotImplementedError, match="stream's state"):
        getattr(toy_model(), entry)(None, None, None)


def test_yarn_and_the_softmax_factor_are_the_published_ones():
    cfg = XingConfig()
    assert xing.softmax_factor(cfg) == pytest.approx(
        (0.1 * np.log(64.0) + 1.0) ** 2) == pytest.approx(2.00474, rel=1e-5)
    freq = xing.yarn_inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert freq.shape == (32,)
    # fast dimensions keep their frequency, slow ones run 64 x slower
    np.testing.assert_allclose(freq[:8], plain[:8])
    np.testing.assert_allclose(freq[-4:], plain[-4:] / 64.0)
    assert np.all(np.diff(freq) < 0)
    np.testing.assert_allclose(
        freq, reference._yarn_frequencies(
            {"qk_rope_head_dim": 64, "rope_theta": 10000.0,
             "original_max_position_embeddings": 4096, "beta_fast": 32,
             "beta_slow": 1, "rope_factor": 64}), rtol=1e-12)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    whole = XingConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 30_276_195_174
    # the name's 29B: the 40-layer stack, embedding and head
    assert count_params(replace(whole, num_mtp_layers=0)) \
        == config["published"]["n_params_main"] == 29_505_505_264
    assert (whole.qk_head_dim, whole.v_head_dim, whole.expert_layers) \
        == (192, 128, 38)
    model = xing_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    assert model.meta["n_params"] == 913_473_668
    # every width under the source's own key
    cut = model.config
    assert (cut.d_model, cut.num_heads, cut.q_lora_rank, cut.kv_lora_rank,
            cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim,
            cut.d_ff_dense, cut.d_ff, cut.top_k, cut.hc_mult,
            cut.hc_sinkhorn_iters, cut.num_experts) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_per_tok", "hc_mult", "hc_sinkhorn_iters")) + (
                config["published"]["n_routed_experts"],)
    assert (cut.d_model, cut.d_ff_dense, cut.d_ff, cut.q_lora_rank,
            cut.kv_lora_rank, cut.num_heads, cut.num_experts, cut.top_k,
            cut.hc_mult, cut.hc_sinkhorn_iters) == (
        3584, 9216, 1024, 768, 512, 32, 64, 4, 4, 20)
    scaling = config["rope_scaling"]
    assert (cut.rope_theta, cut.norm_eps, cut.routed_scaling_factor,
            cut.num_mtp_layers, cut.hc_eps, cut.hc_clamp_min,
            cut.hc_clamp_max, cut.rope_factor,
            cut.original_max_position_embeddings, cut.beta_fast,
            cut.beta_slow, cut.mscale, cut.mscale_all_dim) == (
        config["rope_theta"], config["rms_norm_eps"],
        config["routed_scaling_factor"], config["num_nextn_predict_layers"],
        config["hc_eps"], config["mhc_h_res_clamp_min"],
        config["mhc_h_res_clamp_max"], scaling["factor"],
        scaling["original_max_position_embeddings"], scaling["beta_fast"],
        scaling["beta_slow"], scaling["mscale"], scaling["mscale_all_dim"])
    assert config["first_k_dense_replace"] == cut.num_dense_layers == 1
    assert config["published"]["first_k_dense_replace"] \
        == whole.num_dense_layers == 2
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    moe = shapes["blocks"]["moe"]
    assert moe["router"].shape == (4, 3584, 64)
    assert moe["w_gate"].shape == moe["w_in"].shape == (4, 8, 3584, 1024)
    assert moe["w_out"].shape == (4, 8, 1024, 3584)
    assert shapes["dense"]["w_gate"].shape == (1, 3584, 9216)
    assert shapes["blocks"]["w_uq"].shape == (4, 768, 32 * 192)
    assert shapes["blocks"]["w_dkv"].shape == (4, 3584, 512 + 64)
    assert shapes["blocks"]["hc_attn"]["phi"].shape == (4, 4 * 3584, 24)
    assert shapes["mtp"]["w_eh"].shape == (7168, 3584)
    assert shapes["wte"].shape == (16384, 3584)
    with pytest.raises(ValueError, match="leading dense layers"):
        XingConfig(num_layers=2, num_dense_layers=2)
    with pytest.raises(ValueError, match="0 or 1"):
        XingConfig(num_mtp_layers=2)
