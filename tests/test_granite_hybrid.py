"""Granite 4.0-H (models/granite_hybrid.py) through the normal path at toy
size on the CPU, against the plain reference the benchmark uses
(benchmarks/references/granite_hybrid.py — this file imports that same
file, there is no second copy): logits, loss and every leaf's gradient,
uncut and as one chip's share; packed documents; the four muP scalars each
shown to matter; the share test that ties the cut to the model (experts,
attention heads, Mamba-2 heads up to the gated norm's statistic); the
published count and what is refused.

``DS_GGEMM_INTERPRET=1`` runs the real grouped GEMM kernels in Pallas'
interpreter.  Everything is float32 with seeded weights: the two sides
differ only in the order of summation and in the form of the state-space
scan (chunked here, per token there).  The tests that build an engine are
``tests/test_granite_hybrid_engine.py``."""
import functools
import importlib.util
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                 count_params,
                                                 granite_hybrid_model,
                                                 take_share)
from deepspeed_tpu.models.model import param_stream_scope
from deepspeed_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "granite_hybrid_reference",
    os.path.join(REPO, "benchmarks", "references", "granite_hybrid.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LOSS_TOL = 2e-5         # measured <= 2e-6
GRAD_TOL = 2e-4         # max |a - b| / max |b| per leaf; measured <= 4e-5
SHARES = 8

#: sixteen heads of each kind and sixteen experts: eight shares of two
TOY = dict(num_layers=4,
           layer_types=("mamba", "attention", "mamba", "mamba"), d_model=64,
           num_heads=16, num_kv_heads=8, head_dim=8, mamba_num_heads=16,
           mamba_head_dim=8, ssm_state_size=16, chunk_size=16, d_ff=32,
           shared_expert_d_ff=64, num_experts=16, top_k=4, vocab_size=512,
           max_seq_len=128, dtype="float32", remat=True)
GAS, B, S, DOCS = 2, 2, 72, 4


def share_of(r):
    """What share ``r`` of :data:`SHARES` is built with; a plan that takes
    every routed row."""
    return dict(experts_held=2, expert_offset=2 * r, mamba_heads_held=2,
                attn_heads_held=2, kv_heads_held=1, head_share=r,
                held_rows_factor=SHARES)


@pytest.fixture(autouse=True)
def real_kernels(monkeypatch):
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    tracing.reset_programs()
    yield
    tracing.reset_programs()


def toy_model(**overrides):
    return granite_hybrid_model("4.0-h-small", **{**TOY, **overrides})


def sizes_of(model):
    return {k: getattr(model.config, k) for k in reference.SIZES}


def seeded_params(model, seed=0):
    """Seeded weights at which every part matters: norm weights away from
    their start, router logits wide, the skip term and the convolution's
    bias off their start, attention scores and logits wide."""
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if name == "final_norm":        # a tied head: logits of std 1
            return 10.0 * w + 3.0 * jax.random.normal(sub, w.shape)
        if name.endswith("norm") or name in ("D", "conv_b"):
            return w + 0.3 * jax.random.normal(sub, w.shape)
        if name == "router":
            return w * 40.0
        if name in ("wq", "wk"):
            return w * 60.0
        if name == "wte":
            return w * 3.0
        return w * 4.0 if w.ndim > 3 else w

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


def reference_loss(params, mb, sizes, **kwargs):
    return reference.micro_batch_loss(
        params, mb["input_ids"], mb.get("segment_ids"), sizes, block=36,
        **kwargs)


def one_device():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


@functools.lru_cache(maxsize=None)
def toy(held="uncut"):
    """(model, seeded weights, first micro-batch) of the uncut toy, or of
    its share 3 cut out of the same weights."""
    whole = toy_model()
    params = seeded_params(whole)
    if held == "a_share":
        model = toy_model(**share_of(3))
        return model, take_share(params, whole.config, model.config), \
            micro(packed_batch())
    return whole, params, micro(packed_batch())


# ------------------------------------------------ against the reference
@pytest.mark.parametrize("held", ["uncut", "a_share"])
def test_loss_logits_and_every_gradient_leaf_match_the_reference(held):
    model, params, mb = toy(held)
    sizes = sizes_of(model)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, sizes=sizes)))(params, mb)
        logits = jax.jit(model.apply)(params, mb)
        nll, scored = jax.jit(functools.partial(
            reference_loss, sizes=sizes, per_token=True))(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    got_nll = jax.scipy.special.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.roll(mb["input_ids"], -1, 1)[..., None], -1)[..., 0]
    assert float(jnp.abs(got_nll - nll)[scored].max()) < 1e-4
    worst = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)
    if held == "a_share":
        blocks = jax.tree.map(jnp.shape, params["blocks"])
        assert blocks["ssm"]["w_in"] == (1, 3, 64, 16 + 16 + 32 + 2)
        assert blocks["ssm"]["conv_w"] == (1, 3, 4, 16 + 32)
        assert blocks["ssm"]["w_out"] == (1, 3, 16, 64)
        assert blocks["attn"]["wq"] == (1, 1, 64, 16)
        assert blocks["attn"]["wk"] == (1, 1, 64, 8)
        assert blocks["attn"]["moe"]["w_gate"] == (1, 1, 2, 64, 32)
        assert blocks["attn"]["moe"]["router"] == (1, 1, 64, 16)
        assert blocks["attn"]["moe"]["shared_in"] == (1, 1, 64, 64)


def test_nothing_crosses_a_document_boundary():
    """State, convolution history and attention: the second document's
    logits do not move when the first one's tokens do, and the loss is not
    the one of a model that never resets."""
    model, params, mb = toy()
    apply = jax.jit(model.apply)
    seg = np.asarray(mb["segment_ids"])
    first = seg[0] == 0
    other = dict(mb, input_ids=mb["input_ids"].at[0].set(jnp.where(
        first, (mb["input_ids"][0] + 7) % TOY["vocab_size"],
        mb["input_ids"][0])))
    a, b = apply(params, mb), apply(params, other)
    assert float(jnp.abs(a[0, first] - b[0, first]).max()) > 1e-2
    np.testing.assert_array_equal(a[0, ~first], b[0, ~first])
    np.testing.assert_array_equal(a[1], b[1])
    loss = float(jax.jit(model.loss)(params, mb))
    unreset = float(jax.jit(functools.partial(
        reference_loss, sizes=sizes_of(model)))(
            params, {"input_ids": mb["input_ids"]}))
    assert abs(loss - unreset) > 100 * LOSS_TOL


@pytest.mark.parametrize("name, plain", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", TOY["head_dim"] ** -0.5),
    ("logits_scaling", 1.0)])
def test_each_of_the_four_multipliers_matters(name, plain):
    """The control: the reference with one scalar put back to what a plain
    decoder has misses the program's loss by far more than the
    tolerance; and the program's config carries the published four."""
    model, params, mb = toy()
    loss = float(jax.jit(model.loss)(params, mb))
    without = float(jax.jit(functools.partial(
        reference_loss, sizes=sizes_of(model),
        multipliers={name: plain}))(params, mb))
    assert abs(loss - without) > 50 * LOSS_TOL, (name, loss, without)
    assert (GraniteHybridConfig().embedding_multiplier,
            GraniteHybridConfig().residual_multiplier,
            GraniteHybridConfig().attention_multiplier,
            GraniteHybridConfig().logits_scaling) == (12, 0.22, 1 / 128, 16)


# ------------------------------------- the share test: the cut and the model
def _normed_input(seed=5):
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, TOY["d_model"]))
    return x, micro(packed_batch())["segment_ids"]


def _layer(params, kind, j=0):
    return jax.tree.map(lambda a: a[0, j], params["blocks"][kind])


def _close(got, want, tol=2e-5):
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < tol, err


def test_the_expert_shares_add_up_to_the_uncut_sublayer():
    """(a) the program's eight shares of the expert sublayer (two experts
    each), the shared expert counted once, are the uncut reference's
    ``MoE(h) + Shared(h)``; no row is over a share's bound and every
    share's router loss is the whole layer's."""
    whole, params, _ = toy()
    x, _ = _normed_input()
    layer = _layer(params, "ssm")
    sizes = sizes_of(whole)
    h = reference._norm(x, layer["mlp_norm"], sizes["norm_eps"])
    with jax.default_matmul_precision("highest"):
        want, balance = reference.expert_sublayer(
            h.reshape(B * S, -1), layer["moe"], sizes, block=36)
        routed, _ = reference.expert_sublayer(
            h.reshape(B * S, -1), layer["moe"], sizes, block=36,
            shared=False)
        total = want - routed                     # the shared expert, once
        for r in range(SHARES):
            share = toy_model(**share_of(r))
            mine = _layer(take_share(params, whole.config, share.config),
                          "ssm")
            y, (aux, over) = jax.jit(functools.partial(
                gh._fed, config=share.config, train=True, rng=None))(
                    x, mine)
            assert int(over[0]) == 0    # the rows over; then the load
            assert float(aux) == pytest.approx(
                sizes["aux_loss_coef"] * float(balance), rel=1e-5)
            branch = (y - x) / share.config.residual_multiplier
            total = total + branch.reshape(B * S, -1) - (want - routed)
    _close(total, want)


def test_the_attention_shares_add_up_to_the_uncut_mixer():
    """(b) the program's eight shares of the attention branch (two query
    heads and their key/value head each, ``W_o``'s rows for them) add up
    to the uncut reference's."""
    whole, params, _ = toy()
    x, seg = _normed_input()
    sizes = sizes_of(whole)
    layer = _layer(params, "attn")
    with jax.default_matmul_precision("highest"):
        want = reference.attention_mixer(
            reference._norm(x, layer["norm"], sizes["norm_eps"]), layer,
            sizes, seg)
        total = 0.0
        for r in range(SHARES):
            share = toy_model(**share_of(r)).config
            mine = _layer(take_share(params, whole.config, share), "attn")
            y = jax.jit(functools.partial(
                gh._mixed, config=share, kind="attn", segment_ids=seg))(
                    x, mine)
            total = total + (y - x) / share.residual_multiplier
    _close(total, want)


def test_the_mamba_shares_add_up_given_the_groups_statistic(capsys):
    """(c) the eight shares' gated ``y`` before the norm (two heads each,
    B and C whole on every share), joined, are the uncut layer's; with the
    uncut layer's mean square handed to the norm their ``W_out`` products
    add up to the uncut mixer — and a share that uses its own channels'
    statistic, as one chip without the exchange does, is the program's
    share; how far that moves a share's output is printed."""
    whole, params, _ = toy()
    x, seg = _normed_input()
    sizes = sizes_of(whole)
    layer = _layer(params, "ssm")
    h = reference._norm(x, layer["norm"], sizes["norm_eps"])
    with jax.default_matmul_precision("highest"):
        gated = reference.mamba_gated(h, layer, sizes, seg)
        want = reference.mamba_mixer(h, layer, sizes, seg)
        mean_square = jnp.mean(gated * gated, -1, keepdims=True)
        joined, total, moved = [], 0.0, []
        for r in range(SHARES):
            share = toy_model(**share_of(r))
            mine = _layer(take_share(params, whole.config, share.config),
                          "ssm")
            mine_sizes = sizes_of(share)
            joined.append(reference.mamba_gated(h, mine, mine_sizes, seg))
            given = reference.mamba_mixer(h, mine, mine_sizes, seg,
                                          mean_square=mean_square)
            own = reference.mamba_mixer(h, mine, mine_sizes, seg)
            total = total + given
            moved.append(float(jnp.linalg.norm(own - given)
                               / jnp.linalg.norm(given)))
            y = jax.jit(functools.partial(
                gh._mixed, config=share.config, kind="ssm",
                segment_ids=seg))(x, mine)
            _close((y - x) / share.config.residual_multiplier, own, 1e-4)
    _close(jnp.concatenate(joined, -1), gated)
    _close(total, want)
    with capsys.disabled():
        print(f"\ngranite share test: a share's own gated-norm statistic "
              f"(16 of 128 channels) moves its W_out product by "
              f"{min(moved):.3f}-{max(moved):.3f} of its norm (|own - "
              f"given|_2 / |given|_2 over {SHARES} shares, toy size)")
    assert 0.01 < min(moved) and max(moved) < 2.0


# ------------------------------------------------------- the rest of it
def test_zero3_and_streaming_refuse_clearly():
    model, params, mb = toy()
    with param_stream_scope(True, mode="gather"):
        with pytest.raises(NotImplementedError, match="ZeRO stage 0-2"):
            model.loss(params, mb)


@pytest.mark.parametrize("entry", ["init_cache_fn", "prefill_fn",
                                   "decode_fn", "verify_fn"])
def test_serving_entry_points_name_the_missing_piece(entry):
    with pytest.raises(NotImplementedError, match="recurrent state"):
        getattr(toy_model(), entry)(None, None, None)


@pytest.mark.parametrize("bad, words", [
    (dict(attn_heads_held=4, kv_heads_held=2), "key/value heads"),
    (dict(n_groups=2, mamba_heads_held=8), "one group"),
    (dict(layer_types=("mamba", "window")), "unknown"),
    (dict(num_layers=50), "layers for 50")])
def test_sizes_that_do_not_fit_are_refused_by_name(bad, words):
    with pytest.raises(ValueError, match=words):
        GraniteHybridConfig(**bad)


def test_the_size_is_the_published_one_and_the_cut_is_the_files():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    whole = GraniteHybridConfig()
    assert count_params(whole) == config["published"]["n_params"] \
        == 32_207_337_984
    assert whole.layer_kinds == "MMMMMAMMMM" * 4
    assert whole.pattern == ("ssm",) * 5 + ("attn",) + ("ssm",) * 4
    assert tuple(config["layer_types"]) == whole.layer_types
    assert (whole.d_inner, whole.conv_channels) == (8192, 8448)
    model = granite_hybrid_model(**config["builder"]["kwargs"])
    for key, want in config["model"].items():
        have = model.meta[key] if key == "n_params" \
            else getattr(model.config, key)
        assert have == want, key
    cut = model.config
    assert model.meta["n_params"] == 1_221_088_944
    assert (cut.layer_kinds, cut.d_inner, cut.conv_channels) \
        == ("MMMMMAMMMM", 1024, 1280)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ssm, attn = shapes["blocks"]["ssm"], shapes["blocks"]["attn"]
    assert ssm["w_in"].shape == (1, 9, 4096, 1024 + 1024 + 256 + 16)
    assert ssm["w_out"].shape == (1, 9, 1024, 4096)
    assert ssm["moe"]["router"].shape == (1, 9, 4096, 72)
    assert ssm["moe"]["w_gate"].shape == (1, 9, 9, 4096, 768)
    assert ssm["moe"]["shared_in"].shape == (1, 9, 4096, 1536)
    assert attn["wq"].shape == (1, 1, 4096, 512)
    assert attn["wk"].shape == (1, 1, 4096, 128)
    assert shapes["wte"].shape == (12544, 4096) and "lm_head" not in shapes
    # every key of the source's that the cut changed is listed, and no other
    published = {**config, **{k: v for k, v in config["published"].items()
                              if k not in ("what", "n_params")}}
    assert sorted(k for k in config["published"]
                  if k not in ("what", "n_params")) \
        == sorted(config["reduced"])
    assert published["mamba_n_heads"] * published["mamba_d_head"] \
        == published["mamba_expand"] * published["hidden_size"]
    assert replace(whole, num_layers=10).pattern == cut.pattern
