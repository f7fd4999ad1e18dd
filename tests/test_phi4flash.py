"""Phi-4-mini-flash (models/phi4flash.py) at toy size on the CPU against the
plain reference (benchmarks/references/phi4flash.py): loss and every
leaf's gradient on seeded weights at L = 8 and at L = 12 (two gated memory
units and two cross layers: the gradients into m, k, v are sums over their
readers), and the layout rule.  The planted faults are in
tests/test_phi4flash_faults.py, the engine and the accounts in
tests/test_phi4flash_engine.py."""
import functools
import os
import sys
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import phi4flash
from deepspeed_tpu.models.phi4flash import (CROSS, FULL, GMU, MAMBA, SWA,
                                            phi4flash_model)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from references import phi4flash as reference  # noqa: E402

B, S = 2, 64
#: document lengths of the two sequences: three and two documents, none
#: ending where a scan chunk (16) or the window (16) does
DOCS = ((20, 13, 31), (35, 29))
LOSS_TOL = 2e-5         # float32 on both sides; measured <= 1e-6
GRAD_TOL = 2e-3         # |a - b|_2 / |b|_2 a leaf; measured <= 4e-4


def toy_model(**overrides):
    return phi4flash_model("tiny", **{"dtype": "float32", "remat": True,
                                      **overrides})


def seeded_params(model, seed=1):
    """The model's own draw with every leaf moved, so that what starts at
    0 or 1 (biases, norm weights, D) or small (the lambda vectors) counts:
    a fault in any of them shows."""
    params = model.init(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             for leaf, k in zip(leaves, keys)]
    params = jax.tree.unflatten(treedef, moved)
    for layer in params["layers"].values():
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            if name in layer:
                layer[name] = layer[name] * 3.0
        if "w_o" in layer:
            # sharper maps that weigh more: one key more or less is seen
            for name in ("w_qkv", "w_q", "w_o"):
                if name in layer:
                    layer[name] = layer[name] * 3.0
        if "w_x" in layer:
            # a state that writes and reads more, and lasts: what crosses
            # a boundary that is not reset is then seen
            layer["w_x"] = layer["w_x"] * 4.0
            layer["dt_bias"] = layer["dt_bias"] - 1.0
    return params


def micro(seed=3, vocab=256, docs=DOCS):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, vocab)
    seg = np.stack([np.repeat(np.arange(len(d)), d) for d in docs])
    return {"input_ids": ids, "segment_ids": jnp.asarray(seg, jnp.int32)}


def reference_loss(model, grad=False):
    sizes = asdict(model.config)
    fn = lambda p, mb: reference.micro_batch_loss(
        p, mb["input_ids"], mb["segment_ids"], sizes)
    return jax.jit(jax.value_and_grad(fn) if grad else fn)


@functools.lru_cache(maxsize=None)
def seeded_toy():
    """(model, seeded weights, micro-batch, the reference's loss there),
    made once a process: the right side of every planted fault."""
    model = toy_model()
    params, mb = seeded_params(model), micro()
    with jax.default_matmul_precision("highest"):
        want = float(reference_loss(model)(params, mb))
    return model, params, mb, want


@pytest.mark.parametrize("layers", [8, 12])
def test_loss_and_gradients_match_the_reference(layers):
    model = toy_model(num_layers=layers)
    params, mb = seeded_params(model), micro()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, mb)
        want, want_grads = reference_loss(model, grad=True)(params, mb)
    assert abs(float(loss) - float(want)) < LOSS_TOL
    worst = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
        grads, want_grads)
    assert max(jax.tree.leaves(worst)) < GRAD_TOL, worst
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.abs(leaf).max()) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("layers, want", [
    (8, (MAMBA, SWA, MAMBA, SWA, MAMBA, FULL, GMU, CROSS)),
    (12, (MAMBA, SWA, MAMBA, SWA, MAMBA, SWA, MAMBA, FULL, GMU, CROSS, GMU,
          CROSS)),
])
def test_the_layout_is_the_depths_alone(layers, want):
    assert phi4flash.layer_kinds(layers) == want
    config = toy_model(num_layers=layers).config
    assert (config.memory_layer, config.kv_layer) \
        == (layers // 2, layers // 2 + 1)
    assert [reference.layer_kind(l, layers) for l in range(layers)] \
        == list(want)


def test_the_published_layout_and_count():
    kinds = phi4flash.layer_kinds(32)
    assert [kinds.count(k) for k in (MAMBA, SWA, FULL, GMU, CROSS)] \
        == [9, 8, 1, 7, 7]
    assert kinds[16] == MAMBA and kinds[17] == FULL and kinds[18] == GMU
    # the rule and the widths uncut against the model card's 3.8 B
    assert phi4flash.count_params(phi4flash.Phi4FlashConfig()) \
        == 3_852_562_944


@pytest.mark.parametrize("layers", [6, 4, 10])
def test_a_depth_the_rule_cannot_lay_out_is_refused(layers):
    with pytest.raises(ValueError, match="multiple of 4"):
        toy_model(num_layers=layers)


def test_a_parameter_tree_a_layer_and_the_tied_head():
    model = toy_model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params) == {"wte", "layers", "lnf_w", "lnf_b"}
    assert sorted(params["layers"]) == [f"{l:02d}" for l in range(8)]
    assert "w_q" in params["layers"]["07"] \
        and "w_qkv" not in params["layers"]["07"]
    assert {"w_1", "w_2"} <= set(params["layers"]["06"])
    assert {"A_log", "D", "dt_bias", "w_x", "w_dt", "conv_w"} \
        <= set(params["layers"]["04"])
    assert model.meta["n_params"] == sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(params))


@pytest.mark.parametrize("entry", ["init_cache_fn", "prefill_fn",
                                   "decode_fn", "verify_fn"])
def test_serving_entry_points_raise(entry):
    with pytest.raises(NotImplementedError, match="decode cache"):
        getattr(toy_model(), entry)()
