"""Nemotron-H's toy model (tests/test_nemotron_h.py: the same sizes, seeded
weights, packed batch and reference) with each thing that makes the model
itself left out in turn: every departure outside the tolerance, the
control inside it, and two repeats of the layer pattern.  A file of its
own so that ``--dist loadfile`` gives the family's tests to three workers."""
import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import nemotron_h
from deepspeed_tpu.models.llama import rope
from deepspeed_tpu.models.nemotron_h import NemotronHConfig
from deepspeed_tpu.moe import layer as moe_layer
from deepspeed_tpu.moe import sharded_moe

from tests.test_nemotron_h import (  # noqa: F401 (the fixtures come by name)
    LOSS_TOL, micro, packed_batch, real_kernels, reference_loss,
    reference_loss_without_reset, reference_numbers, seeded_params,
    sizes_of, toy, toy_model)


# ----------------------------------------------- what makes it this model
def _with_moe(monkeypatch, **changes):
    explicit = NemotronHConfig.moe.fget
    monkeypatch.setattr(NemotronHConfig, "moe", property(
        lambda self: replace(explicit(self), **changes)))


def _rotary_added(monkeypatch):
    attend = nemotron_h.causal_attention
    monkeypatch.setattr(
        nemotron_h, "causal_attention", lambda q, k, v, **kw: attend(
            rope(q, 10000.0), rope(k, 10000.0), v, **kw))


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


def _norm_before_the_gate(monkeypatch):
    def wrong(y, z, w, groups, eps):
        shape = y.shape[:-1] + (groups, y.shape[-1] // groups)
        normed = nemotron_h._rms_norm(y.reshape(shape),
                                      w.reshape(shape[-2:]), eps)
        return normed.reshape(y.shape) * jax.nn.silu(z)
    monkeypatch.setattr(nemotron_h, "_gated_norm", wrong)


def _one_norm_over_all_channels(monkeypatch):
    right = nemotron_h._gated_norm
    monkeypatch.setattr(nemotron_h, "_gated_norm",
                        lambda y, z, w, groups, eps: right(y, z, w, 1, eps))


def _zeroed(name):
    return lambda params: jax.tree_util.tree_map_with_path(
        lambda path, w: w * 0 if path[-1].key == name else w, params)


#: name -> (what it does to the MODEL's side: a patch, overrides of the
#: builder, a change of the parameters the model is given).  The reference
#: keeps the equations; the loss then has to leave the tolerance.
DEPARTURES = {
    "rotary_added": (_rotary_added, {}, None),
    "softmax_for_sigmoid": (
        lambda mp: _with_moe(mp, router="softmax"), {}, None),
    "bias_added_to_the_weights": (_bias_in_the_weights, {}, None),
    "no_scaling_factor": (None, dict(routed_scaling_factor=1.0), None),
    "swiglu_for_relu2": (
        lambda mp: _with_moe(mp, activation="silu_glu"), {}, None),
    "norm_before_the_gate": (_norm_before_the_gate, {}, None),
    "one_norm_over_all_channels": (_one_norm_over_all_channels, {}, None),
    "no_skip_term": (None, {}, _zeroed("D")),
    "no_conv_bias": (None, {}, _zeroed("conv_b")),
    "no_document_reset": (None, {}, None),
}


@pytest.mark.parametrize("left_out", sorted(DEPARTURES))
def test_a_departure_left_out_is_outside_the_tolerance(left_out,
                                                       monkeypatch):
    patch, overrides, change = DEPARTURES[left_out]
    right, params, mb, loss_and_grads = toy()
    want = float(reference_numbers()[0])
    if left_out == "no_document_reset":
        # the model packed against the reference that never resets
        want = float(reference_loss_without_reset())
    if patch:
        patch(monkeypatch)
    if left_out == "swiglu_for_relu2":
        # its third matrices are leaves the reference does not read
        model = toy_model(**overrides)
        params = seeded_params(model)
        want = float(jax.jit(functools.partial(
            reference_loss, sizes=sizes_of(right)))(params, mb))
    if patch or overrides:
        got = float(jax.jit(toy_model(**overrides).loss)(params, mb))
    else:       # the model as it is, on other weights or the same
        got = float(loss_and_grads(change(params) if change else params,
                                   mb)[0])
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_with_nothing_left_out_the_same_comparison_holds():
    """The control of the test above: the same parameters and batch, no
    departure, inside the tolerance."""
    _, params, mb, loss_and_grads = toy()
    want = float(reference_numbers()[0])
    assert abs(float(loss_and_grads(params, mb)[0]) - want) < LOSS_TOL


def test_two_repeats_of_the_pattern_walk_two_stacks_deep():
    model = toy_model(num_layers=10)
    params, mb = seeded_params(model), micro(packed_batch())
    assert params["blocks"]["ssm"]["w_in"].shape[:2] == (2, 2)
    want = float(jax.jit(functools.partial(
        reference_loss, sizes=sizes_of(model)))(params, mb))
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL
