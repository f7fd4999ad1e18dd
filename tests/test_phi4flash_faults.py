"""Phi-4-mini-flash's toy model (tests/test_phi4flash.py: the same sizes,
seeded weights, packed batch and reference) with a fault planted in each
thing that makes the model itself: every fault outside 50 times the
tolerance the right model meets, and the control inside it.  A file of its
own so that ``--dist loadfile`` gives the family's tests to three
workers."""
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import phi4flash
from deepspeed_tpu.models.phi4flash import FULL, SWA, Phi4FlashConfig
from tests.test_phi4flash import LOSS_TOL, seeded_toy, toy_model


def _combine_with(**changes):
    """``_combine`` with one piece wrong: ``sign`` of lambda's term,
    ``layer`` whose lambda_init it takes, no ``subln``, no ``scale``."""
    def plant(monkeypatch):
        def combine(a1, a2, layer, config, index):
            f32 = lambda a: a.astype(jnp.float32)
            init = phi4flash.lambda_init(index + changes.get("layer", 0))
            lam = jnp.exp(jnp.sum(f32(layer["lambda_q1"])
                                  * f32(layer["lambda_k1"]))) \
                - jnp.exp(jnp.sum(f32(layer["lambda_q2"])
                                  * f32(layer["lambda_k2"]))) + init
            o = f32(a1) - changes.get("sign", 1.0) * lam * f32(a2)
            if changes.get("subln", True):
                o = phi4flash._rms_norm(o, f32(layer["subln"]),
                                        config.subln_eps)
            if changes.get("scale", True):
                o = o * (1.0 - init)
            return o.astype(a1.dtype)
        monkeypatch.setattr(phi4flash, "_combine", combine)
    return plant


def _value_halves_swapped(monkeypatch):
    def pairs(v, heads, hd):
        B, S, _ = v.shape
        v = v.reshape(B, S, heads // 2, 2, hd)
        return v[:, :, :, ::-1].reshape(B, S, heads // 2, 2 * hd)
    monkeypatch.setattr(phi4flash, "_value_pairs", pairs)


def _a_window_on_the_full_layer(monkeypatch):
    monkeypatch.setattr(
        phi4flash, "_window_of", lambda kind, config:
        config.sliding_window if kind in (SWA, FULL) else None)


def _gmu_fed_the_gated_y(monkeypatch):
    monkeypatch.setattr(phi4flash, "_memory", lambda y, gated: gated)


def _layer_handed_on(name, index):
    def plant(monkeypatch):
        monkeypatch.setattr(Phi4FlashConfig, name,
                            property(lambda self: index(self.num_layers)))
    return plant


def _state_not_reset(monkeypatch):
    real = phi4flash.selective_scan
    monkeypatch.setattr(
        phi4flash, "selective_scan",
        lambda u, dt, A, B, C, D, bias, segment_ids, **kw: real(
            u, dt, A, B, C, D, bias, None, **kw))


def _convolution_not_reset(monkeypatch):
    real = phi4flash.causal_conv
    monkeypatch.setattr(
        phi4flash, "causal_conv",
        lambda x, w, segment_ids, **kw: real(x, w, None, **kw))


#: fault -> (patch or None, builder overrides)
FAULTS = {
    "lambdas_sign": (_combine_with(sign=-1.0), {}),
    "lambda_init_of_the_next_layer": (_combine_with(layer=1), {}),
    "sub_norm_left_out": (_combine_with(subln=False), {}),
    "one_minus_lambda_init_left_out": (_combine_with(scale=False), {}),
    "value_halves_swapped": (_value_halves_swapped, {}),
    "window_one_key_short": (None, dict(sliding_window=15)),
    "window_one_key_long": (None, dict(sliding_window=17)),
    "a_window_on_the_full_layer": (_a_window_on_the_full_layer, {}),
    "gmu_fed_the_gated_y": (_gmu_fed_the_gated_y, {}),
    "m_from_two_layers_before": (
        _layer_handed_on("memory_layer", lambda L: L // 2 - 2), {}),
    "cross_layer_on_a_windowed_layers_keys": (
        _layer_handed_on("kv_layer", lambda L: L // 2 - 1), {}),
    "a_documents_state_not_reset": (_state_not_reset, {}),
    "a_documents_convolution_not_reset": (_convolution_not_reset, {}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_outside_the_tolerance(fault, monkeypatch):
    patch, overrides = FAULTS[fault]
    _, params, mb, want = seeded_toy()
    if patch:
        patch(monkeypatch)
    model = toy_model(**overrides)
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(model.loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_with_nothing_planted_the_same_comparison_holds():
    """The control of the test above: the same parameters and batch, no
    fault, inside the tolerance."""
    model, params, mb, want = seeded_toy()
    with jax.default_matmul_precision("highest"):
        assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL
