"""JoyAI-LLM-Flash's toy model (tests/test_joyai.py: the same sizes, seeded
weights, packed batch and reference) with each thing that makes the model
itself left out in turn: every departure outside the tolerance and the
control inside it.  The departures' patches are
tests/test_joyai_engine.py's; a file of its own so that ``--dist
loadfile`` gives the family's tests to three workers."""
import jax
import pytest

from tests.test_joyai import (  # noqa: F401 (the fixtures come by name)
    DOCS, LOSS_TOL, _isolation, jitted_reference_loss, seeded_toy,
    toy_model)
from tests.test_joyai_engine import (  # noqa: F401 (the fixtures come by name)
    DEPARTURES)


@pytest.mark.parametrize("left_out", sorted(DEPARTURES))
def test_a_departure_left_out_is_outside_the_tolerance(left_out,
                                                       monkeypatch):
    patch, overrides = DEPARTURES[left_out]
    # many short documents where the departure is at their boundaries
    docs = 14 if left_out == "module_loss_crossing_documents" else DOCS
    _, params, mb, want = seeded_toy(docs)
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
    model, params, mb, want = seeded_toy()
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL
    alone = toy_model(num_mtp_layers=0)
    main = {k: v for k, v in params.items() if k != "mtp"}
    want = float(jitted_reference_loss(alone)(main, mb))
    assert abs(float(jax.jit(alone.loss)(main, mb)) - want) < LOSS_TOL
