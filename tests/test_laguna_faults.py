"""Laguna's toy model (tests/test_laguna.py: the same sizes, seeded weights,
packed batch and reference) with a fault planted in each thing that makes
the model itself: every fault outside the tolerance and the control inside
it.  The faults' patches are tests/test_laguna_engine.py's; a file of its
own so that ``--dist loadfile`` gives the family's tests to three workers."""
import jax
import pytest

from tests.test_laguna import (  # noqa: F401 (the fixtures come by name)
    LOSS_TOL, _isolation, seeded_toy, toy_model)
from tests.test_laguna_engine import (  # noqa: F401 (the fixtures come by name)
    FAULTS)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_outside_the_tolerance(fault, monkeypatch):
    patch, overrides = FAULTS[fault]
    _, params, mb, want = seeded_toy()
    if patch:
        patch(monkeypatch)
    model = toy_model(**overrides)
    got = float(jax.jit(model.loss)(params, mb))
    assert abs(got - want) > 50 * LOSS_TOL, (got, want)


def test_with_nothing_planted_the_same_comparison_holds():
    """The control of the test above: the same parameters and batch, no
    fault, inside the tolerance."""
    model, params, mb, want = seeded_toy()
    assert abs(float(jax.jit(model.loss)(params, mb)) - want) < LOSS_TOL
