"""The float32 half of ``tests/test_held_row_sum.py``'s 72 cases of the sum
against the scatter-add: the same test by the same name on the same
helpers, in a file of its own so that ``--dist loadfile`` gives the cases
to two workers."""
import jax.numpy as jnp

from tests.test_held_row_sum import (  # noqa: F401 (the fixture comes by name)
    kernel, sum_cases, the_kernel_sums_as_the_scatter_add)


@sum_cases(jnp.float32, "f32")
def test_the_kernel_sums_as_the_scatter_add(load, shape, dtype, kernel):
    the_kernel_sums_as_the_scatter_add(load, shape, dtype)
