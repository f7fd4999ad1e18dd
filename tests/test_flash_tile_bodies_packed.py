"""The packed half of ``tests/test_flash_tile_bodies.py``'s 64 cases (tiles
of other documents beside the interior and boundary ones): the same test
by the same name on the same helpers, in a file of its own so that
``--dist loadfile`` gives the cases to two workers."""
from tests.test_flash_tile_bodies import (  # noqa: F401 (the fixture comes by name)
    hold_the_tile_body_to_the_parent, parent_kernels, tile_body_cases)


@tile_body_cases(packed=True)
def test_the_tile_body_is_the_parents_formula(packed, window, blocks, widths,
                                              rep, interpret_pallas,
                                              parent_kernels):
    hold_the_tile_body_to_the_parent(packed, window, blocks, widths, rep,
                                     parent_kernels)
