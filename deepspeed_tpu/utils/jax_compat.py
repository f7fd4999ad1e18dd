"""The jax names the package routes through one place.

Written for the one installation there is (jax 0.9.0): ``jax.shard_map``
with ``check_vma`` / ``axis_names``, ``lax.axis_size``,
``jax.sharding.get_abstract_mesh`` and ``jax.extend.core`` for the jaxpr
types.  Call sites import them from here so a future jax that moves one
is a one-file edit.
"""
from jax import shard_map  # noqa: F401
from jax.extend.core import ClosedJaxpr, Jaxpr  # noqa: F401
from jax.lax import axis_size  # noqa: F401
from jax.sharding import get_abstract_mesh  # noqa: F401
