"""Block-wise power-iteration eigenvalue estimation (reference:
deepspeed/runtime/eigenvalue.py — used to schedule MoQ quantization at
engine.py:2085).

Functional JAX version: estimates the top Hessian eigenvalue of the loss w.r.t.
a parameter subtree via power iteration on Hessian-vector products
(jvp-of-grad), fully jittable.
"""
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


class Eigenvalue:
    def __init__(self, verbose: bool = False, max_iter: int = 100,
                 tol: float = 1e-2, stability: float = 1e-6,
                 gas_boundary_resolution: int = 1,
                 layer_name: str = "", layer_num: int = 0):
        self.verbose = verbose
        self.max_iter = max_iter
        self.tol = tol
        self.stability = stability
        self.gas_boundary_resolution = gas_boundary_resolution

    def _normalize(self, v):
        norm = jnp.sqrt(sum(jnp.vdot(x, x).real
                            for x in jax.tree.leaves(v)))
        norm = jnp.maximum(norm, self.stability)
        return jax.tree.map(lambda x: x / norm, v), norm

    def compute_eigenvalue(self, loss_fn: Callable, params, rng=None):
        """Top eigenvalue of ∇²_params loss via power iteration with HVPs."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(rng, len(leaves))
        v = jax.tree_util.tree_unflatten(
            treedef, [jax.random.normal(k, l.shape, l.dtype)
                      for k, l in zip(keys, leaves)])
        v, _ = self._normalize(v)
        grad_fn = jax.grad(loss_fn)

        # compiled: a loss with a manual region over some of the mesh's
        # axes (the shared head's, over the data axes) has no eager form
        @jax.jit
        def hvp(params, vec):
            return jax.jvp(grad_fn, (params,), (vec,))[1]

        eig = jnp.float32(0.0)
        for _ in range(self.max_iter):
            hv = hvp(params, v)
            new_eig = sum(jnp.vdot(a, b).real for a, b in zip(
                jax.tree.leaves(v), jax.tree.leaves(hv)))
            v, _ = self._normalize(hv)
            if abs(float(new_eig) - float(eig)) < self.tol * max(
                    abs(float(new_eig)), 1e-12):
                eig = new_eig
                break
            eig = new_eig
        return float(eig)
