"""LoRA adapters over any engine Model (reference capability:
deepspeed/runtime/hybrid_engine.py:138-158 — the LoRA fuse/unfuse the RLHF
hybrid engine performs around generate; adapter maths per Hu et al. 2021).

TPU-native design: instead of the reference's in-place module surgery, the
wrapped Model's params tree is ``{"base": <frozen base>, "lora": {path:
{"a": A, "b": B}}}`` and every forward runs against ``merge(params)`` —
``W' = W + (alpha/r)·A@B`` computed inside jit, where XLA fuses the
rank-r outer product into the surrounding layout (no materialised weight
copy survives the fusion for the scanned stacked blocks).  The base
subtree is ``stop_gradient``-ed, so the backward pass never computes base
weight gradients, and ``trainable_mask`` excludes base from the optimizer
(zero update, zero moment memory).  A/B inherit the base leaf's logical
PartitionSpec on their preserved dimension, so TP/ZeRO shard adapters
exactly like the weights they decorate.

``fuse_fn`` materialises the merged base-shaped tree once — the hybrid
engine calls it at generate-rebind time so the KV-cache decode path runs
fused weights at full speed (one merge per policy update, not per token).
"""
from dataclasses import replace
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

DEFAULT_TARGETS: Tuple[str, ...] = ("qkv_w", "proj_w")


def _path_str(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in kp)


def _target_leaves(base_tree, targets):
    """[(path_str, leaf)] for every >=2-D leaf whose last path key is in
    ``targets``."""
    out = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(base_tree)[0]:
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name in targets and getattr(leaf, "ndim", 0) >= 2:
            out.append((_path_str(kp), leaf))
    return out


def init_lora_params(base_params, rank: int, targets=DEFAULT_TARGETS,
                     rng=None, dtype=None):
    """Fresh adapters for ``base_params``: A ~ N(0, 1/in_dim) (so the
    rank-r product starts variance-bounded), B = 0 — merged == base at
    step 0, the LoRA paper's init."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    lora = {}
    for path, leaf in _target_leaves(base_params, targets):
        *lead, d_in, d_out = leaf.shape
        dt = dtype or leaf.dtype
        rng, k = jax.random.split(rng)
        lora[path] = {
            "a": (jax.random.normal(k, (*lead, d_in, rank), dt)
                  * (d_in ** -0.5)),
            "b": jnp.zeros((*lead, rank, d_out), dt),
        }
    if not lora:
        raise ValueError(
            f"wrap_lora: no >=2-D param leaf named in {targets!r}")
    return lora


def merge_lora(base_params, lora_params, scale: float,
               freeze_base: bool = True):
    """Base-shaped tree with ``W + scale·A@B`` at adapter sites.  With
    ``freeze_base`` the base leaves are stop_gradient-ed (training);
    fuse_fn passes False so the merge is a pure function of the params."""
    def visit(kp, leaf):
        w = jax.lax.stop_gradient(leaf) if freeze_base else leaf
        ab = lora_params.get(_path_str(kp))
        if ab is None:
            return w
        prod = jnp.einsum("...ir,...ro->...io", ab["a"], ab["b"])
        return w + scale * prod.astype(w.dtype)

    return jax.tree_util.tree_map_with_path(visit, base_params)


def _map_paths(tree):
    return [(_path_str(kp), leaf)
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _ab_spec(spec, ndim) -> Tuple[P, P]:
    """Adapter specs from the decorated leaf's spec: A keeps the input
    dim's sharding, B the output dim's — rank stays replicated.  P() (the
    engine's replicated convention — None is an empty pytree to the spec
    machinery) when the leaf carries no spec."""
    if spec is None:
        return P(), P()
    t = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    lead, s_in, s_out = t[:-2], t[-2], t[-1]
    return P(*lead, s_in, None), P(*lead, None, s_out)


def wrap_lora(model, rank: int, alpha: Optional[float] = None,
              targets: Sequence[str] = DEFAULT_TARGETS):
    """Model -> Model whose params are ``{"base", "lora"}`` and whose
    forward/loss run merged weights with a frozen base.

    The wrapped model keeps the engine contract: ``init`` builds base +
    adapters, ``logical_specs``/``trainable_mask`` mirror the new tree,
    ``fuse_fn`` materialises merged weights for the inference view.  The
    pipeline decomposition (embed/block/head) is dropped — PP slices raw
    block params, which would bypass the merge; LoRA+PP is rejected
    loudly rather than silently unfused.
    """
    targets = tuple(targets)
    scale = (alpha if alpha is not None else float(rank)) / float(rank)

    def init_fn(rng):
        r1, r2 = jax.random.split(rng)
        base = model.init(r1)
        return {"base": base,
                "lora": init_lora_params(base, rank, targets, r2)}

    def merged(params):
        return merge_lora(params["base"], params["lora"], scale)

    def apply_fn(params, batch, rng=None):
        return model.apply_fn(merged(params), batch, rng)

    def loss_fn(params, batch, rng=None):
        return model.loss_fn(merged(params), batch, rng)

    def loss_with_counts_fn(params, batch, rng=None):
        return model.loss_with_counts_fn(merged(params), batch, rng)

    def fuse(params):
        """Merged base-shaped tree (reference _fuse_lora) — feed to the
        inference engine together with the UNWRAPPED model."""
        return merge_lora(params["base"], params["lora"], scale,
                          freeze_base=False)

    def specs_and_mask():
        base_specs = model.logical_specs
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        spec_of = dict(_map_paths(base_specs)) if base_specs else {}
        lora_specs, lora_mask = {}, {}
        for path, leaf in _target_leaves(shapes, targets):
            a_spec, b_spec = _ab_spec(spec_of.get(path), leaf.ndim)
            lora_specs[path] = {"a": a_spec, "b": b_spec}
            lora_mask[path] = {"a": True, "b": True}
        base_mask = jax.tree.map(lambda _: False, shapes)
        if base_specs is None:
            # spec-less (pure-DP) base: replicate it explicitly — a None
            # subtree is an EMPTY pytree to the spec machinery
            base_specs = jax.tree.map(lambda _: P(), shapes)
        specs = {"base": base_specs, "lora": lora_specs}
        mask = {"base": base_mask, "lora": lora_mask}
        return specs, mask

    specs, mask = specs_and_mask()
    wrapped = replace(
        model,
        init_fn=init_fn,
        numpy_init_fn=None, layer_init_fn=None, nonblock_init_fn=None,
        apply_fn=apply_fn, loss_fn=loss_fn,
        loss_with_counts_fn=loss_with_counts_fn
        if model.loss_with_counts_fn is not None else None,
        logical_specs=specs,
        trainable_mask=mask,
        fuse_fn=fuse,
        embed_fn=None, block_fn=None, head_fn=None,
        init_cache_fn=None, prefill_fn=None, decode_fn=None,
        meta={**model.meta, "lora": {"rank": rank, "alpha": alpha,
                                     "scale": scale, "targets": targets},
              "base_model": model},
    )
    return wrapped


def attach_lora_params(wrapped_model, base_params, rng=None):
    """Full params tree for a *pretrained* base: fresh adapters around the
    given base weights (the RLHF flow — policy starts from the SFT model)."""
    cfg = wrapped_model.meta["lora"]
    return {"base": base_params,
            "lora": init_lora_params(base_params, cfg["rank"],
                                     cfg["targets"], rng)}
