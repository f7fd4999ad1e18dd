"""Layer-streamed weight pass for NVMe-resident params (ISSUE 17).

The reference's ZeRO-Infinity trains a model whose fp16 params live on
NVMe by fetching each submodule's partition just in time
(``zero/partitioned_param_swapper.py`` + ``PartitionedParameterCoordinator``).
This module is that weight pass on the TPU stack: the model's stacked
block subtree never materializes — each layer's shard comes out of a
:class:`~deepspeed_tpu.offload.param_store.ParamStore` one at a time,
double-buffered (``get_layer(i, direction)`` submits the read for
``i±1`` before returning ``i``), runs through the model's per-layer
``block_fn``, and goes cold again.

Parity contract (the acceptance bar): the forward is the same op
sequence as the all-resident ``apply_fn`` — embed, L× block, head —
and the loss math below is an EXACT mirror of
``models.model._default_lm_loss`` (shift-by-one targets, fp32 CE,
``attention_mask``/``segment_ids`` masking, masked mean).  The backward
is a hand-rolled per-layer VJP chain over saved activations; gradient
values match the monolithic ``jax.grad`` up to floating-point
summation order (tied leaves such as GPT-2's ``wte`` accumulate their
embed- and head-side contributions in a fixed order here).  The
streamed path is dropout-free by construction: ``block_fn`` calls take
no rng, so models with stochastic blocks must not use it.

Memory shape: params are the streamed resource; activations are not —
the forward saves L+1 layer activations (O(L·B·S·D)) for the backward,
the standard trade until activation checkpointing is layered on top.
Per-layer gradients are pulled to host fp32 numpy as soon as each VJP
completes, so device/host never holds more than one layer's params +
grads beyond the ParamStore's K-layer working set.
"""
from typing import List

import numpy as np
import jax
import jax.numpy as jnp
import optax

__all__ = ["StreamedParamRunner", "uses_default_lm_loss",
           "lm_loss_from_logits"]


def uses_default_lm_loss(model) -> bool:
    """True when the model's loss is the stock causal-LM CE (the only
    loss the streamed head VJP reproduces bit-for-bit): the default one,
    or a family's own that says so (``loss_fn.causal_lm_loss``:
    ``models/gpt2.py`` takes that loss a chunk of tokens at a time)."""
    return "_default_lm_loss" in getattr(
        model.loss_fn, "__qualname__", "") or getattr(
            model.loss_fn, "causal_lm_loss", False)


def lm_loss_from_logits(logits, batch):
    """EXACT mirror of ``models.model._default_lm_loss`` from the point
    the logits exist — any drift here breaks the streamed-vs-resident
    parity test, on purpose."""
    tokens = batch["input_ids"]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    mask = batch.get("attention_mask")
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets)
    m = None
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
    seg = batch.get("segment_ids")
    if seg is not None:
        # packed sequences: the last token of one segment must not be
        # scored against the first token of the next
        same = (seg[:, 1:] == seg[:, :-1]).astype(jnp.float32)
        m = same if m is None else m * same
    if m is not None:
        return (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
    return losses.mean()


def _to_host_f32(tree):
    return jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a), np.float32), tree)


class StreamedParamRunner:
    """Forward/backward over a ParamStore-held block stack.

    ``nonblock`` below is the params tree *minus* the stacked
    ``blocks_key`` subtree — ``embed_fn``/``head_fn`` must only touch
    leaves outside the blocks (true of every pipeline-decomposed model;
    the blocks are by definition the streamed part)."""

    def __init__(self, model, num_layers: int, store):
        for attr in ("embed_fn", "block_fn", "head_fn"):
            if getattr(model, attr) is None:
                raise ValueError(
                    "offload_param.device=nvme needs a pipeline-decomposed "
                    f"model (missing Model.{attr}) — the streamed weight "
                    "pass runs layer by layer")
        self.model = model
        self.num_layers = int(num_layers)
        self.store = store
        self._embed = jax.jit(model.embed_fn)
        self._block = jax.jit(model.block_fn)

        def block_vjp(layer, x, ct):
            _, vjp = jax.vjp(model.block_fn, layer, x)
            return vjp(ct)
        self._block_vjp = jax.jit(block_vjp)

        def head_loss(nonblock, x, batch):
            return lm_loss_from_logits(model.head_fn(nonblock, x), batch)
        self._head_loss = jax.jit(head_loss)
        self._head_vg = jax.jit(jax.value_and_grad(head_loss,
                                                   argnums=(0, 1)))

        def embed_vjp(nonblock, batch, ct):
            _, vjp = jax.vjp(lambda nb: model.embed_fn(nb, batch), nonblock)
            return vjp(ct)[0]
        self._embed_vjp = jax.jit(embed_vjp)

    # ------------------------------------------------------------- forward
    def _forward(self, nonblock, batch) -> list:
        """Activation tape: [x0 (embed), x1, ..., xL].  Layer-k compute
        overlaps the layer-k+1 read via the store's double buffer."""
        x = self._embed(nonblock, batch)
        acts = [x]
        for i in range(self.num_layers):
            layer = self.store.get_layer(i, direction=+1)
            x = self._block(layer, x)
            acts.append(x)
        return acts

    def loss(self, nonblock, batch, rng=None):
        """Forward-only streamed loss (eval path)."""
        acts = self._forward(nonblock, batch)
        return self._head_loss(nonblock, acts[-1], batch)

    def logits(self, nonblock, batch):
        """Streamed logits (the serving cold-layer weight pass)."""
        acts = self._forward(nonblock, batch)
        return jax.jit(self.model.head_fn)(nonblock, acts[-1])

    # ------------------------------------------------------------ backward
    def loss_and_grads(self, nonblock, batch, rng=None):
        """One micro-batch: returns ``(loss, nonblock_grads,
        layer_grads)`` with grads as host fp32 numpy — ``layer_grads[i]``
        is layer-i's grad pytree (no leading L axis).  The backward
        sweep streams layers in reverse with ``direction=-1`` prefetch;
        tied nonblock leaves sum their head- and embed-side
        contributions."""
        acts = self._forward(nonblock, batch)
        loss, (g_nb, ct) = self._head_vg(nonblock, acts[-1], batch)
        layer_grads: List = [None] * self.num_layers
        for i in range(self.num_layers - 1, -1, -1):
            layer = self.store.get_layer(i, direction=-1)
            g_layer, ct = self._block_vjp(layer, acts[i], ct)
            acts[i + 1] = None              # tape entry consumed: free it
            layer_grads[i] = _to_host_f32(g_layer)
        g_embed = self._embed_vjp(nonblock, batch, ct)
        g_nonblock = jax.tree_util.tree_map(
            lambda a, b: a + b, _to_host_f32(g_nb), _to_host_f32(g_embed))
        return np.float32(jax.device_get(loss)), g_nonblock, layer_grads
