"""Attention dispatch: XLA einsum attention (always available) and the Pallas
flash-attention kernel on real TPU (reference capability: the fused attention in
csrc/transformer/*.cu and csrc/transformer/inference/csrc/softmax.cu, rebuilt as
TPU kernels rather than translated).
"""
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _kernel_needs_shard_map(q, impl: str) -> bool:
    """A Pallas kernel cannot be partitioned by GSPMD: where the local
    product may resolve to one and the mesh has more than one device, it
    runs per device inside sequence/layer.py's shard_map.  Serving's
    single-device programs shed the mesh together with the layout pins."""
    from deepspeed_tpu.comm.mesh import get_topology, pins_enabled
    return (get_topology().mesh.size > 1 and pins_enabled()
            and (impl == "flash" or (impl == "auto" and _on_tpu()
                                     and q.shape[1] >= 256)))


def xla_causal_attention(q, k, v, segment_ids=None, window=None):
    """Reference einsum attention with causal mask; [B, S, H, hd] layout
    (``v``, and then the result, may be of another width than ``q`` and
    ``k``; the scale is the score width's).  fp32 softmax accumulation for
    bf16 inputs.  ``segment_ids`` [B, S] restricts attention within packed
    segments; ``window`` to the last ``window`` keys, the query's own
    among them."""
    B, S, H, hd = q.shape
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))[None, None]
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((S, S), dtype=bool), -window)
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None]
                       == segment_ids[:, None, None, :])
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_causal_attention(q, k, v, segment_ids=None, fallback=True):
    """Pallas TPU flash attention (blockwise, never materialises the [S,S]
    scores in HBM).

    Kernel selection: the in-tree from-scratch FlashAttention-2 kernel
    (ops/pallas/ds_flash_attention) by DEFAULT — it beat the tuned stock
    wrapper 1.39x fwd+bwd at the 760M bench shape (B12 S1024 H16 hd96,
    3.92 ms vs 5.46 ms, PERF.md round-4 on-chip A/B) — with
    ``DS_FLASH_KERNEL=stock`` opting dense unpacked shapes back into the
    stock wrapper.  Packed batches (``segment_ids``) always need the
    from-scratch kernel (only it supports segments).  Dense shapes the
    kernel cannot take (VMEM budget, non-decomposing S) degrade to the
    stock wrapper, then to the exact XLA einsum; with ``fallback=False``
    (the explicit ``impl="flash"`` contract) they raise instead."""
    import os
    prefer_stock = os.environ.get(
        "DS_FLASH_KERNEL", "").lower() == "stock"
    if segment_ids is not None or not prefer_stock:
        from deepspeed_tpu.ops.pallas.ds_flash_attention import \
            ds_flash_attention
        vmem_ok = _ds_vmem_ok(q, segment_ids is not None, v)
        if not fallback and not vmem_ok:
            # explicit impl="flash" on a shape the VMEM heuristic rejects:
            # raise EAGERLY at trace time — under jit the Mosaic
            # scoped-VMEM failure happens at XLA compile time where no
            # except block here could wrap it, so a late opaque error is
            # the only alternative.  DS_FLASH_VMEM_MB is the escape hatch
            # for shapes the conservative margin mis-rejects.
            budget = _flash_vmem_budget_mib()
            raise ValueError(
                f"impl='flash': q shape {tuple(q.shape)} ({q.dtype}) "
                f"exceeds the flash kernel's VMEM budget "
                f"(DS_FLASH_VMEM_MB={budget} MiB; the check holds a "
                f"safety margin — raise it if this shape is known to "
                f"compile). Shorten the sequence or use impl='auto' for "
                f"the XLA fallback.")
        if vmem_ok:    # the eager guard makes not-fallback imply vmem_ok
            try:
                return ds_flash_attention(q, k, v, segment_ids=segment_ids,
                                          causal=True)
            except Exception as e:
                # the eager guard above means not-fallback implies vmem_ok
                if not fallback:
                    if isinstance(e, ValueError):
                        raise   # genuine shape error, already actionable
                    budget = _flash_vmem_budget_mib()
                    raise ValueError(
                        f"impl='flash': q shape {tuple(q.shape)} "
                        f"({q.dtype}) failed in the flash kernel despite "
                        f"passing the VMEM heuristic (budget "
                        f"DS_FLASH_VMEM_MB={budget} MiB). Lower the "
                        f"budget or use impl='auto' for the XLA "
                        f"fallback.") from e
                if not isinstance(e, ValueError):
                    raise       # fallback covers shape rejections only
        if segment_ids is not None:
            # only the ds kernel masks segments: exact XLA path
            return xla_causal_attention(q, k, v, segment_ids)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    try:
        return flash_attention(q, k, v, causal=True)
    except ValueError:
        if not fallback:
            raise
        # stock wrapper rejects the shape too: terminal exact einsum
        return xla_causal_attention(q, k, v)


def _flash_vmem_budget_mib() -> int:
    """The budget the VMEM check held a shape to (by device kind, or
    DS_FLASH_VMEM_MB), for the messages below."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import _vmem_budget
    return _vmem_budget() >> 20


def _ds_vmem_ok(q, packed=False, v=None) -> bool:
    """VMEM-budget check for the from-scratch kernel's whole-S staging; the
    eval_shape probe cannot see Mosaic VMEM exhaustion, so oversized shapes
    are routed to the XLA path here (loudly, once per shape class).  ``v``
    where its head width is not ``q``'s."""
    from deepspeed_tpu.ops.pallas.ds_flash_attention import vmem_fits
    key = ("vmem", q.shape[1], q.shape[3], q.dtype.itemsize, packed)
    dv = None
    if v is not None and v.shape[3] != q.shape[3]:
        dv = v.shape[3]
        key += (dv,)
    if key not in _FLASH_STATUS:
        _FLASH_STATUS[key] = vmem_fits(q.shape[1], q.shape[3],
                                       q.dtype.itemsize, packed=packed,
                                       v_head_dim=dv)
        if _FLASH_STATUS[key] is not True:
            from deepspeed_tpu.utils.logging import logger
            logger.warning(
                f"attention: ds flash kernel working set for S={q.shape[1]} "
                f"head_dim={q.shape[3]} {q.dtype} exceeds the VMEM budget — "
                "routing this shape away from the ds kernel (stock flash "
                "wrapper for dense batches, exact XLA einsum for packed) — "
                "raise DS_FLASH_VMEM_MB only if the target core has more "
                "VMEM")
    return _FLASH_STATUS[key] is True


_FLASH_STATUS = {}  # probe/guard result per shape-class key: True / message


def flash_status() -> dict:
    """What the ``impl="auto"`` ladder has decided so far, per shape class:
    True where a Pallas kernel was selected, the failure message where the
    ``[S,S]`` einsum took its place."""
    return dict(_FLASH_STATUS)


def _flash_usable(q, fn=None, k=None, ds=False, packed=False,
                  v=None) -> bool:
    """Probe the Pallas flash path once per shape class and remember the
    outcome.  A failure is logged loudly (never silently degraded — VERDICT
    round 1 flagged the silent except here) so a bench run on a slow fallback
    is visible in the logs.  ``ds=True`` marks fns that route to the
    from-scratch kernel, whose whole-S VMEM staging the eval_shape probe
    cannot vet — those get the budget check first.  ``v`` where its head
    width is not ``q``'s (only the from-scratch kernel takes that)."""
    from deepspeed_tpu.utils.logging import logger
    fn = fn or flash_causal_attention
    kv = vv = q if k is None else k
    if ds and not _ds_vmem_ok(q, packed=packed, v=v):
        return False
    key = (q.shape[1], q.shape[3], kv.shape[2],
           getattr(fn, "__name__", "bidirectional"))
    if v is not None and v.shape[3] != q.shape[3]:
        key += (v.shape[3],)
        vv = v
    if key not in _FLASH_STATUS:
        try:
            jax.eval_shape(fn, q, kv, vv)
            _FLASH_STATUS[key] = True
            logger.info(f"attention: Pallas flash selected for S={key[0]} "
                        f"head_dim={key[1]}")
        except Exception as e:  # trace-time failure: kernel unsupported here
            _FLASH_STATUS[key] = f"{type(e).__name__}: {e}"
            logger.warning(
                f"attention: Pallas flash UNAVAILABLE for S={key[0]} "
                f"head_dim={key[1]} — falling back to XLA einsum attention "
                f"(materialises [S,S] scores). Cause: {_FLASH_STATUS[key]}")
    return _FLASH_STATUS[key] is True


def _ds_gqa_causal(q, k, v):
    from deepspeed_tpu.ops.pallas.ds_flash_attention import \
        ds_flash_attention
    return ds_flash_attention(q, k, v, causal=True)


#: (block_q, block_k) of the windowed calls: with 512 x 512 a q-block of a
#: 512-key window visits two key tiles, 1,024 keys a query; chosen on the
#: chip at S 8192, 72 query heads to 8, window 512 (PERF.md section 6, PR 42)
WINDOW_BLOCKS = (512, 512)


def _local_causal_attention(q, k, v, impl: str = "auto", segment_ids=None,
                            window=None, kv_of=None):
    if window is not None and window >= q.shape[1]:
        window = None               # every earlier key: the causal program
    gqa = k.shape[2] != q.shape[2]
    # a value head narrower or wider than the score head: the from-scratch
    # kernel takes the two widths, the stock wrapper one — routed as GQA is
    two_widths = v.shape[3] != q.shape[3]
    if segment_ids is not None or window is not None:
        # packed sequences, a sliding window: only the from-scratch kernel
        # (GQA-native, segment-masked, its loops starting at the window's
        # first tile) or the exact einsum can honor the mask
        from deepspeed_tpu.ops.pallas.ds_flash_attention import \
            ds_flash_attention
        flash = partial(ds_flash_attention, segment_ids=segment_ids,
                        causal=True, kv_of=kv_of)
        if window is not None:
            flash = partial(flash, window=window, block_q=WINDOW_BLOCKS[0],
                            block_k=WINDOW_BLOCKS[1])
        if impl == "flash":
            # explicit request: no fallback — surface the real error
            return flash(q, k, v)
        if impl == "auto" and _on_tpu() and q.shape[1] >= 256 \
                and _ds_vmem_ok(q, packed=segment_ids is not None, v=v):
            try:
                return flash(q, k, v)
            except ValueError:
                from deepspeed_tpu.utils.logging import warning_once
                warning_once(
                    f"packed or windowed attention: S={q.shape[1]} does not "
                    "block-decompose for the flash kernel — exact einsum "
                    "fallback (materialises [S,S] scores)")
        if gqa:
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return xla_causal_attention(q, k, v, segment_ids, window)
    if impl == "flash":
        # explicit request: no fallback — surface the real error
        if gqa or two_widths:
            return _ds_gqa_causal(q, k, v)
        return flash_causal_attention(q, k, v, fallback=False)
    if impl == "auto" and _on_tpu() and q.shape[1] >= 256:
        if (gqa or two_widths) and _flash_usable(
                q, fn=_ds_gqa_causal, k=k, ds=True, v=v):
            # grouped-query: the from-scratch kernel reads each KV head
            # once per group instead of attending repeated copies
            return _ds_gqa_causal(q, k, v)
        # (two widths: no other kernel takes them — the einsum below)
        if gqa and not two_widths:
            # kernel unusable for this shape: repeat and try the tuned
            # stock wrapper before surrendering to the [S,S] einsum
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            gqa = False
        if not two_widths and _flash_usable(q):
            return flash_causal_attention(q, k, v)
    if gqa:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return xla_causal_attention(q, k, v)


def xla_bidirectional_attention(q, k, v, pad_mask=None):
    """Encoder (BERT-style) attention; optional key padding mask [B, S]
    (1 = real token).  fp32 softmax accumulation."""
    B, S, H, hd = q.shape
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if pad_mask is not None:
        scores = jnp.where(pad_mask[:, None, None, :].astype(bool), scores,
                           jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def bidirectional_attention(q, k, v, pad_mask=None, impl: str = "auto"):
    """q/k/v: [B, S, H, hd] -> [B, S, H, hd], no causal mask.

    Unpadded batches (``pad_mask=None``) ride the Pallas flash kernel on
    TPU at S>=256.  A padding mask maps onto the from-scratch kernel's
    segment ids (real tokens segment 1, pads segment 0 — pads only see
    pads, whose outputs are discarded), so padded encoder batches get the
    flash path too; sequence lengths that do not block-decompose fall back
    to the exact XLA path.  On a multi-device mesh a kernel runs per
    device inside the same shard_map as ``causal_attention``'s.
    """
    if _kernel_needs_shard_map(q, impl):
        from deepspeed_tpu.sequence.layer import distributed_attention
        return distributed_attention(
            q, k, v,
            lambda a, b, c, m=None: _local_bidirectional_attention(
                a, b, c, m, impl),
            segment_ids=pad_mask)
    return _local_bidirectional_attention(q, k, v, pad_mask, impl)


def _local_bidirectional_attention(q, k, v, pad_mask, impl):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    noncausal = partial(flash_attention, causal=False)

    def flash_padded(a, b, c):
        from deepspeed_tpu.ops.pallas.ds_flash_attention import \
            ds_flash_attention
        return ds_flash_attention(a, b, c, segment_ids=pad_mask,
                                  causal=False)

    if impl == "flash":
        if pad_mask is not None:
            return flash_padded(q, k, v)
        # explicit request: no fallback — surface the real error
        return noncausal(q, k, v)
    if impl == "auto" and _on_tpu() and q.shape[1] >= 256:
        if pad_mask is None and _flash_usable(q, fn=noncausal):
            return noncausal(q, k, v)
        # padded: probe the segment-capable kernel the same (loudly
        # logged) way the unpadded path probes the stock wrapper
        if pad_mask is not None and _flash_usable(q, fn=flash_padded,
                                                  ds=True, packed=True):
            return flash_padded(q, k, v)
    return xla_bidirectional_attention(q, k, v, pad_mask)


def causal_attention(q, k, v, impl: str = "auto", segment_ids=None,
                     window=None, kv_of=None):
    """q [B, S, H, hd], k/v [B, S, KV, hd] -> [B, S, H, hd]; KV may divide
    H (GQA — the from-scratch flash kernel attends compact KV natively,
    other paths repeat); ``v`` may be narrower than ``q`` and ``k``
    (latent attention) or wider (differential attention's pair of value
    heads), and the result is then ``v``'s width.  ``kv_of``: the layer
    whose keys and values these are where it is not the caller's own, for
    the flash calls' account (packed or windowed calls on one device).
    ``segment_ids`` [B, S] restricts attention within packed segments
    (models thread ``batch["segment_ids"]`` here; the from-scratch kernel
    masks natively, the einsum path exactly).  ``window``: query i attends
    keys j with ``i - j < window`` (None: all before it); the from-scratch
    kernel skips the tiles outside it, the einsum path masks them.

    When the mesh has an active ``seq`` axis, attention runs under Ulysses
    sequence parallelism (head-scatter all-to-all; see sequence/layer.py) —
    models get SP transparently.  Packed segments compose with Ulysses
    (the head-scattered local product sees the full sequence) but not
    with ring CP (block-granular masks only — rejected loudly).
    """
    from deepspeed_tpu.comm.mesh import get_topology, MODEL_AXIS, SEQ_AXIS
    topo = get_topology()
    sp = topo.mesh.shape[SEQ_AXIS]
    if sp > 1 and topo.sequence_parallel_impl == "ring":
        if window is not None:
            raise NotImplementedError(
                "a sliding window does not compose with ring context "
                "parallelism (the ring's chunks are whole blocks of keys) — "
                "use sequence_parallel_impl='ulysses'")
        if segment_ids is not None:
            raise NotImplementedError(
                "packed sequences (segment_ids) do not compose with ring "
                "context parallelism — use sequence_parallel_impl="
                "'ulysses' for packed batches")
        # ring CP (config mesh.sequence_parallel_impl="ring"): K/V blocks
        # rotate around the seq axis; the ring repeats compact KV itself
        # only in its dense fallback, but its shard_map spec expects
        # matching head counts — repeat here for GQA models
        if k.shape[2] != q.shape[2]:
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        from deepspeed_tpu.sequence.ring_attention import ring_attention
        # honor the caller's impl choice: "xla" means the exact einsum
        # path, which is the ring's "dense" chunk product
        return ring_attention(q, k, v, causal=True,
                              impl={"xla": "dense"}.get(impl, impl))
    if sp > 1 or _kernel_needs_shard_map(q, impl):
        # heads are split over model (and scattered over seq by Ulysses):
        # compact KV stays compact — 1/group the wire bytes — whenever the
        # KV heads divide over both; otherwise repeat first
        tp = topo.mesh.shape[MODEL_AXIS]
        if k.shape[2] != q.shape[2] and k.shape[2] % (sp * tp):
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        from deepspeed_tpu.sequence.layer import distributed_attention
        if segment_ids is not None:
            return distributed_attention(
                q, k, v,
                lambda a, b, c, seg: _local_causal_attention(
                    a, b, c, impl, seg, window, kv_of),
                segment_ids=segment_ids)
        return distributed_attention(
            q, k, v, lambda a, b, c: _local_causal_attention(
                a, b, c, impl, window=window, kv_of=kv_of))
    return _local_causal_attention(q, k, v, impl, segment_ids, window, kv_of)
