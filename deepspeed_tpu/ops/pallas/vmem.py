"""The VMEM a Mosaic call's buffers may take, by device kind: one table and
one rule for every kernel that sizes its blocks by it (``grouped_gemm``,
``ds_flash_attention``), and the rule by which a call with an XLA form
beside its kernels takes one or the other (:func:`lowering`); and what
XLA's own fusions keep there of one array (``XLA_KEEPS``: the width of the
head's chunk, ``models/model.py head_chunk_tokens``).

A call that asks for nothing is granted 16 MiB; ``UNASKED`` is what its
buffers may fill of that, a quarter left for the compiler's own scratch.
On a device kind listed in ``BUDGET`` (by substring: a v5e reports "TPU v5
lite" and has 128 MiB) a call may plan for that much instead, and one whose
buffers pass ``UNASKED`` then asks for the budget plus ``HEADROOM``
(:func:`limit_for`); a call that always fitted asks for nothing and is the
call it was.
"""
from typing import Optional

import jax

BUDGET = (("v5 lite", 64 << 20),)
UNASKED = 12 << 20
HEADROOM = 32 << 20
#: bytes of one array that XLA's own fusions keep in VMEM and out of HBM, by
#: device kind: of a v5e's 128 MiB, float32 [1024, 25008] and [2048, 12544]
#: (98 MiB) stay, [2048, 16384] (128 MiB) does not (the compiled text's
#: memory space ``S(1)``; scripts/head_loss_table.py).  The first row is
#: also what a kind not listed is read as: the one chip this was read on.
XLA_KEEPS = (("v5 lite", 100 << 20),)


def device_kind() -> str:
    return str(jax.devices()[0].device_kind).lower()


def budget() -> int:
    kind = device_kind()
    return next((b for sub, b in BUDGET if sub in kind), UNASKED)


def xla_keeps() -> int:
    """:data:`XLA_KEEPS` of the chips of the mesh in use."""
    from deepspeed_tpu.comm.mesh import get_topology
    kind = str(get_topology().mesh.devices.flat[0].device_kind).lower()
    return next((b for sub, b in XLA_KEEPS if sub in kind), XLA_KEEPS[0][1])


def limit_for(need_bytes: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a call whose buffers take ``need_bytes``;
    None where they fit what a call is granted unasked."""
    if need_bytes <= UNASKED:
        return None
    return max(budget(), need_bytes) + HEADROOM


def call_on_one_device() -> bool:
    """Whether the call being traced runs on one device: the process has
    one, or the call lies in a manual region of its mesh (a ``shard_map``
    over every axis), where each device runs the call on what it holds.
    A Mosaic call has no partitioning rule, so anywhere else on a host of
    several devices it takes its XLA form."""
    if jax.device_count() == 1:
        return True
    from deepspeed_tpu.utils.jax_compat import get_abstract_mesh
    mesh = get_abstract_mesh()
    return bool(mesh.axis_names) \
        and frozenset(mesh.manual_axes) == frozenset(mesh.axis_names)


def lowering(interpret, supported: bool, blocking):
    """(the kernels' grid blocking or None, interpret) of a call that is
    one algorithm in two lowerings (the delta rule, the state-space scan,
    the causal convolution), chosen by what the call can observe: its
    Mosaic kernels on a TPU where the call is on one device
    (:func:`call_on_one_device`: no partitioning rule for these calls
    yet), for shapes they take (``supported``) and a working set
    (``blocking().vmem_bytes``) inside :func:`budget`; else (None) the
    XLA form.  ``interpret`` is the caller's: True runs the kernels in
    interpret mode wherever the shapes allow, False the XLA form."""
    if interpret is False or not supported:
        return None, False
    blocking = blocking()
    if interpret:
        return blocking, True
    from deepspeed_tpu.ops.attention import _on_tpu
    fits = (_on_tpu() and call_on_one_device()
            and blocking.vmem_bytes <= budget())
    return (blocking if fits else None), False
