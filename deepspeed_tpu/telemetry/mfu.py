"""MFU / goodput accounting (ISSUE 4 tentpole).

Model FLOPs Utilization in the Megatron-LM sense: the model's useful
FLOPs per second (``flops_per_token × tokens / step_wall_clock`` for
training, or the XLA ``compiled_cost`` of the step) divided by the
hardware peak.  Peak FLOPs resolve per device kind from a small table
(bf16 dense peak per chip), overridable with ``DS_PEAK_FLOPS`` (per
device) for parts the table has not met — on CPU there is no meaningful
peak, so MFU reports only when the env var or the ``telemetry.
peak_flops`` config key supplies one.

Goodput is work that survived: for serving, tokens generated minus
tokens recomputed after preemption (recompute-on-resume re-prefilled
them); for training, steps not lost to a restart.
"""
import os
from typing import Optional

PEAK_FLOPS_ENV = "DS_PEAK_FLOPS"

#: dense bf16 peak FLOPs per chip by device-kind substring (lowercase).
#: Sources: published TPU system specs (per-chip, not per-core).  A v5e
#: reports ``device_kind == "TPU v5 lite"``; its 197 TFLOP/s bf16 is the
#: Google Cloud "TPU v5e" page's figure.
PEAK_FLOPS_BY_KIND = {
    "v5p": 459e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5litepod": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops_per_device(device=None, env: Optional[dict] = None
                          ) -> Optional[float]:
    """Peak FLOPs for one device: DS_PEAK_FLOPS env wins, then the
    device-kind table; None when unknown (CPU, exotic parts) — callers
    skip the MFU gauge rather than report against a made-up peak."""
    env = os.environ if env is None else env
    override = env.get(PEAK_FLOPS_ENV, "").strip()
    if override:
        return float(override)
    if device is None:
        import jax
        device = jax.local_devices()[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    for sub, peak in PEAK_FLOPS_BY_KIND.items():
        if sub in kind:
            return peak
    return None


def total_peak_flops(env: Optional[dict] = None) -> Optional[float]:
    """Aggregate peak across this process's local devices (per-host MFU:
    each host rates its own step against its own chips)."""
    import jax
    devs = jax.local_devices()
    per = peak_flops_per_device(devs[0], env=env)
    if per is None:
        return None
    return per * len(devs)


def mfu(model_flops: float, duration_s: float,
        peak_flops: float) -> Optional[float]:
    """Achieved / peak, as a fraction in [0, ~1].  None on degenerate
    inputs instead of inf/NaN leaking into a gauge."""
    if duration_s <= 0 or peak_flops <= 0 or model_flops < 0:
        return None
    return (model_flops / duration_s) / peak_flops


def tokens_per_second(tokens: float, duration_s: float) -> Optional[float]:
    if duration_s <= 0:
        return None
    return tokens / duration_s


def serving_goodput(useful_tokens: float, wasted_tokens: float) -> float:
    """Fraction of generated-token work that was not thrown away to
    preemption recompute.  1.0 when nothing was wasted (including the
    zero-work case — an idle server has not wasted anything)."""
    total = useful_tokens + wasted_tokens
    if total <= 0:
        return 1.0
    return useful_tokens / total
