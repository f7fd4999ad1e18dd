"""The causal convolution's kernels inside a whole step (tests/test_conv_kernels.py
has the kernels themselves, on the same helpers): a toy hybrid's step holds
them under full remat, and a toy engine's step names them under the conv
scope.  A file of its own so that ``--dist loadfile`` gives the kernels'
tests to two workers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import linear_attention as la
from deepspeed_tpu.telemetry import tracing

from tests.test_conv_kernels import (  # noqa: F401 (the fixtures come by name)
    HYBRIDS, _kernel_calls)


@pytest.mark.parametrize("family", sorted(HYBRIDS))
def test_a_toy_hybrids_step_holds_the_kernels_under_full_remat(
        family, monkeypatch):
    """The choice steered to interpret mode as it would fall on one TPU:
    under ``jax.checkpoint`` with nothing saved the gradient of a toy
    hybrid's loss holds the forward kernel twice (forward and recompute)
    and the backward once for each of a layer's three calls (q, k, v; x,
    B, C) and each such layer of the loop over periods (three of
    Qwen3-Next's four, one of ``ME``), the account says ``kernel`` with
    the family's orientation, and the loss is the XLA form's."""
    import importlib
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    preset, toy, positions, widths, layers = HYBRIDS[family]
    model = getattr(importlib.import_module(f"deepspeed_tpu.models.{family}"),
                    family + "_model")(preset, **toy)
    params = model.init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    mb = {"input_ids": jnp.asarray(rng.integers(0, 512, size=(2, 128),
                                                dtype=np.int32)),
          "segment_ids": jnp.asarray(np.stack([
              np.repeat([0, 1], [3, 125]), np.repeat([0, 1, 2], [64, 1, 63])
          ]).astype(np.int32))}
    rule = la._conv_blocking

    def steered(interpret):
        monkeypatch.setattr(
            la, "_conv_blocking",
            lambda asked, *a: rule(interpret if asked is None else asked, *a))
        loss = lambda p: model.loss(p, mb)
        with tracing.step_account("test/conv"):
            jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
        return float(jax.jit(loss)(params)), jaxpr, \
            tracing.conv_calls("test/conv")

    want, _, account = steered(False)
    assert [row["path"] for row in account] == ["xla"] * len(widths)
    got, jaxpr, account = steered(True)
    assert account == [{"batch": 2, "positions": 128, "channels": width,
                        "taps": 4, "orientation": positions,
                        "path": "kernel", "slab": min(width, 256),
                        "tile": 128} for width in widths]
    counts = _kernel_calls(jaxpr.jaxpr, {})
    assert (counts["ds_conv_fwd"], counts["ds_conv_bwd"]) \
        == (2 * 3 * layers, 3 * layers), counts
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_a_toy_engines_step_names_the_kernels_under_the_conv_scope(
        monkeypatch):
    """The toy Nemotron-H above through the engine, the choice steered to
    interpret mode: the step's account reads ``path: "kernel"`` and the
    step's map names both kernels under ``ssm/conv`` in the phases they
    run in (interpret mode leaves no Mosaic call, but the kernels' names
    are scopes of what it runs)."""
    import deepspeed_tpu
    from jax.experimental.compilation_cache import compilation_cache
    from deepspeed_tpu.models.nemotron_h import nemotron_h_model
    from tests.util import base_config
    monkeypatch.setenv("DS_GGEMM_INTERPRET", "1")
    preset, toy, _, _, _ = HYBRIDS["nemotron_h"]
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 512, size=(1, 2, 128),
                                       dtype=np.int32),
             "segment_ids": np.stack([np.repeat([0, 1], [3, 125]),
                                      np.repeat([0, 1, 2], [64, 1, 63])]
                                     ).astype(np.int32)[None]}
    rule = la._conv_blocking
    monkeypatch.setattr(
        la, "_conv_blocking",
        lambda asked, *a: rule(True if asked is None else asked, *a))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    tracing.reset_programs()
    try:
        engine, *_ = deepspeed_tpu.initialize(
            model=nemotron_h_model(preset, **toy),
            config=base_config(train_micro_batch_size_per_gpu=2,
                               gradient_accumulation_steps=1),
            mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",)))
        assert np.isfinite(float(engine.train_batch(batch=batch)))
        account = tracing.conv_calls("train/step")
        table = tracing.get_program_map("train/step")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        tracing.reset_programs()
    assert [row["path"] for row in account] == ["kernel"]
    seen = {(name, row["phase"]) for row in table.values()
            for name in ("ds_conv_fwd", "ds_conv_bwd")
            if "/ssm/conv/" in (row["scope"] or "")
            and f"/{name}/" in (row["scope"] or "")}
    assert seen >= {("ds_conv_fwd", "forward"), ("ds_conv_fwd", "recompute"),
                    ("ds_conv_bwd", "backward")}, seen
    assert ("ds_conv_fwd", "backward") not in seen
