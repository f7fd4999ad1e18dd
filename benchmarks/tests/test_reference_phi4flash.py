"""The plain Phi-4-mini-flash reference against models/phi4flash.py at a
tiny size, float32, on the CPU (the gradients, the planted faults, the
engine and the operator's own tests are tests/test_phi4flash*.py's and
tests/test_selective_scan.py's, on this same file), and the controls its two
tolerances have to catch."""
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.phi4flash import phi4flash_model
from references import phi4flash as reference


def _setup(scale=1.0, **overrides):
    model = phi4flash_model("tiny", **{"dtype": "float32", **overrides})
    keep = ("A_log", "dt_bias", "D")
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in keep
        or path[-1].key.startswith(("ln", "subln", "lambda")) else a * scale,
        model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 2, 72
    ids = rng.integers(0, 256, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 3)), axis=-1)
    cuts[0, 0] = (15, 16, 17)     # a one-token document inside a chunk
    cuts[0, 1] = (16, 32, 48)     # boundaries at the chunks' edges
    data = {"input_ids": ids,
            "segment_ids": (np.arange(seq)[None, None, :, None]
                            >= cuts[:, :, None, :]).sum(-1).astype(np.int32)}
    return model, params, data, asdict(model.config)


def _model_loss(model, params, data):
    with jax.default_matmul_precision("highest"):
        return np.mean([float(model.loss(
            params, {k: jnp.asarray(v[g]) for k, v in data.items()}))
            for g in range(2)])


@pytest.mark.parametrize("packed", [False, True])
def test_reference_matches_the_model(packed):
    model, params, data, sizes = _setup()
    if not packed:
        data = {"input_ids": data["input_ids"]}
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    # float32 both sides; the chunked scan against the per-token one
    assert abs(got - want) < 2e-5, (got, want)


def test_token_by_token_catches_fp8_and_not_bf16():
    """The control (PERF.md section 2, PR 54) on what
    drivers/train_steps_counted.py compares: the scored positions' losses
    one by one, as the root of the mean squared difference, of the
    reference with every matrix product's operands rounded to a lower
    precision.  bf16 is the engine's own arithmetic and has to stay inside
    TOKEN_NLL_RMS_ATOL; the next precision below, fp8 e4m3, has to land
    outside.  At toy size the weights are scaled up until the logits
    matter.  The per-token losses' mean is step_loss's cross-entropy."""
    _, params, data, sizes = _setup(scale=6.0)
    micro = {k: v[0] for k, v in data.items()}
    exact, scored = reference.token_losses(params, micro, sizes, chunk=1)
    mean = reference.step_loss(
        params, {k: v[:1] for k, v in data.items()}, sizes, chunk=1)
    assert float(exact[scored].mean()) == pytest.approx(mean, abs=1e-5)

    def rms(dtype):
        got, _ = reference.token_losses(params, micro, sizes, chunk=1,
                                        matmul_dtype=dtype)
        return float(np.sqrt(np.mean(np.square(got - exact)[scored])))

    bf16, fp8 = rms(jnp.bfloat16), rms(jnp.float8_e4m3fn)
    assert bf16 < reference.TOKEN_NLL_RMS_ATOL < fp8, (bf16, fp8)


def test_the_mean_loss_keeps_bf16_inside():
    """LOSS_ATOL on the first step's mean loss: the bf16 control stays
    inside it (whether fp8 lands outside is the chip's reading: PERF.md
    section 2, PR 54)."""
    _, params, data, sizes = _setup()
    exact = reference.step_loss(params, data, sizes, chunk=1)
    bf16 = reference.step_loss(params, data, sizes, chunk=1,
                               matmul_dtype=jnp.bfloat16)
    assert abs(bf16 - exact) < reference.LOSS_ATOL, bf16 - exact


def test_the_rematerialised_form_is_the_same_arithmetic():
    """``remat=True`` (scripts/olmoe_grad_check.py: what the float32
    gradient at the published widths needs to fit one chip) keeps fewer
    values and computes the same loss and gradient."""
    _, params, data, sizes = _setup()
    ids, seg = (jnp.asarray(data[k][0]) for k in ("input_ids",
                                                  "segment_ids"))
    loss = lambda remat: jax.value_and_grad(
        lambda p: reference.micro_batch_loss(p, ids, seg, sizes,
                                             remat=remat))(params)
    with jax.default_matmul_precision("highest"):
        (plain, plain_g), (kept, kept_g) = loss(False), loss(True)
    assert float(plain) == pytest.approx(float(kept), abs=1e-6)
    for a, b in zip(jax.tree.leaves(plain_g), jax.tree.leaves(kept_g)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(
            jnp.abs(a).max()) + 1e-9)
