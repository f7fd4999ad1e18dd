"""The plain Ouro reference against models/ouro.py at a tiny size, float32,
on the CPU (every gradient leaf, the four passes' logits, the exit masses,
the float64 numpy written out by hand and the six planted departures are
tests/test_ouro.py's, on this same file), and the controls its two
tolerances have to catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.ouro import ouro_model
from references import ouro as reference

TOY = dict(num_layers=3, total_ut_steps=4, d_model=64, num_heads=4,
           num_kv_heads=4, head_dim=16, d_ff=96, vocab_size=512,
           max_seq_len=128, dtype="float32")


def _setup(scale=1.0, **overrides):
    model = ouro_model("2.6b", **{**TOY, **overrides})
    params = jax.tree.map(lambda a: a * scale,
                          model.init(jax.random.PRNGKey(0)))
    # the gates off a half, so that the exit distribution matters
    params["exit_gate"] = {"w": params["exit_gate"]["w"] * 20.0,
                           "b": params["exit_gate"]["b"] - 0.3}
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 3, 48
    ids = rng.integers(0, 512, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 2)), axis=-1)
    cuts[0, 0] = (15, 16)         # a one-token document
    data = {"input_ids": ids,
            "segment_ids": (np.arange(seq)[None, None, :, None]
                            >= cuts[:, :, None, :]).sum(-1).astype(np.int32)}
    sizes = {k: getattr(model.config, k) for k in reference.SIZES}
    return model, params, data, sizes


def _model_loss(model, params, data):
    loss = jax.jit(model.loss)
    with jax.default_matmul_precision("highest"):
        return np.mean([float(loss(
            params, {k: jnp.asarray(v[g]) for k, v in data.items()}))
            for g in range(2)])


@pytest.mark.parametrize("packed", [False, True])
def test_reference_matches_the_model(packed):
    model, params, data, sizes = _setup()
    if not packed:
        data = {"input_ids": data["input_ids"]}
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    assert abs(got - want) < 2e-5, (got, want)


@pytest.mark.parametrize("overrides", [
    dict(total_ut_steps=1), dict(total_ut_steps=2, num_layers=5),
    dict(num_layers=1), dict(exit_entropy_beta=0.05)],
    ids=["one_pass", "two_passes_of_five", "one_layer", "beta_0.05"])
def test_reference_matches_the_model_otherwise_built(overrides):
    model, params, data, sizes = _setup(**overrides)
    got = reference.step_loss(params, data, sizes, chunk=1)
    assert abs(got - _model_loss(model, params, data)) < 2e-5


def test_one_pass_is_a_plain_decoder():
    """``total_ut_steps`` 1: the one pass takes all the mass, the entropy
    is zero and the loss is the one head's cross-entropy."""
    _, params, data, sizes = _setup(total_ut_steps=1)
    micro = {k: v[0] for k, v in data.items()}
    objectives, scored = reference.token_objectives(params, micro, sizes,
                                                    chunk=1)
    last, _ = reference.token_losses(params, micro, sizes, chunk=1)
    np.testing.assert_allclose(objectives[scored], last[scored], atol=1e-6)


def test_token_by_token_catches_fp8_and_not_bf16():
    """The control (PERF.md section 2, PR 70) on what
    drivers/train_steps_counted.py compares: the scored positions'
    last-pass losses one by one, as the root of the mean squared
    difference, of the reference with every matrix product's operands
    rounded to a lower precision.  bf16 is the engine's own arithmetic and
    has to stay inside TOKEN_NLL_RMS_ATOL; the next precision below, fp8
    e4m3, has to land outside.  At toy size the weights are scaled up until
    the logits matter.  The per-token objectives' mean is step_loss's."""
    _, params, data, sizes = _setup(scale=2.5)
    micro = {k: v[0] for k, v in data.items()}
    exact, scored = reference.token_losses(params, micro, sizes, chunk=1)
    objectives, _ = reference.token_objectives(params, micro, sizes, chunk=1)
    mean = reference.step_loss(
        params, {k: v[:1] for k, v in data.items()}, sizes, chunk=1)
    assert float(objectives[scored].mean()) == pytest.approx(mean, abs=1e-5)

    def rms(dtype):
        got, _ = reference.token_losses(params, micro, sizes, chunk=1,
                                        matmul_dtype=dtype)
        return float(np.sqrt(np.mean(np.square(got - exact)[scored])))

    bf16, fp8 = rms(jnp.bfloat16), rms(jnp.float8_e4m3fn)
    assert bf16 < reference.TOKEN_NLL_RMS_ATOL < fp8, (bf16, fp8)


def test_the_mean_loss_keeps_bf16_inside():
    """LOSS_ATOL on the first step's mean loss: the bf16 control stays
    inside it (whether fp8 lands outside is the chip's reading: PERF.md
    section 2, PR 70)."""
    _, params, data, sizes = _setup()
    exact = reference.step_loss(params, data, sizes, chunk=1)
    bf16 = reference.step_loss(params, data, sizes, chunk=1,
                               matmul_dtype=jnp.bfloat16)
    assert abs(bf16 - exact) < reference.LOSS_ATOL, bf16 - exact
