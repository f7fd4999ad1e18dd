"""The plain Nemotron-H reference against models/nemotron_h.py at a tiny
size, float32, on the CPU (the engine, the gradients, the departures, the
shares' sum and the operator's own tests are tests/test_nemotron_h.py's and
tests/test_state_space.py's, on this same file), and the controls its two
tolerances have to catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.nemotron_h import nemotron_h_model
from references import nemotron_h as reference

TOY = dict(num_layers=5, hybrid_override_pattern="MEM*E", d_model=64,
           num_heads=4, num_kv_heads=2, head_dim=32, mamba_num_heads=8,
           mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
           d_ff=32, shared_expert_d_ff=64, num_experts=16, top_k=4,
           experts_held=4, expert_offset=4, vocab_size=512, max_seq_len=128,
           dtype="float32")


def _setup(scale=1.0, **overrides):
    model = nemotron_h_model("3-nano-30b-a3b", **{**TOY, **overrides})
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in ("A_log", "dt_bias") else
        a * scale, model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 3, 72
    ids = rng.integers(0, 512, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 3)), axis=-1)
    cuts[0, 0] = (15, 16, 17)     # a one-token document inside a chunk
    cuts[0, 1] = (16, 32, 48)     # boundaries at the chunks' edges
    data = {"input_ids": ids,
            "segment_ids": (np.arange(seq)[None, None, :, None]
                            >= cuts[:, :, None, :]).sum(-1).astype(np.int32)}
    sizes = {k: getattr(model.config, k) for k in reference.SIZES}
    return model, params, data, sizes


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


def test_reference_matches_the_model_holding_every_expert():
    model, params, data, sizes = _setup(experts_held=None, expert_offset=0)
    got = reference.step_loss(params, data, sizes, chunk=1)
    assert abs(got - _model_loss(model, params, data)) < 2e-5


def test_token_by_token_catches_fp8_and_not_bf16():
    """The control (PERF.md section 2, PR 34) on what
    drivers/train_steps_counted.py compares: the scored positions' losses
    one by one, as the root of the mean squared difference, of the
    reference with every matrix product's operands rounded to a lower
    precision.  bf16 is the engine's own arithmetic and has to stay inside
    TOKEN_NLL_RMS_ATOL; the next precision below, fp8 e4m3, has to land
    outside.  At toy size the weights are scaled up until the logits
    matter.  The per-token losses' mean is step_loss's cross-entropy."""
    _, params, data, sizes = _setup(scale=4.0)
    micro = {k: v[0] for k, v in data.items()}
    exact, scored = reference.token_losses(params, micro, sizes, chunk=1)
    mean = reference.step_loss(
        params, {k: v[:1] for k, v in data.items()},
        {**sizes, "aux_loss_coef": 0.0}, chunk=1)
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
    section 2, PR 34)."""
    _, params, data, sizes = _setup()
    exact = reference.step_loss(params, data, sizes, chunk=1)
    bf16 = reference.step_loss(params, data, sizes, chunk=1,
                               matmul_dtype=jnp.bfloat16)
    assert abs(bf16 - exact) < reference.LOSS_ATOL, bf16 - exact
