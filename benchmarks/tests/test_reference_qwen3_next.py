"""The plain Qwen3-Next reference against models/qwen3_next.py at a tiny
size, float32, on the CPU (the engine, the gradients, the shares' sum and
the operator's own tests are tests/test_qwen3_next.py's and
tests/test_linear_attention.py's, on this same file), and the control its
tolerance has to catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.qwen3_next import qwen3_next_model
from references import qwen3_next as reference

TOY = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
           head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16, d_ff=32,
           shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
           expert_offset=4, vocab_size=512, max_seq_len=128,
           delta_rule_chunk=16, dtype="float32")


def _setup(scale=1.0, **overrides):
    model = qwen3_next_model("80b-a3b", **{**TOY, **overrides})
    params = jax.tree.map(lambda a: a * scale,
                          model.init(jax.random.PRNGKey(0)))
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


def test_tolerance_catches_the_precision_below_bf16_and_not_bf16():
    """The control (PERF.md section 2, PR 32): the reference with every
    matrix product's operands rounded to a lower precision.  bf16 is the
    engine's own arithmetic and has to stay inside the tolerance; the next
    precision below, fp8 e4m3, has to land outside.  At toy size so few
    tokens average so little that the weights are scaled up to make the
    logits matter."""
    _, params, data, sizes = _setup(scale=2.0)
    exact = reference.step_loss(params, data, sizes, chunk=1)
    bf16 = reference.step_loss(params, data, sizes, chunk=1,
                               matmul_dtype=jnp.bfloat16)
    fp8 = reference.step_loss(params, data, sizes, chunk=1,
                              matmul_dtype=jnp.float8_e4m3fn)
    assert abs(bf16 - exact) < reference.LOSS_ATOL < abs(fp8 - exact), \
        (bf16 - exact, fp8 - exact)


def test_token_by_token_catches_fp8_where_the_mean_does_not():
    """The same control on what drivers/train_steps_counted.py compares:
    the scored positions' losses one by one, as the root of the mean
    squared difference.  With the weights scaled so that the toy's
    rounding noise is the chip's (bf16 2.9e-2 here, 2.8e-2 to 3.5e-2
    there; fp8 0.41 here, 0.28 to 0.30 there: PERF.md section 2, PR 32)
    bf16 stays inside TOKEN_NLL_RMS_ATOL and fp8 lands outside — while at
    unscaled weights, as at the chip's initialisation in six seeds of ten,
    the mean over the tokens averages fp8's rounding away and LOSS_ATOL
    does not see it.  The per-token losses' mean is step_loss's
    cross-entropy."""
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
    _, params, data, sizes = _setup()
    fp8_mean = reference.step_loss(params, data, sizes, chunk=1,
                                   matmul_dtype=jnp.float8_e4m3fn) \
        - reference.step_loss(params, data, sizes, chunk=1)
    assert abs(fp8_mean) < reference.LOSS_ATOL      # the mean does not see it
