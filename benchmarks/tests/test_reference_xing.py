"""The plain Xing4.0 reference against models/xing.py at a tiny size,
float32, on the CPU (the gradients, the departures and the shares' sum are
tests/test_xing.py's, on this same file), and the controls its two
tolerances have to catch, for the main head and for the prediction
module's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.xing import xing_model
from references import xing as reference

TOY = dict(num_layers=3, num_dense_layers=1, d_model=64, num_heads=4,
           q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, rope_factor=8.0,
           original_max_position_embeddings=16, d_ff_dense=96, d_ff=32,
           shared_expert_d_ff=32, num_experts=16, top_k=4, experts_held=4,
           expert_offset=4, vocab_size=512, max_seq_len=128,
           dtype="float32")


def _setup(scale=1.0, **overrides):
    model = xing_model("4.0-29b-a4b", **{**TOY, **overrides})
    params = model.init(jax.random.PRNGKey(0))
    # the streams' leaves away from their start, so that the mixing is in
    # what is compared; the matrices times ``scale``
    key = jax.random.PRNGKey(1)

    def push(path, w):
        nonlocal key
        key, sub = jax.random.split(key)
        name = path[-1].key
        if name in ("alpha", "b_pre", "b_post", "b_res"):
            return jax.random.normal(sub, w.shape)
        if name == "phi":
            return jax.random.normal(sub, w.shape) / np.sqrt(w.shape[-2])
        return w * scale

    params = jax.tree_util.tree_map_with_path(push, params)
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 3, 48
    ids = rng.integers(0, 512, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 3)), axis=-1)
    cuts[0, 0] = (15, 16, 17)     # two one-token documents
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
    dict(experts_held=None, expert_offset=0), dict(num_mtp_layers=0),
    dict(hc_mult=2), dict(num_layers=4, num_dense_layers=2),
    dict(mscale=0.5, mscale_all_dim=2.0)],
    ids=["every_expert", "module_off", "two_streams", "two_leading_layers",
         "mscale_on_the_tables"])
def test_reference_matches_the_model_otherwise_built(overrides):
    model, params, data, sizes = _setup(**overrides)
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    assert abs(got - want) < 2e-5, (got, want)


@pytest.mark.parametrize("head", ["main", "mtp"])
def test_token_by_token_catches_fp8_and_not_bf16(head):
    """The control on what drivers/train_steps_counted.py compares for the
    main head, and what scripts/reference_control.py compares for the
    prediction module's: the scored positions' losses one by one, as the
    root of the mean squared difference, of the reference with every
    matrix product's operands (``r Phi`` among them) rounded to a lower
    precision.  bf16 is the engine's own arithmetic and has to stay inside
    TOKEN_NLL_RMS_ATOL; the next precision below, fp8 e4m3, has to land
    outside (on the chip at the cell's size: PERF.md section 2).  At toy
    size the weights are scaled up until the logits matter."""
    _, params, data, sizes = _setup(scale=2.5)
    micro = {k: v[0] for k, v in data.items()}
    per_token = {"main": reference.token_losses,
                 "mtp": reference.mtp_token_losses}[head]
    exact, scored = per_token(params, micro, sizes, chunk=1)

    def rms(dtype):
        got, _ = per_token(params, micro, sizes, chunk=1, matmul_dtype=dtype)
        return float(np.sqrt(np.mean(np.square(got - exact)[scored])))

    bf16, fp8 = rms(jnp.bfloat16), rms(jnp.float8_e4m3fn)
    assert bf16 < reference.TOKEN_NLL_RMS_ATOL < fp8, (bf16, fp8)


def test_the_mean_loss_keeps_bf16_inside():
    """LOSS_ATOL on the first step's mean loss: the bf16 control stays
    inside it (whether fp8 lands outside is the chip's reading: PERF.md
    section 2)."""
    _, params, data, sizes = _setup()
    exact = reference.step_loss(params, data, sizes, chunk=1)
    low = reference.step_loss(params, data, sizes, chunk=1,
                              matmul_dtype=jnp.bfloat16)
    assert 0 < abs(low - exact) < reference.LOSS_ATOL


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    source = inspect.getsource(reference)
    assert "deepspeed_tpu" not in source.replace(
        "``deepspeed_tpu", "").split('"""', 2)[2]
    assert "import jax" in source
