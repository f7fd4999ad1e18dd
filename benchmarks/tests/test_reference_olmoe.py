"""The plain OLMoE reference against models/mixtral.py at a tiny size,
float32, on the CPU (the engine, the gradients and each departure left out
are tests/test_olmoe.py's, on this same file), and the control its
tolerance has to catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.mixtral import mixtral_model
from references import olmoe as reference

TOY = dict(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, d_ff=64,
           num_experts=4, top_k=2, vocab_size=512, max_seq_len=128,
           dtype="float32", moe_dispatch="grouped")


def _setup(scale=1.0):
    model = mixtral_model("olmoe-1b-7b", **TOY)
    params = jax.tree.map(lambda a: a * scale,
                          model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 3, 64
    ids = rng.integers(0, 512, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.sort(rng.integers(1, seq, size=(gas, batch, 2)), axis=-1)
    data = {"input_ids": ids,
            "segment_ids": (np.arange(seq)[None, None, :, None]
                            >= cuts[:, :, None, :]).sum(-1).astype(np.int32)}
    c = model.config
    sizes = {k: getattr(c, k) for k in (
        "num_heads", "num_kv_heads", "head_dim", "num_experts", "top_k",
        "rms_norm_eps", "rope_theta", "aux_loss_coef",
        "router_z_loss_coef")}
    return model, params, data, sizes


@pytest.mark.parametrize("packed", [False, True])
def test_reference_matches_the_model(packed):
    model, params, data, sizes = _setup()
    if not packed:
        data = {"input_ids": data["input_ids"]}
    with jax.default_matmul_precision("highest"):
        want = np.mean([float(model.loss(
            params, {k: jnp.asarray(v[g]) for k, v in data.items()}))
            for g in range(2)])
    got = reference.step_loss(params, data, sizes, chunk=1)
    # float32 both sides; only the order of summation differs
    assert abs(got - want) < 2e-5, (got, want)


def test_tolerance_catches_the_precision_below_bf16_and_not_bf16():
    """The control (PERF.md section 2, PR 28): the reference with every
    matrix product's operands rounded to a lower precision.  The engine
    computes in bf16, so a bf16 control is the engine's own arithmetic and
    has to stay inside (on the chip at the cell's size it read 5.7e-4, the
    engine at most 5.3e-4); the next precision below, fp8 e4m3 with 4
    significant bits, has to land outside (1.9e-2 there).  At toy size so
    few tokens average so little that the weights are doubled to make the
    logits matter: bf16 then reads 1.5e-4 and fp8 8.1e-3."""
    _, params, data, sizes = _setup(scale=2.0)
    exact = reference.step_loss(params, data, sizes, chunk=1)
    bf16 = reference.step_loss(params, data, sizes, chunk=1,
                               matmul_dtype=jnp.bfloat16)
    fp8 = reference.step_loss(params, data, sizes, chunk=1,
                              matmul_dtype=jnp.float8_e4m3fn)
    assert abs(bf16 - exact) < reference.LOSS_ATOL < abs(fp8 - exact), \
        (bf16 - exact, fp8 - exact)
    assert 5.7e-4 < reference.LOSS_ATOL < 1.9e-2
