"""The plain Kimi-Linear reference against models/kimi_linear.py at a tiny
size, float32, on the CPU (the gradients, the departures and the shares'
sum are tests/test_kimi_linear.py's, on this same file), the per-token
recurrence with a vector decay against one written out in numpy, and the
controls its two tolerances have to catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.kimi_linear import kimi_linear_model
from references import kimi_linear as reference

TOY = dict(num_layers=8, d_model=64, kda_num_heads=2, kda_head_dim=16,
           kda_gate_rank=8, delta_rule_chunk=16, num_heads=4,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, d_ff_dense=96, d_ff=32, shared_expert_d_ff=32,
           num_experts=16, top_k=4, experts_held=4, expert_offset=4,
           vocab_size=512, max_seq_len=128, dtype="float32")


def _setup(scale=1.0, **overrides):
    model = kimi_linear_model("48b-a3b", **{**TOY, **overrides})
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if path[-1].key in (
            "A_log", "dt_bias", "e_score_correction_bias") or w.ndim < 2
        else w * scale, params)
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
    model, params, data, sizes = _setup(scale=4.0)
    if not packed:
        data = {"input_ids": data["input_ids"]}
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    assert abs(got - want) < 2e-5, (got, want)


@pytest.mark.parametrize("overrides", [
    dict(experts_held=None, expert_offset=0), dict(num_layers=5),
    dict(num_layers=3)], ids=["every_expert", "a_tail_without_its_mla",
                              "kda_alone"])
def test_reference_matches_the_model_otherwise_built(overrides):
    model, params, data, sizes = _setup(scale=4.0, **overrides)
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    assert abs(got - want) < 2e-5, (got, want)


def test_the_walk_of_the_runs_is_the_layers_order():
    """``layers_in_order`` from ``layer_kinds`` alone: the published 27
    layers' 26 expert layers, each run's stacks read period by period."""
    kinds = "KKKM" * 6 + "KKM"
    blocks = {f"run{r}": {"kda": np.arange(p * k).reshape(p, k) + 100 * r,
                          "mla": np.arange(p).reshape(p, 1) + 100 * r + 50}
              for r, (p, k) in enumerate([(1, 2), (5, 3), (1, 2)])}
    walked = [(letter, int(leaf))
              for letter, leaf in reference.layers_in_order(blocks, kinds)]
    assert [letter for letter, _ in walked] == list(kinds[1:])
    assert walked[:3] == [("K", 0), ("K", 1), ("M", 50)]
    assert walked[3:11] == [("K", 100), ("K", 101), ("K", 102), ("M", 150),
                            ("K", 103), ("K", 104), ("K", 105), ("M", 151)]
    assert walked[-3:] == [("K", 200), ("K", 201), ("M", 250)]


def test_token_by_token_catches_fp8_and_not_bf16():
    """The control on what drivers/train_steps_counted.py compares: the
    scored positions' losses one by one, as the root of the mean squared
    difference, of the reference with every matrix product's operands (the
    recurrence's reads and writes among them) rounded to a lower
    precision.  bf16 is the engine's own arithmetic and has to stay inside
    TOKEN_NLL_RMS_ATOL; the next precision below, fp8 e4m3, has to land
    outside (on the chip at the cell's size: PERF.md section 2).  At toy
    size the weights are scaled up until the logits matter."""
    _, params, data, sizes = _setup(scale=2.0)
    micro = {k: v[0] for k, v in data.items()}
    exact, scored = reference.token_losses(params, micro, sizes, chunk=1)

    def rms(dtype):
        got, _ = reference.token_losses(params, micro, sizes, chunk=1,
                                        matmul_dtype=dtype)
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
