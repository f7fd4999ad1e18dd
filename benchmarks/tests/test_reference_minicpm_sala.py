"""The plain MiniCPM-SALA reference against models/minicpm_sala.py at a
tiny size, float32, on the CPU (the gradients, the selection against loops
and the attend stage are tests/test_minicpm_sala.py's, on this same file),
its selection against the program's, and the controls its limits have to
catch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.minicpm_sala import minicpm_sala_model
from deepspeed_tpu.ops.sparse_attention import select_blocks
from references import minicpm_sala as reference


def _setup(scale=1.0, **overrides):
    model = minicpm_sala_model("tiny", **{"dtype": "float32", **overrides})
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim < 2 or path[-1].key == "wte"
        else w * scale, params)
    rng = np.random.default_rng(0)
    gas, batch, seq = 2, 2, 64
    ids = rng.integers(0, 256, size=(gas, batch, seq), dtype=np.int32)
    cuts = np.array([[[5, 6, 47], [13, 14, 14]], [[41, 50, 63], [1, 9, 18]]])
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
    model, params, data, sizes = _setup(scale=5.0)
    if not packed:
        data = {"input_ids": data["input_ids"]}
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    assert abs(got - want) < 2e-5, (got, want)


@pytest.mark.parametrize("overrides", [
    dict(num_layers=2), dict(mixer_types=("lightning-attn", "minicpm4",
                                          "minicpm4", "lightning-attn")),
    dict(topk=8, window_size=16, dense_len=0)],
    ids=["two_layers", "another_order", "another_selection"])
def test_reference_matches_the_model_otherwise_built(overrides):
    model, params, data, sizes = _setup(scale=5.0, **overrides)
    got = reference.step_loss(params, data, sizes, chunk=1)
    want = _model_loss(model, params, data)
    assert abs(got - want) < 2e-5, (got, want)


def test_the_references_selection_is_the_programs():
    """``selection`` (what scripts/sparse_selection_check.py holds the
    program to on the chip) against ``select_blocks`` on the same float32
    q and k: every row."""
    model, params, data, sizes = _setup(scale=25.0)
    micro = {k: v[0] for k, v in data.items()}
    want = reference.selection(params, micro, sizes)
    cfg = model.config
    layer = params["layers"]["00"]
    with jax.default_matmul_precision("highest"):
        x = cfg.scale_emb * params["wte"][micro["input_ids"]]
        norm = lambda t, w: t * jax.lax.rsqrt(
            jnp.mean(t * t, -1, keepdims=True) + cfg.norm_eps) * w
        h = norm(x, layer["attn_norm"])
        q = norm((h @ layer["w_q"]).reshape(2, 64, 4, 16), layer["q_norm"])
        k = norm((h @ layer["w_k"]).reshape(2, 64, 2, 16), layer["k_norm"])
    got, _ = select_blocks(q, k, jnp.asarray(micro["segment_ids"]),
                           cfg.selection)
    assert want.shape == (2, 2, 64, 4)
    same = (np.asarray(got) == want).all(-1)
    assert same.mean() >= 0.99, same.mean()


def test_token_by_token_catches_fp8_and_not_bf16():
    """The control on what drivers/train_steps_counted.py compares: the
    scored positions' losses one by one, as the root of the mean squared
    difference, of the reference with every matrix product's operands (the
    selection's scores and what enters the recurrence among them) rounded
    to a lower precision.  bf16 is the engine's own arithmetic and has to
    stay inside TOKEN_NLL_RMS_ATOL; the next precision below, fp8 e4m3, has
    to land outside (on the chip at the cell's size: PERF.md section 2).
    At toy size the weights are scaled up until the logits matter."""
    _, params, data, sizes = _setup(scale=1.0)
    micro = {k: v[0] for k, v in data.items()}
    exact, scored = reference.token_losses(params, micro, sizes, chunk=1)

    def rms(dtype):
        got, _ = reference.token_losses(params, micro, sizes, chunk=1,
                                        matmul_dtype=dtype)
        return float(np.sqrt(np.mean(np.square(got - exact)[scored])))

    bf16, fp8 = rms(jnp.bfloat16), rms(jnp.float8_e4m3fn)
    assert bf16 < reference.TOKEN_NLL_RMS_ATOL < fp8, (bf16, fp8)


def test_a_lower_precision_moves_the_selection():
    """The selection is discrete: the fp8 control keeps other blocks than
    the float32 reference in far more rows than the bf16 one."""
    _, params, data, sizes = _setup(scale=25.0)
    micro = {k: v[1] for k, v in data.items()}
    exact = reference.selection(params, micro, sizes)
    share = lambda dtype: float((reference.selection(
        params, micro, sizes, matmul_dtype=dtype) == exact).all(-1).mean())
    bf16, fp8 = share(jnp.bfloat16), share(jnp.float8_e4m3fn)
    assert fp8 < bf16 <= 1.0, (bf16, fp8)


def test_the_mean_loss_keeps_bf16_inside():
    """LOSS_ATOL on the first step's mean loss: the bf16 control stays
    inside it (whether fp8 lands outside is the chip's reading: PERF.md
    section 2) — at the published d_model / dim_model_base of 16, as the
    configuration's rehearsal has it: the limit was set on logits that
    small."""
    _, params, data, sizes = _setup(dim_model_base=4)
    exact = reference.step_loss(params, data, sizes, chunk=1)
    low = reference.step_loss(params, data, sizes, chunk=1,
                              matmul_dtype=jnp.bfloat16)
    # (that the control rounds at all is the token test's to show)
    assert abs(low - exact) < reference.LOSS_ATOL


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    source = inspect.getsource(reference)
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert "import jax" in source
