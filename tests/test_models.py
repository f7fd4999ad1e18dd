"""Model tests (reference pattern: tests/unit/ops numeric checks vs reference
implementations)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import tiny_gpt2, random_batch
from deepspeed_tpu.ops.attention import xla_causal_attention


def test_gpt2_forward_shape():
    m = tiny_gpt2()
    params = m.init(jax.random.PRNGKey(0))
    batch = random_batch(batch_size=2, seq_len=16)
    logits = m.apply(params, batch)
    assert logits.shape == (2, 16, 128)
    assert np.isfinite(np.asarray(logits)).all()


def test_gpt2_loss_near_uniform_at_init():
    m = tiny_gpt2()
    params = m.init(jax.random.PRNGKey(0))
    loss = float(m.loss(params, random_batch(batch_size=4, seq_len=32)))
    assert abs(loss - np.log(128)) < 0.5


def test_causality():
    """Changing a future token must not affect earlier logits."""
    m = tiny_gpt2()
    params = m.init(jax.random.PRNGKey(0))
    b1 = random_batch(batch_size=1, seq_len=16, seed=0)
    b2 = {"input_ids": b1["input_ids"].copy()}
    b2["input_ids"][0, -1] = (b2["input_ids"][0, -1] + 1) % 128
    l1 = np.asarray(m.apply(params, b1))
    l2 = np.asarray(m.apply(params, b2))
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
    assert not np.allclose(l1[0, -1], l2[0, -1])


def test_attention_causal_mask():
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (1, 8, 2, 4))
    out = xla_causal_attention(q, q, q)
    assert out.shape == (1, 8, 2, 4)
    # first position can only attend to itself -> output == v[0]
    np.testing.assert_allclose(np.asarray(out[0, 0]), np.asarray(q[0, 0]),
                               rtol=1e-5, atol=1e-6)


def test_param_count():
    from deepspeed_tpu.models.gpt2 import GPT2Config, count_params, init_params
    cfg = GPT2Config(vocab_size=128, max_seq_len=64, num_layers=2,
                     num_heads=4, d_model=32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(p.size for p in jax.tree.leaves(params))
    assert actual == count_params(cfg)


def test_remat_matches():
    m1 = tiny_gpt2(remat=False)
    m2 = tiny_gpt2(remat=True)
    params = m1.init(jax.random.PRNGKey(0))
    b = random_batch(batch_size=2, seq_len=16)
    l1 = float(m1.loss(params, b))
    l2 = float(m2.loss(params, b))
    assert abs(l1 - l2) < 1e-6


def test_numpy_init_matches_jax_init_distributions():
    """The host-side numpy initializer mirrors init_params: same tree
    structure/shapes/dtypes and matching per-leaf std within sampling
    error (it is the offload tier's fast init for billion-param models)."""
    import jax
    from deepspeed_tpu.models.gpt2 import (gpt2_model, numpy_init_params)
    model = gpt2_model("custom", vocab_size=512, max_seq_len=64,
                       num_layers=3, num_heads=4, d_model=64,
                       dtype="float32")
    jp = model.init(jax.random.PRNGKey(0))
    npp = numpy_init_params(model.config, seed=0)
    assert jax.tree.structure(jp) == jax.tree.structure(npp)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(jp)[0],
            jax.tree_util.tree_flatten_with_path(npp)[0]):
        assert a.shape == b.shape, path
        sa, sb = float(np.std(np.asarray(a))), float(np.std(b))
        assert abs(sa - sb) <= 0.1 * max(sa, sb, 1e-3), (path, sa, sb)


def test_neox_and_bloom_native_models_train(devices8):
    """The new native architectures (neox partial-rotary parallel-residual,
    bloom ALiBi) train through the engine like every other model."""
    import deepspeed_tpu
    from deepspeed_tpu.models import neox_model, bloom_model
    from tests.util import base_config
    rng = np.random.default_rng(0)
    from deepspeed_tpu.models.gptneo import gptneo_model
    for factory in (lambda: neox_model("tiny", attention_impl="xla"),
                    lambda: bloom_model("tiny"),
                    lambda: gptneo_model("tiny"),
                    lambda: neox_model("tiny", attention_impl="xla",
                                       rotary_interleaved=True,
                                       head_bias=True)):   # gpt-j form
        from deepspeed_tpu.comm import reset_topology
        reset_topology()
        engine, *_ = deepspeed_tpu.initialize(
            model=factory(), config=base_config(
                zero_optimization={"stage": 2}))
        losses = []
        for i in range(3):
            batch = {"input_ids": rng.integers(
                0, 256, size=(1, 8, 16), dtype=np.int32)}
            losses.append(float(engine.train_batch(batch=batch)))
        assert all(np.isfinite(losses))


# what the parent tree's four builders computed, each with its own copy of
# the arithmetic: (size, overrides) -> (n_params, active_params)
_A_SHARE = dict(experts_held=4, expert_offset=2)
HELD_SHARE_FAMILIES = {
    "qwen3_next": ("qwen3-next", [
        ("tiny", {}, 95216, 58352.0), ("tiny", _A_SHARE, 70640, 52208.0),
        ("80b-a3b", {}, 79674391296, 3874929408.0)]),
    "nemotron_h": ("nemotron-h", [
        ("tiny", {}, 55272, 34792.0), ("tiny", _A_SHARE, 47080, 32744.0),
        ("3-nano-30b-a3b", {}, 31577940288, 3227754816.0)]),
    "joyai": ("joyai", [
        ("tiny", {}, 86328, 58680.0), ("tiny", _A_SHARE, 67896, 54072.0),
        ("llm-flash", {}, 50190491648, 3382059008.0)]),
    "laguna": ("laguna", [
        ("tiny", {}, 116896, 71840.0), ("tiny", _A_SHARE, 92320, 65696.0),
        ("s-2.1", {}, 117561953280, 8140950528.0)]),
}


@pytest.mark.parametrize("family", sorted(HELD_SHARE_FAMILIES))
def test_a_held_share_family_counts_and_words_as_one(family):
    """The four families whose ``Model`` is ``held_share_model``'s: the
    counts are the ones each computed for itself before (tiny, a share of
    tiny, the published size), ``count_params`` is ``meta["n_params"]``,
    and the warning for rows over the bound names ``held_rows_factor``.
    The counts leave the step whatever is held: whether rows are bounded
    is the mesh's to say as well (an ``expert`` axis exchanges them inside
    a bound), after the model is built."""
    import importlib
    from deepspeed_tpu.moe.layer import ROWS_OVER_BOUND
    module = importlib.import_module(f"deepspeed_tpu.models.{family}")
    name, sizes = HELD_SHARE_FAMILIES[family]
    for size, overrides, n_params, active in sizes:
        model = getattr(module, family + "_model")(size, **overrides)
        assert model.meta["name"] == f"{name}-{size}"
        assert model.meta["n_params"] == n_params \
            == module.count_params(model.config)
        assert model.meta["active_params"] == active
        assert model.flops_per_token == 6.0 * active
        assert "held_rows_factor times" in \
            model.meta["step_counts"][ROWS_OVER_BOUND]
        assert model.loss_with_counts_fn is not None
