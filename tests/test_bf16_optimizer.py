"""Mixed-precision optimizer states (runtime/bf16_optimizer.py — the
reference BF16_Optimizer capability re-designed as an HBM byte diet:
bf16 moments, Kahan-compensated bf16 masters, bf16 grad accumulation via
the reference's data_types.grad_accum_dtype key)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import deepspeed_tpu
from deepspeed_tpu.runtime.bf16_optimizer import mp_adamw
from tests.util import tiny_gpt2, base_config, random_batches


def _run(tx, params, grads_seq):
    state = tx.init(params)
    for g in grads_seq:
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
    return params


def test_fp32_mode_matches_optax_adamw():
    """With fp32 states the transform IS adamw (same math path)."""
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal((4,)), jnp.float32)}
    grads_seq = [jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape) * 0.1,
                              jnp.float32), params) for _ in range(5)]
    ours = _run(mp_adamw(1e-2, weight_decay=0.01), params, grads_seq)
    ref = _run(optax.adamw(1e-2, weight_decay=0.01), params, grads_seq)
    for k in params:
        np.testing.assert_allclose(ours[k], ref[k], rtol=1e-5, atol=1e-6)


def test_bf16_moments_track_fp32():
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)}
    grads_seq = [{"w": jnp.asarray(rng.standard_normal((16, 8)) * 0.1,
                                   jnp.float32)} for _ in range(10)]
    lo = _run(mp_adamw(1e-2, mu_dtype="bfloat16", nu_dtype="bfloat16"),
              params, grads_seq)
    hi = _run(mp_adamw(1e-2), params, grads_seq)
    # moments lose mantissa, not training signal: updates stay close
    np.testing.assert_allclose(lo["w"], hi["w"], rtol=0.02, atol=2e-4)


@pytest.mark.parametrize("entry,shape", [
    ("optax", (128,)), ("in_place", (128,)), ("in_place", (2, 16, 128))],
    ids=["optax", "in_place", "in_place_stacked"])
def test_kahan_master_accumulates_tiny_updates(entry, shape):
    """THE bf16-master failure mode: per-step updates below bf16 resolution
    silently vanish without compensation.  Kahan must accumulate them —
    through ``update`` + ``apply_updates`` and through ``update_in_place``,
    a vector left to XLA and a stacked leaf behind its barrier."""
    p0 = jnp.full(shape, 1.0, jnp.bfloat16)
    # constant gradient -> adam steps converge to -lr (sign(g) like);
    # pick lr so each step (~1e-4) is far below bf16 ulp at 1.0 (~7.8e-3)
    g = {"w": jnp.full(shape, 1e-3, jnp.float32)}
    steps = 200

    tx = mp_adamw(1e-4, master_dtype="bfloat16")
    params = {"w": p0}
    state = tx.init(params)

    @jax.jit
    def step(state, params):
        if entry == "optax":
            updates, state = tx.update(g, state, params)
            return optax.apply_updates(params, updates), state
        return tx.update_in_place(g, state, params)[:2]

    for _ in range(steps):
        params, state = step(state, params)
    moved = float(np.mean(np.asarray(params["w"], np.float32)))

    # plain bf16 adam (no compensation): the same trajectory stalls at 1.0
    plain = {"w": p0}
    ptx = optax.adam(1e-4)
    pstate = ptx.init(jax.tree.map(lambda x: x.astype(jnp.float32), plain))
    pw = plain["w"]
    for _ in range(steps):
        upd, pstate = ptx.update(g, pstate)
        pw = (pw.astype(jnp.float32) + upd["w"]).astype(jnp.bfloat16)
    stalled = float(np.mean(np.asarray(pw, np.float32)))

    # fp32 oracle
    otx = optax.adam(1e-4)
    ow = jnp.full(shape, 1.0, jnp.float32)
    ostate = otx.init({"w": ow})
    for _ in range(steps):
        upd, ostate = otx.update(g, ostate)
        ow = ow + upd["w"]
    oracle = float(np.mean(np.asarray(ow)))

    # oracle moves ~ -200*1e-4 = -0.02; Kahan must track it closely
    assert abs(moved - oracle) < 2e-3, (moved, oracle)
    # the uncompensated path visibly loses most of the motion...
    assert abs(stalled - oracle) > 3 * abs(moved - oracle), (stalled, oracle)


def test_engine_bf16_master_mode(devices8):
    """Engine wiring: bf16 Kahan masters + bf16 moments + bf16 grad accum
    train a tiny model to a loss trajectory near the fp32-master baseline,
    with the state dtypes actually lowered."""
    cfg_lo = base_config(
        bf16={"enabled": True, "master_weights_dtype": "bfloat16",
              "optimizer_states_dtype": "bfloat16"},
        data_types={"grad_accum_dtype": "bf16"},
        zero_optimization={"stage": 2})
    cfg_hi = base_config(bf16={"enabled": True},
                         zero_optimization={"stage": 2})
    lo, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg_lo)
    hi, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg_hi)

    assert jax.tree.leaves(lo.state["params"])[0].dtype == jnp.bfloat16
    assert jax.tree.leaves(hi.state["params"])[0].dtype == jnp.float32
    mu_leaf = jax.tree.leaves(lo.state["opt_state"])[1]
    assert any(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(lo.state["opt_state"])
               if l.ndim > 0)

    losses_lo, losses_hi = [], []
    for i in range(4):
        b = random_batches(1, batch_size=8, seed=100 + i)[0]
        batch = {"input_ids": b["input_ids"][None]}
        losses_lo.append(float(lo.train_batch(batch=batch)))
        losses_hi.append(float(hi.train_batch(batch=batch)))
    np.testing.assert_allclose(losses_lo, losses_hi, rtol=0.05)


def test_engine_bf16_master_checkpoint_roundtrip(devices8, tmp_path):
    cfg = base_config(
        bf16={"enabled": True, "master_weights_dtype": "bfloat16"},
        zero_optimization={"stage": 1})
    e1, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg)
    for i in range(2):
        b = random_batches(1, batch_size=8, seed=7 + i)[0]
        e1.train_batch(batch={"input_ids": b["input_ids"][None]})
    e1.save_checkpoint(str(tmp_path / "ck"))
    b = random_batches(1, batch_size=8, seed=55)[0]
    l_next = float(e1.train_batch(batch={"input_ids": b["input_ids"][None]}))

    e2, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=cfg)
    e2.load_checkpoint(str(tmp_path / "ck"))
    l_resume = float(e2.train_batch(batch={"input_ids": b["input_ids"][None]}))
    assert abs(l_next - l_resume) < 1e-5


def test_non_adam_rejects_state_dtypes(devices8):
    with pytest.raises(ValueError, match="Adam-family"):
        deepspeed_tpu.initialize(model=tiny_gpt2(), config=base_config(
            optimizer={"type": "Lamb", "params": {"lr": 1e-3}},
            bf16={"enabled": True, "optimizer_states_dtype": "bfloat16"}))


def test_user_optimizer_instance_rejects_state_dtypes(devices8):
    """A plain optax transform has no Kahan compensation; combining it
    with bf16 masters would silently drop sub-ulp updates — reject."""
    import optax
    with pytest.raises(ValueError, match="user-provided optimizer"):
        deepspeed_tpu.initialize(
            model=tiny_gpt2(), optimizer=optax.adamw(1e-3),
            config=base_config(
                bf16={"enabled": True,
                      "master_weights_dtype": "bfloat16"}))


def test_grad_accum_dtype_whitelist(devices8):
    with pytest.raises(ValueError, match="grad_accum_dtype"):
        deepspeed_tpu.initialize(model=tiny_gpt2(), config=base_config(
            data_types={"grad_accum_dtype": "fp17"}))


def test_state_dtypes_require_bf16_enabled(devices8):
    """The byte-diet dtypes are bf16-training features: without
    bf16.enabled they must reject loudly (matching the
    master_weights_dtype gate), not silently configure nothing."""
    with pytest.raises(ValueError, match="optimizer_states_dtype"):
        deepspeed_tpu.initialize(model=tiny_gpt2(), config=base_config(
            bf16={"enabled": False, "optimizer_states_dtype": "bfloat16"}))
    with pytest.raises(ValueError, match="grad_accum_dtype"):
        deepspeed_tpu.initialize(model=tiny_gpt2(), config=base_config(
            data_types={"grad_accum_dtype": "bf16"}))


def test_state_dtypes_accepted_with_bf16_enabled(devices8):
    """Gate's other branch: with bf16.enabled the same keys configure the
    engine (bf16 grad accumulation + bf16 moments)."""
    eng, *_ = deepspeed_tpu.initialize(model=tiny_gpt2(), config=base_config(
        bf16={"enabled": True, "optimizer_states_dtype": "bfloat16"},
        data_types={"grad_accum_dtype": "bf16"}))
    assert eng.grad_dtype == jnp.bfloat16
    assert eng._opt_states_dtype == "bfloat16"


def test_master_weights_dtype_requires_bf16_enabled(devices8):
    """All three byte-diet keys gate identically — the master dtype used
    to be silently ignored without bf16."""
    with pytest.raises(ValueError, match="master_weights_dtype"):
        deepspeed_tpu.initialize(model=tiny_gpt2(), config=base_config(
            bf16={"enabled": False, "master_weights_dtype": "bfloat16"}))
