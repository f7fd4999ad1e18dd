"""``mp_adamw``'s second entry, ``update_in_place``, against ``update`` +
``optax.apply_updates``: one function of a leaf, so the states agree to the
last bit; the sums it hands back are the step's ``global_norm`` /
``group_stats``; a leaf of three or more axes is taken behind an
``optimization_barrier`` (one pass over its operands on the chip: PERF.md
§3), a matrix and a vector are not."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.bf16_optimizer import mp_adamw
from deepspeed_tpu.telemetry import tracing

MODES = {          # master dtype, moments' dtype
    "bf16-kahan": ("bfloat16", "bfloat16"),
    "fp32-master": ("float32", "bfloat16"),
    "fp32-moments": ("bfloat16", None),
}
STEPS = 12


def _bits(tree):
    return [np.asarray(x.astype(jnp.float32)) for x in jax.tree.leaves(tree)]


def _assert_same_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
    for x, y in zip(_bits(a), _bits(b)):
        np.testing.assert_array_equal(x, y)


def _setup(shape, mode, seed=0):
    master, moments = MODES[mode]
    rng = np.random.default_rng(seed)
    params = {"w": jnp.asarray(rng.standard_normal(shape), jnp.dtype(master)),
              "b": jnp.asarray(rng.standard_normal(shape[-1:]),
                               jnp.dtype(master))}
    tx = mp_adamw(optax.linear_schedule(1e-2, 1e-3, STEPS), weight_decay=0.1,
                  mu_dtype=moments, nu_dtype=moments, master_dtype=master)
    grads = [jax.tree.map(lambda p: jnp.asarray(
        rng.standard_normal(p.shape) * 0.1, jnp.bfloat16), params)
        for _ in range(STEPS)]
    return tx, params, grads


def _optax_way(tx):
    @jax.jit
    def step(g, state, params):
        updates, state = tx.update(g, state, params)
        return optax.apply_updates(params, updates), state, updates
    return step


def _reference_sums(g, u, p):
    f64 = lambda x: np.asarray(x.astype(jnp.float32), np.float64)
    return (np.sum(f64(g) ** 2), int(np.sum(~np.isfinite(f64(g)))),
            np.sum(f64(u) ** 2), np.sum(f64(p) ** 2))


def _barriers(fn, *args):
    """The operand counts of the ``optimization_barrier``s ``fn`` traces."""
    return [len(eqn.invars) for eqn in jax.make_jaxpr(fn)(*args).eqns
            if eqn.primitive.name == "optimization_barrier"]


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("shape", [
    (1, 3, 2, 64, 256),      # five axes, as a stack of experts a layer
    (3, 32, 384),            # three
    (2, 128, 80),            # a minor axis that is not whole lanes
    (5, 16, 72),             # nor the second-minor whole tiles (a router)
    (96, 256),               # a matrix: no barrier
], ids=lambda s: "x".join(map(str, s)))
def test_in_place_equals_the_optax_entry_to_the_last_bit(shape, mode):
    tx, params, grads = _setup(shape, mode)
    optax_step, in_place = _optax_way(tx), jax.jit(tx.update_in_place)
    pa, sa = pb, sb = params, tx.init(params)
    for g in grads:
        before = pa
        pa, sa, updates = optax_step(g, sa, pa)
        pb, sb, sums = in_place(g, sb, pb)
        _assert_same_bits((pa, sa), (pb, sb))
    # the order is the flatten order of the tree: "b" (a vector), "w"
    for i, name in enumerate(sorted(params)):
        want = _reference_sums(g[name], updates[name], before[name])
        got = [float(col[i]) for col in sums]
        assert int(got[1]) == want[1] == 0
        np.testing.assert_allclose(got[0::2], want[0::2], rtol=2e-5)
    # the stacked leaf's five operands (four where float32 masters keep no
    # residual beside them: the placeholder is a scalar, and goes along)
    assert _barriers(tx.update_in_place, g, sb, pb) == (
        [5] if len(shape) >= 3 else [])


def test_sums_are_the_steps_norms():
    """The four sums against ``global_norm`` / ``group_stats`` as
    ``apply_grads`` forms them from the trees on the optax path."""
    from deepspeed_tpu.runtime.step_programs import global_norm
    from deepspeed_tpu.telemetry.numerics import group_stats, group_stats_of
    tx, params, grads = _setup((2, 3, 32, 256), "bf16-kahan", seed=2)
    state = tx.init(params)
    g = grads[0]
    updates, _ = tx.update(g, state, params)
    _, _, sums = jax.jit(tx.update_in_place)(g, state, params)
    np.testing.assert_allclose(jnp.sqrt(sum(sums.grad_sq)), global_norm(g),
                               rtol=5e-6)
    np.testing.assert_allclose(jnp.sqrt(sum(sums.update_sq)),
                               global_norm(updates), rtol=5e-6)
    np.testing.assert_allclose(jnp.sqrt(sum(sums.param_sq)),
                               global_norm(params), rtol=5e-6)
    norms, bad = group_stats_of(sums.grad_sq, sums.nonfinite, [0, 1], 2)
    want_norms, want_bad = group_stats(g, [0, 1], 2)
    np.testing.assert_allclose(norms, want_norms, rtol=5e-6)
    np.testing.assert_array_equal(bad, want_bad)


@pytest.mark.parametrize("leaf", ["w", "b"])
@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
def test_a_planted_nonfinite_gradient_is_counted_once(poison, leaf):
    tx, params, grads = _setup((3, 32, 256), "bf16-kahan", seed=3)
    at = (1, 17, 130) if leaf == "w" else (130,)
    g = dict(grads[0], **{leaf: grads[0][leaf].at[at].set(poison)})
    _, _, sums = jax.jit(tx.update_in_place)(g, tx.init(params), params)
    hit, clean = (1, 0) if leaf == "w" else (0, 1)
    assert [int(n) for n in sums.nonfinite][hit] == 1
    assert [int(n) for n in sums.nonfinite][clean] == 0
    assert not np.isfinite(float(sums.grad_sq[hit]))
    assert np.isfinite(float(sums.grad_sq[clean]))


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("layout", ["all_split", "state_split"])
def test_over_a_two_device_mesh(mode, layout):
    """A stacked leaf split over a mesh axis — with its state (ZeRO-3, the
    expert axis) or its state alone (ZeRO-1/2) — keeps its layout through
    the barrier and gives the one-device bits."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    tx, params, grads = _setup((4, 32, 256), mode, seed=4)
    optax_step, in_place = _optax_way(tx), jax.jit(tx.update_in_place)
    put = lambda tree, spec: jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(
            mesh, spec if x.ndim == 3 else P())), tree)
    pa, sa = params, tx.init(params)
    pb = put(pa, P("data") if layout == "all_split" else P())
    sb = put(sa, P("data"))
    for g in grads[:4]:
        before = pa
        pa, sa, updates = optax_step(g, sa, pa)
        pb, sb, sums = in_place(put(g, P("data")), sb, pb)
        _assert_same_bits((pa, sa), (pb, sb))
    assert sb.mu["w"].sharding.spec == P("data")
    want = _reference_sums(g["w"], updates["w"], before["w"])
    np.testing.assert_allclose([float(col[1]) for col in sums][0::2],
                               want[0::2], rtol=2e-5)


@pytest.mark.parametrize("shapes,stacked", [
    ({"w": (2, 16, 128), "b": (128,)}, ["w"]),
    ({"experts": (1, 3, 4, 64, 48), "router": (1, 3, 64, 4),
      "table": (512, 64)}, ["experts", "router"]),
    ({"wq": (64, 64), "wo": (64, 64), "norm": (64,)}, []),
], ids=["stack_and_vector", "expert_layers", "per_layer_matrices"])
def test_the_steps_account_says_which_leaves_are_stacked(shapes, stacked):
    tx = mp_adamw(1e-3, master_dtype="bfloat16", mu_dtype="bfloat16",
                  nu_dtype="bfloat16")
    params = {k: jnp.ones(s, jnp.bfloat16) for k, s in shapes.items()}
    with tracing.step_account("test/in_place"):
        jax.jit(tx.update_in_place)(params, tx.init(params), params)
    size = lambda names: sum(2 * int(np.prod(shapes[k])) for k in names)
    rest = [k for k in shapes if k not in stacked]
    assert tracing.optimizer_fused("test/in_place") == {
        "leaves": len(stacked), "param_bytes": size(stacked),
        "xla_leaves": len(rest), "xla_param_bytes": size(rest)}
    assert _barriers(tx.update_in_place, params, tx.init(params),
                     params) == [5] * len(stacked)


def test_the_optax_entry_has_no_barrier_and_no_account():
    """``update`` is what ``optax.chain`` / ``masked`` compose: every leaf
    left to XLA, nothing counted."""
    tx, params, grads = _setup((3, 32, 128), "bf16-kahan")
    state = tx.init(params)
    assert _barriers(tx.update, grads[0], state, params) == []
    with tracing.step_account("test/optax"):
        jax.jit(tx.update)(grads[0], state, params)
    assert tracing.optimizer_fused("test/optax") is None
    chained = optax.chain(optax.clip_by_global_norm(1.0), tx)
    assert not hasattr(chained, "update_in_place")


def test_an_empty_tree_is_an_empty_step():
    tx = mp_adamw(1e-3, master_dtype="bfloat16")
    params, state, sums = tx.update_in_place({}, tx.init({}), {})
    assert params == {} and int(state.count) == 1
    assert sums == ([], [], [], [])
    updates, state = tx.update({}, tx.init({}), {})
    assert updates == {} and int(state.count) == 1
